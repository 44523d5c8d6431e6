"""The open-loop generator: deterministic by seed, the same work for every
seed, and the stated length distributions."""
import numpy as np

from bench import generator, spec

MIX = spec.load_json(spec.ROOT + "/bench/traffic/serve.chat.json")


def _sched(seed, seconds=51):
    return generator.schedule(MIX, seed, seconds, 151936)


def test_same_seed_same_requests():
    a, b = _sched(2 ** 31 + 5), _sched(2 ** 31 + 5)
    assert [(r.at, r.prompt, r.max_new) for r in a] == \
        [(r.at, r.prompt, r.max_new) for r in b]


def test_every_seed_offers_the_same_schedule_with_other_prompts():
    a, b = _sched(1), _sched(2 ** 33 + 2)
    assert len(a) == len(b) == round(MIX["rate_per_s"] * (MIX["ramp_s"] + 51))
    assert [(r.at, len(r.prompt), r.max_new) for r in a] == \
        [(r.at, len(r.prompt), r.max_new) for r in b]
    assert a[0].at == 0.0 and a[-1].at < MIX["ramp_s"] + 51
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


def test_lengths_follow_the_stated_lognormal():
    rng = np.random.default_rng(0)
    p = MIX["prompt"]
    x = generator.lognormal_lengths(rng, 20000, p)
    assert x.min() >= p["min"] and x.max() <= p["max"]
    assert abs(np.median(x) - p["median"]) / p["median"] < 0.03
    # the share clipped at the top is the lognormal's tail above the cap
    from math import erf, log, sqrt
    tail = 0.5 * (1 - erf(log(p["max"] / p["median"]) / p["sigma"] / sqrt(2)))
    assert abs(np.mean(x == p["max"]) - tail) < 0.01
    o = generator.lognormal_lengths(rng, 20000, MIX["output"])
    assert abs(np.median(o) - MIX["output"]["median"]) < 4


def test_prompt_ids_in_vocab_and_uniform():
    ids = np.concatenate([r.prompt for r in _sched(3)])
    assert ids.min() >= 0 and ids.max() < 151936
    assert abs(ids.mean() / 151936 - 0.5) < 0.02
