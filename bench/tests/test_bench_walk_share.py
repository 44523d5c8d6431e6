"""decode_walk_share.serve against a hand count: the tiny cell's engine
runs on the CPU under the profiler, every decode program it dispatches is
counted from its own positions and active rows through the kernel's
``live_blocks``, and the reader of the traced spans must agree."""
import dataclasses

import numpy as np
import pytest

from bench import run as harness
from bench import spec, weights
from bench.drivers import serve_open_loop as drv
from bench.metrics import _program
from bench.tests.test_bench_program_spans import _traced
from bench.tests.tiny import tiny_cell

NAME = "decode_walk_share.serve"


def read(ctx):
    return harness.load_metric(NAME).read(ctx)


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The reader's context of one traced run, and (walked, blocks) of
    each decode program the engine dispatched in it."""
    from jax.profiler import ProfileData
    from repro.kernels.paged_decode import live_blocks
    from repro.models import transformer
    from repro.serve.engine import Engine
    cell = tiny_cell("qwen3-4b.serve.chat")
    mix, conf = cell.traffic, cell.config
    cfg = spec.model_config(conf, cell.config_name)
    layout = drv.make_layout(1)
    params = weights.make(transformer.abstract_params(cfg, layout), layout,
                          7, conf["config"]["initializer_range"])
    eng = Engine(cfg, layout, params, batch_size=mix["slots"],
                 max_len=mix["max_len"], block_size=mix["block"],
                 prefill_chunk=mix["prefill_chunk"],
                 temperature=mix["temperature"], seed=7)
    drv.warm_up(eng, mix, cfg.vocab)
    counts = []
    decode = eng._decode

    def counted(params, pool, tok, pos, tables, active, key):
        live = live_blocks(np.asarray(pos), np.asarray(active),
                           block=eng.kv.block, nb=tables.shape[1])
        counts.append((int(live.sum()), tables.size))
        return decode(params, pool, tok, pos, tables, active, key)

    eng._decode = counted
    pd = ProfileData.from_file(_traced(
        eng, [5, 20, 12, 24, 9, 17], tmp_path_factory.mktemp("walk"),
        cfg.vocab))
    return {"program": _program.from_profile(pd)}, counts


def test_walk_share_equals_the_hand_count(walked):
    ctx, counts = walked
    assert len(counts) >= 2
    hand = 100.0 * sum(w for w, _ in counts) / sum(b for _, b in counts)
    assert 0.0 < hand < 100.0
    assert read(ctx) == pytest.approx(hand)


def test_walk_share_silent_without_its_args(walked):
    # the parent's engine records decode serve.prepare spans without them
    ctx, _ = walked
    bare = [dataclasses.replace(s, args={k: v for k, v in s.args.items()
                                         if k not in ("walked", "blocks")})
            for s in ctx["program"]]
    assert any(s.name == "serve.prepare" for s in bare)
    assert read({"program": bare}) is None
    assert read({"program": []}) is None
