"""Open-loop serving: requests arrive on a schedule from ``generator`` and
enter the program's continuous-batching engine (``Engine.submit`` /
``Engine.step``) whether or not it keeps up.

Set-up makes the weights from the seed, builds the engine, warms up the
decode program and the prefill program of every padding bucket the mix can
reach, then runs the open loop for ``ramp_s`` seconds so that the requests
in flight reach what the rate implies.  The window is the next
``seconds`` seconds of the same loop.  After it, the engine is freed and a
sample of finished requests is replayed through the float32 reference.

End-to-end metrics, all over the window:

* ``ttft_p90_ms``: 90th percentile over every request due in the window of
  (first token's time - scheduled arrival); a request with no first token
  by the close counts its wait so far;
* ``itl_mean_ms``: mean of every gap between consecutive output tokens
  whose later token falls in the window, prefill stalls included.  A
  prefill holds up every running request at once, and at the chat mix's
  rate such stalls make 5 to 10% of the gaps, so a high percentile falls
  now on a decode gap and now on a stall; the mean weighs both as often
  as they come.
* ``serve_tokens_per_s``: output tokens emitted in the window / window.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from .. import generator, spec, weights


def make_layout(chips: int, devices=None):
    from repro.core.plan import ParallelPlan
    return ParallelPlan(n_model=chips).build(devices)


def prefill_buckets(mix: dict) -> List[int]:
    """Padded prompt lengths the mix's prompts can reach."""
    from repro.serve.scheduler import pad_bucket
    p = mix["prompt"]
    return sorted({pad_bucket(n) for n in range(p["min"], p["max"] + 1)})


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile (the q-th of the sorted sample)."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of an empty sample")
    k = max(0, min(len(s) - 1, int(np.ceil(q / 100.0 * len(s))) - 1))
    return float(s[k])


@dataclasses.dataclass
class Track:
    due: generator.Due
    req: object
    times: List[float] = dataclasses.field(default_factory=list)


class OpenLoop:
    """Drives the engine from a schedule through its public calls
    (``submit``, ``step``); records each token's time, and before each step
    the requests that are decoding and the tokens they hold."""

    def __init__(self, eng, schedule, span):
        from repro.serve.engine import Request
        self.eng, self.span = eng, span
        self.pending = list(schedule)
        self.tracks: Dict[int, Track] = {}
        self.live: List[Track] = []          # submitted and not done
        self.Request = Request
        self.occupancy: List[tuple] = []     # (time, decoding, tokens held)
        self.t0 = None

    def run_until(self, t_end: float):
        eng = self.eng
        while True:
            now = time.perf_counter() - self.t0
            if now >= t_end:
                return
            while self.pending and self.pending[0].at <= now:
                d = self.pending.pop(0)
                with self.span("bench.submit"):
                    req = self.Request(uid=d.uid, prompt=d.prompt,
                                       max_new=d.max_new)
                    eng.submit(req)
                tr = Track(d, req)
                self.tracks[d.uid] = tr
                if not req.done:
                    self.live.append(tr)
            if not self.live:
                nxt = self.pending[0].at if self.pending else t_end
                with self.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, t_end) - now))
                continue
            dec = [tr for tr in self.live if tr.times]
            self.occupancy.append(
                (now, len(dec),
                 sum(len(tr.due.prompt) + len(tr.times) for tr in dec)))
            with self.span("bench.engine_step"):
                eng.step()
            t = time.perf_counter() - self.t0
            still = []
            for tr in self.live:
                new = len(tr.req.out) - len(tr.times)
                tr.times.extend([t] * new)
                if not tr.req.done:
                    still.append(tr)
            self.live = still


def window_metrics(loop: OpenLoop, w0: float, w1: float) -> dict:
    """Over the window [w0, w1): TTFTs of the requests due, gaps between
    tokens, tokens emitted; the context length of every token a decode
    produced (token i > 0 of a request attends over its prompt and i
    tokens); and the mean over engine steps of the requests decoding and
    the tokens they hold in the pool."""
    ttft, gaps, tokens, lengths = [], [], 0, []
    for tr in loop.tracks.values():
        ts = tr.times
        if w0 <= tr.due.at < w1:
            first = ts[0] if ts else w1
            ttft.append(min(first, w1) - tr.due.at)
        tokens += sum(w0 <= t < w1 for t in ts)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if w0 <= b < w1]
        p = len(tr.due.prompt)
        lengths += [p + i for i, t in enumerate(ts) if i and w0 <= t < w1]
    occ = [(n, k) for t, n, k in loop.occupancy if w0 <= t < w1]
    return {"ttft": ttft, "gaps": gaps, "tokens": tokens,
            "decode_lengths": lengths,
            "decoding_mean": float(np.mean([n for n, _ in occ])) if occ else 0.0,
            "pool_tokens_mean": float(np.mean([k for _, k in occ])) if occ else 0.0}


def warm_up(eng, mix: dict, vocab: int):
    """Run one request per prefill bucket, and a few decode steps, so that
    every program the window will call is compiled or loaded."""
    from repro.serve.engine import Request
    p = mix["prompt"]
    for i, b in enumerate(prefill_buckets(mix)):
        n = max(p["min"], min(b, p["max"]))
        req = Request(uid=-1 - i, prompt=[(7 * j) % vocab for j in range(n)],
                      max_new=3)
        eng.submit(req)
        while not req.done:
            eng.step()


def run(cell: spec.Cell, seed: int, seconds: float, harness) -> dict:
    """One run of the cell; ``harness`` gives the clock, spans and trace."""
    from repro.models import transformer
    from repro.serve.engine import Engine

    mix, conf = cell.traffic, cell.config
    cfg = spec.model_config(conf, cell.config_name)
    layout = make_layout(cell.chips)
    params = weights.make(transformer.abstract_params(cfg, layout), layout,
                          seed, conf["config"]["initializer_range"])
    eng = Engine(cfg, layout, params, batch_size=mix["slots"],
                 max_len=mix["max_len"], block_size=mix["block"],
                 prefill_chunk=mix["prefill_chunk"],
                 temperature=mix["temperature"], seed=seed & 0x7FFFFFFF)
    warm_up(eng, mix, cfg.vocab)
    sched = generator.schedule(mix, seed, seconds, cfg.vocab)
    loop = OpenLoop(eng, sched, harness.span)
    loop.t0 = time.perf_counter()
    w0 = mix["ramp_s"]
    loop.run_until(w0)
    setup_s = harness.setup_done()
    with harness.window():
        loop.run_until(w0 + seconds)
        w1 = time.perf_counter() - loop.t0     # before the trace is written
    m = window_metrics(loop, w0, w1)
    tracks = list(loop.tracks.values())
    counts = {
        "requests_due": sum(w0 <= tr.due.at < w1 for tr in tracks),
        "decode_lengths": m["decode_lengths"],
        "tokens": m["tokens"], "window_s": w1 - w0,
        "decoding_mean": m["decoding_mean"],
        "pool_tokens_mean": m["pool_tokens_mean"],
    }
    mem = harness.memory_peak()
    finished = [(tr.due.prompt, list(tr.req.out)) for tr in tracks
                if tr.req.done and not tr.req.error]
    failed = sum(bool(tr.req.error) for tr in tracks)
    del eng, loop, tracks
    gc.collect()                      # the engine's pool goes before the check
    t_check = time.perf_counter()
    check = check_served(conf, cell.limits, params, finished, seed,
                         mix["max_len"])
    check["seconds"] = time.perf_counter() - t_check
    return {"metrics": {"ttft_p90_ms": 1e3 * percentile(m["ttft"], 90),
                        "itl_mean_ms": 1e3 * float(np.mean(m["gaps"])),
                        "serve_tokens_per_s": m["tokens"] / (w1 - w0),
                        "setup_s": setup_s},
            "counts": counts, "check": check,
            "attempted": counts["requests_due"], "failed": failed,
            "memory": mem}


# ---------------------------------------------------------------------------
# correctness: served tokens against the float32 reference
# ---------------------------------------------------------------------------
def sample_finished(finished, n: int, seed: int):
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng(seed ^ 0xC4EC)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [finished[order[0]]] + [finished[rest[i]] for i in pick]


def _gap_fn(model: dict, control: bool):
    """Jitted: per served position, how far below the reference's best
    logit lies the served token (or, for the control, the token that the
    float8 reference puts first)."""
    import jax
    import jax.numpy as jnp
    from bench.reference import dense

    def f(params, tokens, served, mask):
        ref = dense.logits(model, params, tokens[None], "f32")[0]
        best = jnp.max(ref, -1)
        if control:
            served = jnp.argmax(dense.logits(model, params, tokens[None],
                                             "fp8")[0], -1)
        got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        return jnp.max(jnp.where(mask, best - got, -jnp.inf))

    return jax.jit(f)


def served_gaps(model: dict, params, sample, length: int,
                control: bool = False) -> List[float]:
    """Widest gap per sampled request, every sequence padded to
    ``length`` so that one program serves them all."""
    import jax
    f = _gap_fn(model, control)
    out = []
    for prompt, served in sample:
        seq = (list(prompt) + list(served))[:-1]
        tokens = np.zeros(length, np.int32)
        tokens[:len(seq)] = seq
        tgt = np.zeros(length, np.int32)
        mask = np.zeros(length, bool)
        p0 = len(prompt) - 1
        tgt[p0:p0 + len(served)] = served
        mask[p0:p0 + len(served)] = True
        out.append(float(jax.device_get(f(params, tokens, tgt, mask))))
    return out


def check_served(conf: dict, limits: dict, params, finished, seed: int,
                 length: int, control: bool = False) -> dict:
    """``correct`` when the weights hold exactly the leaves the
    configuration states and no sampled served token lies more than the
    limit below the reference's best logit."""
    from bench.reference import dense
    lim = limits["served_logit_gap"]["limit"]
    off = dense.leaves_off(conf["config"], params)
    sample = sample_finished(finished, limits["sample"], seed)
    value = None
    if sample and not off:
        value = max(served_gaps(conf["config"], params, sample, length,
                                control))
    return {"correct": value is not None and value <= lim,
            "numbers": {"param_leaves_off": {"value": len(off), "limit": 0},
                        "served_logit_gap": {"value": value, "limit": lim}},
            "leaves_off": off,
            "served_tokens": sum(len(s) for _, s in sample)}
