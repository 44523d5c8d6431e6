"""Find the highest arrival rate a serve cell sustains: one sweep on the
chip, one process, the engine built and warmed once.

    python3 bench/sweep.py --workload qwen3-4b.serve.chat \
        --rates 0.5 0.6 0.7 --seeds 31 32 33 --seconds 51

For each seed the weights are made anew from it; for each rate the engine
is emptied, then the cell's open loop runs at that rate (its ramp, then
``--seconds``), and one JSON line reports the offered and completed output
tokens per second, the TTFT and inter-token tails, the requests waiting
for their first token at the window's open and close, and the mean over
engine steps of the requests decoding and the pool tokens they hold.  The
knee is the highest rate whose waiting requests do not grow over the
window and whose completed tokens reach 90% of the offered ones, on every
seed.  The cell's rate is then set to four fifths of it in its traffic
file, by hand.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]


def empty(eng):
    """Drop every queued and running request, freeing their blocks."""
    sch = eng.scheduler
    sch.fifo.clear()
    sch.prio.clear()
    sch.pending_prefill.clear()
    for i, r in enumerate(eng.slots):
        if r is not None:
            eng._finish(i)


def reseed(eng, cell, cfg, layout, seed: int):
    """Empty the engine and give it the weights of ``seed``."""
    from bench import weights
    from repro.models import transformer
    empty(eng)
    eng.params = None
    gc.collect()
    eng.params = weights.make(transformer.abstract_params(cfg, layout),
                              layout, seed, cell.model["initializer_range"])


def open_loop(eng, mix: dict, seed: int, seconds: float, vocab: int):
    """The cell's open loop on a running engine: ramp, then ``seconds``.
    Returns the loop and the window's bounds."""
    from bench import generator
    from bench.drivers import serve_open_loop as drv
    sched = generator.schedule(mix, seed, seconds, vocab)
    loop = drv.OpenLoop(eng, sched, lambda n: contextlib.nullcontext())
    loop.t0 = time.perf_counter()
    w0 = mix["ramp_s"]
    loop.run_until(w0)
    waiting0 = sum(not tr.times for tr in loop.live)
    loop.run_until(w0 + seconds)
    w1 = time.perf_counter() - loop.t0
    return loop, w0, w1, waiting0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    args = ap.parse_args(argv)
    from bench import generator, spec
    from bench.drivers import serve_open_loop as drv
    from bench.run import CACHE_DIR, device_check
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    dev = device_check(cell.chips)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR)
    from repro.serve.engine import Engine
    mix = cell.traffic
    cfg = spec.model_config(cell.config, cell.config_name)
    layout = drv.make_layout(cell.chips)
    eng = None
    for seed in args.seeds:
        if eng is None:
            from bench import weights
            from repro.models import transformer
            params = weights.make(transformer.abstract_params(cfg, layout),
                                  layout, seed,
                                  cell.model["initializer_range"])
            eng = Engine(cfg, layout, params, batch_size=mix["slots"],
                         max_len=mix["max_len"], block_size=mix["block"],
                         prefill_chunk=mix["prefill_chunk"],
                         temperature=mix["temperature"], seed=1)
            del params
            drv.warm_up(eng, mix, cfg.vocab)
        else:
            reseed(eng, cell, cfg, layout, seed)
        for rate in args.rates:
            empty(eng)
            m = dict(mix, rate_per_s=rate)
            sched = generator.schedule(m, seed, args.seconds, cfg.vocab)
            mean_out = sum(d.max_new for d in sched) / len(sched)
            loop, w0, w1, waiting0 = open_loop(eng, m, seed, args.seconds,
                                               cfg.vocab)
            r = drv.window_metrics(loop, w0, w1)
            due = [t for t in loop.tracks.values() if w0 <= t.due.at < w1]
            offered = rate * mean_out
            print(json.dumps({
                "seed": seed, "rate_per_s": rate,
                "offered_tokens_per_s": offered,
                "tokens_per_s": r["tokens"] / (w1 - w0),
                "done_share": r["tokens"] / (w1 - w0) / offered,
                "ttft_p50_ms": 1e3 * drv.percentile(r["ttft"], 50),
                "ttft_p90_ms": 1e3 * drv.percentile(r["ttft"], 90),
                "itl_mean_ms": 1e3 * sum(r["gaps"]) / len(r["gaps"]),
                "itl_p50_ms": 1e3 * drv.percentile(r["gaps"], 50),
                "itl_p90_ms": 1e3 * drv.percentile(r["gaps"], 90),
                "itl_p95_ms": 1e3 * drv.percentile(r["gaps"], 95),
                "waiting_open": waiting0,
                "waiting_close": sum(not tr.times for tr in loop.live),
                "due": len(due),
                "decoding_mean": r["decoding_mean"],
                "pool_tokens_mean": r["pool_tokens_mean"],
                "pool_tokens": mix["slots"] * mix["max_len"],
                "device": dev}), flush=True)


if __name__ == "__main__":
    main()
