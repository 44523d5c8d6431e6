"""Readings that set a serve cell's limit, on the chip at the cell's own
size: the program's served-token gap over many seeds, the control's (the
reference computed in float8 in the program's place) on the same prompts
and tokens, and planted faults.  It is not part of a benchmark run.

    python3 bench/control.py --workload qwen3-4b.serve.chat --seeds 1 2 3 \
        --seconds 20 [--faults]

One process: the engine is built and warmed once; each seed brings its own
weights and schedule, runs the cell's open loop (ramp, then ``--seconds``),
and its finished requests are sampled as a benchmark run samples them.
Each seed prints one JSON line:

* ``program``: widest gap of a served token below the reference's best;
* ``control``: the same gap of the token the float8 reference puts first;
* ``token_altered``: the served tokens each moved to the next id, as a
  sampler that alters what it produces would serve them.

With ``--faults`` one more seed runs with the decode step's pool write
left out (the step returns its state unchanged), and prints its gap.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]


def run_seed(eng, cell, cfg, layout, seed, seconds):
    """The cell's open loop on the engine with the weights of ``seed``;
    the finished requests sampled as a benchmark run samples them."""
    from bench.drivers import serve_open_loop as drv
    from bench.sweep import open_loop, reseed
    reseed(eng, cell, cfg, layout, seed)
    loop, _, _, _ = open_loop(eng, cell.traffic, seed, seconds, cfg.vocab)
    finished = [(t.due.prompt, list(t.req.out)) for t in loop.tracks.values()
                if t.req.done and not t.req.error]
    return drv.sample_finished(finished, cell.limits["sample"], seed)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    from bench import spec, weights
    from bench.drivers import serve_open_loop as drv
    from bench.run import CACHE_DIR, device_check
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    dev = device_check(cell.chips)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR)
    from repro.models import transformer
    from repro.serve.engine import Engine
    mix, model = cell.traffic, cell.model
    cfg = spec.model_config(cell.config, cell.config_name)
    layout = drv.make_layout(cell.chips)
    params = weights.make(transformer.abstract_params(cfg, layout), layout,
                          args.seeds[0], model["initializer_range"])
    eng = Engine(cfg, layout, params, batch_size=mix["slots"],
                 max_len=mix["max_len"], block_size=mix["block"],
                 prefill_chunk=mix["prefill_chunk"],
                 temperature=mix["temperature"], seed=1)
    del params
    drv.warm_up(eng, mix, cfg.vocab)
    L = mix["max_len"]
    for seed in args.seeds:
        sample = run_seed(eng, cell, cfg, layout, seed, args.seconds)
        prog = drv.served_gaps(model, eng.params, sample, L)
        ctrl = drv.served_gaps(model, eng.params, sample, L, control=True)
        alt = [(p, [(t + 1) % cfg.vocab for t in s]) for p, s in sample]
        bad = drv.served_gaps(model, eng.params, alt, L)
        print(json.dumps({"seed": seed, "program": max(prog),
                          "control": max(ctrl), "token_altered": max(bad),
                          "per_request": {"program": prog, "control": ctrl},
                          "served_tokens": sum(len(s) for _, s in sample),
                          "device": dev}), flush=True)
    if args.faults:
        from repro.serve import kvcache
        kvcache.scatter_step = lambda pool, updates, phys: pool
        eng._build_paged()
        seed = args.seeds[-1] + 1
        sample = run_seed(eng, cell, cfg, layout, seed, args.seconds)
        gap = drv.served_gaps(model, eng.params, sample, L)
        print(json.dumps({"seed": seed, "state_unchanged": max(gap),
                          "per_request": gap, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
