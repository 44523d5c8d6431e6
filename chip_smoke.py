"""Chip smoke test: the server and the trainer, once each, on a TPU, through
their normal entry points, at qwen3-4b's published widths with random
weights from a fixed seed.

    python chip_smoke.py             # one chip: serve (36 layers), then train
    python chip_smoke.py --chips 4   # four chips: 3-D cube vs 1-D train only

One chip: the serve phase runs 8 requests through the continuous-batching
engine and the fused paged decode, and fails unless every request generated
tokens and the compiled decode program holds the Pallas kernel
(``tpu_custom_call``).  Its arrays are then freed.  The train phase takes 3
AdamW steps of the model cut to 2 layers (batch 4 x 2048) and fails unless
every loss is finite.

Four chips: the same 2-layer train job under ``--model 4`` (the 3-D cube)
and under ``--model 4 --strategy 1d`` from the same init and data; the
per-step losses must agree within 1e-2 and no chip's peak memory may exceed
1.5x the least-loaded chip's.

The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits nonzero before any model work and prints no
such line.  Everything runs in this one process: a chip belongs to one
process at a time.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = ["--arch", "qwen3-4b"]
SERVE = ARCH + ["--requests", "8", "--batch-size", "8", "--max-len", "2048",
                "--max-new", "32"]
TRAIN = ARCH + ["--layers", "2", "--batch", "4", "--seq", "2048",
                "--steps", "3", "--log-every", "1"]


def fail(msg: str):
    sys.exit(f"chip_smoke: FAILED: {msg}")


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu":
        fail(f"needs a TPU, JAX found platform {dev['platform']!r}")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} devices, JAX found {len(devs)}")
    return dev


def serve_phase():
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    eng, reqs, stats = serve.main(SERVE)
    empty = [r.uid for r in reqs if not r.out]
    if empty:
        fail(f"serve: requests {empty} generated no tokens")
    print(f"serve: {stats['tokens']} tokens for {len(reqs)} requests",
          flush=True)
    B = eng.B
    hlo = eng._decode.lower(
        eng.params, eng.pool, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B,), jnp.int32), eng.kv.tables_device(),
        jnp.ones((B,), bool), jax.random.key(0)).compile().as_text()
    if "tpu_custom_call" not in hlo:
        fail("serve: the compiled decode program holds no Pallas kernel")
    print("serve: Pallas paged-decode kernel present in the compiled "
          "decode program", flush=True)


def release_check():
    import jax
    gc.collect()
    live = jax.live_arrays()
    if live:
        fail(f"{len(live)} arrays still live after the serve phase: "
             f"{[(a.shape, a.dtype) for a in live[:8]]}")
    print("release: no live arrays", flush=True)


def train(extra=()):
    from repro.launch import train as train_launcher
    losses = train_launcher.main(TRAIN + list(extra))
    if len(losses) != 3 or not all(math.isfinite(x) for x in losses):
        fail(f"train {' '.join(extra)}: losses {losses}")
    print(f"train {' '.join(extra) or '1 chip'}: losses {losses}",
          flush=True)
    return losses


def peak_bytes():
    import jax
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def four_chip_phase():
    runs = {}
    for name, extra in (("3d", ["--model", "4"]),
                        ("1d", ["--model", "4", "--strategy", "1d"])):
        runs[name] = train(extra)
        gc.collect()
        peaks = peak_bytes()
        print(f"{name}: peak_bytes_in_use per device {peaks}", flush=True)
        if max(peaks) > 1.5 * min(peaks):
            fail(f"{name}: peak bytes per device unbalanced {peaks}")
    diffs = [abs(a - b) for a, b in zip(runs["3d"], runs["1d"])]
    print(f"3d vs 1d loss diff per step {diffs}", flush=True)
    if max(diffs) > 1e-2:
        fail(f"3d and 1d losses differ by {max(diffs)} > 1e-2")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip 3-D vs 1-D train phase")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    dev = device_check(args.chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.runtime import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chip_phase()
    else:
        serve_phase()
        release_check()
        train()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
