"""Training launcher: ``python -m repro.launch.train --arch tinyllama-1.1b
--steps 100 --dp 2 --model 4 ...``.

On a TPU host it builds the mesh from the attached chips; ``--host-devices N``
emulates an N-device mesh on the CPU instead (the layout factory is identical).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--strategy", default="3d", choices=["3d", "2d", "1d"])
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (n_layers must divide)")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(the pipeline's m when --pp > 1)")
    ap.add_argument("--zero", type=int, default=-1,
                    help="ZeRO stage for optimizer-state sharding over dp: "
                         "0 = replicated, 1 = shard Adam m/v 1/dp, 2 = also "
                         "keep the grad-accumulation buffer dp-sharded; "
                         "default: auto (1 when --dp > 1, else 0)")
    ap.add_argument("--overlap", action="store_true",
                    help="async-TP: chunk the 3-D island collectives so "
                         "all_gather/psum_scatter overlap the partial matmuls")
    ap.add_argument("--overlap-chunks", type=int, default=4,
                    help="chunks per overlapped island matmul (divisor-"
                         "clamped to the local contraction size)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduced variant")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default="")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU emulation: run on the CPU platform split into "
                         "N host devices (set before JAX is imported)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace of the run here (plus a "
                         "<path>.jsonl event log; docs/observability.md)")
    ap.add_argument("--telemetry", default="",
                    help="per-step telemetry (step time / tokens/s / MFU / "
                         "memory watermarks / non-finite sentinel); writes "
                         "the summary JSON here.  NOTE: syncs every step")
    ap.add_argument("--peak-flops", type=float, default=0,
                    help="per-device peak FLOP/s for the MFU denominator "
                         "(default: the published peak of the device kind; "
                         "required on a kind without one, e.g. the CPU)")
    args = ap.parse_args(argv)

    from repro.launch.runtime import emulate_host_devices, enable_compile_cache
    if args.host_devices:
        emulate_host_devices(args.host_devices)

    import jax
    import jax.numpy as jnp
    from repro.config import OptimConfig, ShapeConfig, reduced
    from repro.configs.registry import get
    from repro.core.params import count_params
    from repro.core.plan import ParallelPlan
    from repro.data.pipeline import DataConfig, TokenStream
    from repro.models import transformer
    from repro.optim import make_optimizer
    from repro.train.step import make_train_step
    from repro.checkpoint import store
    from repro.obs import make_tracer
    from repro.obs.telemetry import TrainTelemetry

    enable_compile_cache()
    tracer = make_tracer(bool(args.trace))

    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    changes = {}
    if args.layers:
        changes["n_layers"] = args.layers
    if args.d_model:
        changes["d_model"] = args.d_model
    if changes:
        cfg = dataclasses.replace(cfg, **changes)

    plan = ParallelPlan(n_dp=args.dp, n_model=args.model,
                        strategy=args.strategy, n_stages=args.pp,
                        microbatches=args.microbatch,
                        zero_stage=None if args.zero < 0 else args.zero,
                        overlap=args.overlap,
                        overlap_chunks=args.overlap_chunks)
    # family-aware plan-time validation: unsupported compositions (mtp+pp,
    # serve-mode pp, too-shallow stacks) fail here with a precise message
    plan.validate(n_layers=cfg.n_layers, global_batch=args.batch, model=cfg)
    layout = plan.build()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    opt_cfg = OptimConfig(name=args.optimizer, lr=args.lr, warmup=args.warmup,
                          total_steps=args.steps)

    tel = None
    if args.telemetry:
        tel = TrainTelemetry(
            cfg, layout, global_batch=args.batch, seq_len=args.seq,
            peak_flops_per_device=args.peak_flops or None,
            tracer=tracer)

    print(f"arch={cfg.arch} layers={cfg.n_layers} d={cfg.d_model} "
          f"mesh={dict(layout.mesh.shape)} plan={plan.describe()}")
    params = transformer.init(cfg, layout, jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M")

    from repro.optim.optimizers import opt_state_abstract
    from repro.core.params import init_params
    opt_state = init_params(
        opt_state_abstract(transformer.abstract_params(cfg, layout), layout,
                           opt_cfg), jax.random.key(1), layout=layout)
    step_fn = jax.jit(make_train_step(cfg, layout, opt_cfg),
                      donate_argnums=(0, 1))

    start = 0
    if args.ckpt_dir:
        last = store.latest_step(args.ckpt_dir)
        if last >= 0:
            print(f"restoring step {last} from {args.ckpt_dir}")
            params, opt_state, extra = store.restore(
                args.ckpt_dir, last, params, layout, opt_state)
            start = last

    data = TokenStream(cfg, layout, shape,
                       DataConfig(kind=args.data, path=args.data_path))
    it = iter(data)
    t0 = time.time()
    losses = []
    for step in range(start, args.steps):
        with tracer.span("data_next", track="train"):
            batch = next(it)
        with tracer.span("train_step", track="train", step=step) as sp:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if tel is not None:
                # telemetry's step clock needs device time, so the span
                # opts into a sync point; trace-only runs stay async
                sp.sync(metrics["loss"])
        if tel is not None:
            tel.record(step, metrics)
            if tel.nonfinite is not None and "blame" not in tel.nonfinite:
                tel.nonfinite["blame"] = tel.blame(params)
                print(f"non-finite loss at step {step+1}: "
                      f"{tel.nonfinite['blame']}", file=sys.stderr)
        if (step + 1) % args.log_every == 0 or step == start:
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = (time.time() - t0) / max(step - start + 1, 1)
            print(f"step {step+1:5d} loss={loss:8.4f} "
                  f"xent={float(metrics['xent']):8.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['gnorm']):7.3f} "
                  f"{dt:6.2f}s/step", flush=True)
        if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            d = store.save(args.ckpt_dir, step + 1, params, opt_state,
                           layout=layout)
            print(f"saved {d}")
    if losses:
        print(f"done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    else:
        # checkpoint restore already at/after --steps: the loop never ran
        print(f"nothing to do: restored step {start} >= --steps {args.steps}")
    if tel is not None:
        tel.write(args.telemetry)
        print(tel.format_summary(), flush=True)
        print(f"telemetry: wrote {args.telemetry}")
    if args.trace:
        tracer.write_chrome(args.trace)
        tracer.write_jsonl(args.trace + ".jsonl")
        print(f"trace: wrote {args.trace} (+ {args.trace}.jsonl)")
    return losses


if __name__ == "__main__":
    main()
