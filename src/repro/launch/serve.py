"""Serving launcher: ``python -m repro.launch.serve --arch qwen3-4b --reduced
--requests 8`` — builds the continuous-batching engine (paged KV cache +
chunked prefill for the attention families), submits synthetic requests and
reports the serving metrics (TTFT / TPOT p50/p95, tok/s).  The same
entrypoint drives a TPU slice (set --dp/--model); the plan is validated
with mode='serve' so illegal compositions (pipeline stages at inference)
fail before any device work.  Exits nonzero when no tokens were produced,
so CI smoke runs can assert liveness by exit code.  ``main`` returns the
engine, the requests and the summary for callers that inspect them.
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--strategy", default="3d", choices=["3d", "2d", "1d"])
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample from the k most likely tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass (0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine PRNG seed (temperature > 0 reproducible)")
    ap.add_argument("--priority", type=int, default=0,
                    help="submit every Nth request on the priority queue "
                         "(0 = all FIFO)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV cache block size (tokens per block)")
    ap.add_argument("--prefill-chunk", type=int, default=4096,
                    help="max padded tokens per chunked-prefill step")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="seed-style sequential prefill (one prompt token "
                         "per engine step) — the throughput baseline")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--inference-opt", action="store_true",
                    help="x-replicated decode weights (zero per-token gathers)")
    ap.add_argument("--no-fused-decode", action="store_true",
                    help="paged decode via gather_view materialization "
                         "instead of the fused block-table kernel path")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=False,
                    help="shared-prefix KV reuse: prompts whose prefix is "
                         "resident enter by block reference (copy-on-write "
                         "on partial-block divergence)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--draft", default="",
                    help="draft model arch for speculative decoding (runs "
                         "single-device; greedy output stays bit-identical "
                         "to the non-speculative engine)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="draft tokens proposed per speculative step (γ)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common tokens to every "
                         "synthetic request (exercises the prefix cache)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU emulation: run on the CPU platform split into "
                         "N host devices (set before JAX is imported)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace of the run here (plus a "
                         "<path>.jsonl event log): one lane per request "
                         "(queue/prefill/decode spans) + the engine lane")
    args = ap.parse_args(argv)

    from repro.launch.runtime import emulate_host_devices, enable_compile_cache
    if args.host_devices:
        emulate_host_devices(args.host_devices)

    import dataclasses
    import jax
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.plan import ParallelPlan
    from repro.models import registry, transformer
    from repro.serve import Engine, Request
    from repro.serve.metrics import format_summary
    from repro.checkpoint import store

    enable_compile_cache()
    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    draft_cfg = None
    if args.draft:
        draft_cfg = get(args.draft)
        if args.reduced:
            draft_cfg = reduced(draft_cfg)
    plan = ParallelPlan(n_dp=args.dp, n_model=args.model,
                        strategy=args.strategy)
    plan.validate(n_layers=cfg.n_layers, model=cfg, mode="serve",
                  draft=draft_cfg)
    layout = plan.build()
    if args.inference_opt:
        layout = dataclasses.replace(layout, inference_opt=True)
    print(f"serving {cfg.arch}{' (reduced)' if args.reduced else ''} on "
          f"{layout.n_devices} devices, cube={layout.cube}, "
          f"cache={registry.serve_cache_mode(cfg)}")

    params = transformer.init(cfg, layout, jax.random.key(0))
    if args.ckpt_dir:
        last = store.latest_step(args.ckpt_dir)
        if last >= 0:
            params, _, _ = store.restore(
                args.ckpt_dir, last,
                transformer.abstract_params(cfg, layout), layout)
            print(f"restored checkpoint step {last}")

    draft = None
    if draft_cfg is not None:
        from repro.core.topology import single_device_layout
        from repro.serve.speculate import DraftSpec
        dlay = single_device_layout(args.strategy)
        dparams = transformer.init(draft_cfg, dlay, jax.random.key(0))
        draft = DraftSpec(draft_cfg, dlay, dparams, gamma=args.spec_tokens)
        print(f"draft: {draft_cfg.arch} (single-device), "
              f"gamma={args.spec_tokens}")

    from repro.obs import make_tracer
    tracer = make_tracer(bool(args.trace))
    eng = Engine(cfg, layout, params, batch_size=args.batch_size,
                 max_len=args.max_len, temperature=args.temperature,
                 top_k=args.top_k, top_p=args.top_p, seed=args.seed,
                 block_size=args.block_size,
                 prefill_chunk=args.prefill_chunk,
                 chunked_prefill=not args.no_chunked_prefill,
                 fused_decode=not args.no_fused_decode,
                 prefix_cache=args.prefix_cache, draft=draft, tracer=tracer)
    common = [3 + j % 13 for j in range(args.shared_prefix)]
    reqs = [Request(uid=i,
                    prompt=common + [2 + (i + j) % 17
                                     for j in range(3 + i % 5)],
                    max_new=args.max_new,
                    priority=(1 if args.priority and i % args.priority == 0
                              else 0))
            for i in range(args.requests)]
    stats = eng.run(reqs)
    for r in reqs[:4]:
        tag = f" [rejected: {r.error}]" if r.error else ""
        print(f"  req {r.uid}: {len(r.prompt)} prompt -> {r.out}{tag}")
    print(format_summary(stats))
    if args.trace:
        tracer.write_chrome(args.trace)
        tracer.write_jsonl(args.trace + ".jsonl")
        print(f"trace: wrote {args.trace} (+ {args.trace}.jsonl)")
    if stats["tokens"] <= 0:
        sys.exit("no tokens generated")
    return eng, reqs, stats


if __name__ == "__main__":
    main()
