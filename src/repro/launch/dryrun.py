from repro.launch.runtime import emulate_host_devices
emulate_host_devices(512)
# The two lines above MUST run before any jax import: the dry-run (and only
# the dry-run) builds the production mesh from 512 placeholder CPU devices.

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.config import SHAPES, OptimConfig, Family          # noqa: E402
from repro.configs.registry import ARCH_IDS, LONG_OK, cube_for, get  # noqa: E402
from repro.core.params import abstract_arrays                 # noqa: E402
from repro.launch.mesh import (make_framework_layout,         # noqa: E402
                               make_production_mesh, shape_layout_args)
from repro.models import transformer                          # noqa: E402
from repro.optim import opt_state_abstract                    # noqa: E402
from repro.train.step import (make_decode_step,               # noqa: E402
                              make_prefill_step, make_train_step)

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
               "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collective_stats(hlo: str):
    """Per-device communication bytes from the post-SPMD HLO, using ring
    formulas: AG/RS/A2A move size*(n-1)/n, AR moves 2*size*(n-1)/n, CP size."""
    defs = {}
    per_op = {c: 0.0 for c in COLLECTIVES}
    count = {c: 0 for c in COLLECTIVES}
    for line in hlo.splitlines():
        m = _DEF_RE.match(line)
        if m:
            defs[m.group(1)] = (m.group(2), m.group(3))
        kind = None
        for c in COLLECTIVES:
            if f" {c}(" in line or f" {c}-start(" in line:
                kind = c
                break
        if kind is None or m is None:
            continue
        out_bytes = _shape_bytes(m.group(2), m.group(3))
        # group size from replica_groups
        n = 2
        g2 = _GROUPS2_RE.search(line)
        g1 = _GROUPS_RE.search(line)
        if g2:
            n = int(g2.group(2))
        elif g1:
            first = g1.group(1).split("}")[0].lstrip("{")
            n = max(2, len([t for t in first.split(",") if t.strip() != ""]))
        if kind == "all-gather":
            moved = out_bytes * (n - 1) / n
        elif kind == "all-reduce":
            moved = 2 * out_bytes * (n - 1) / n
        elif kind == "reduce-scatter":
            moved = out_bytes * (n - 1)          # input = out*n; moves in*(n-1)/n
        elif kind == "all-to-all":
            moved = out_bytes * (n - 1) / n
        else:  # collective-permute
            moved = out_bytes
        per_op[kind] += moved
        count[kind] += 1
    total = sum(per_op.values())
    return {"bytes_per_device": total, "by_kind": per_op, "counts": count}


def build_layout(arch: str, shape_name: str, multi_pod: bool, strategy: str,
                 n_pp: int = 1, microbatches: int = 1, zero_stage: int = 1):
    args = shape_layout_args(shape_name, multi_pod)
    cube = cube_for(arch, 16, strategy)
    lay = make_framework_layout(multi_pod=multi_pod, strategy=strategy,
                                cube=cube, n_pp=n_pp,
                                microbatches=microbatches,
                                zero_stage=zero_stage, **args)
    # drop batch axes that exceed the global batch
    shape = SHAPES[shape_name]
    bax = []
    prod = 1
    for a in args["batch_axes"]:
        if prod * lay.size(a) <= shape.global_batch:
            bax.append(a)
            prod *= lay.size(a)
    import dataclasses
    return dataclasses.replace(lay, batch_axes=tuple(bax))


def memory_model(cfg, layout, shape, opt_cfg):
    """Analytic per-device memory breakdown under the layout's specs.

    Reports param, grad, optimizer, and activation bytes as separate
    components (the optimizer line was previously missing entirely), plus
    the replicated-optimizer baseline so the ZeRO savings are visible:

      * params      — model weights, sharded per their own specs (MoE expert
                      tables, SSM projections etc. come from the family's
                      real parameter tree, per pipeline stage when pp > 1).
      * grads       — the f32 accumulation buffer when microbatching (param
                      dtype otherwise); dp-sharded under zero_stage >= 2.
      * opt         — Adam m/v (f32) or Adafactor stats, dp-sharded under
                      zero_stage >= 1 (~1/dp of the replicated baseline).
      * act (est.)  — per-family per-layer activation + state bytes from
                      the BlockStack registry (dense: one bf16 residual;
                      MoE: + capacity-padded dispatch buffers; Mamba/xLSTM:
                      + expanded projections and f32 recurrent state;
                      audio: + the encoder-state pipeline carry), per
                      resident stage slot; a rough lower bound (remat keeps
                      ~1 checkpoint/block).
    """
    import dataclasses as _dc
    import math as _math
    from repro.core.params import sharded_bytes, tree_map_params
    from repro.models import registry as model_registry
    from repro.optim.optimizers import zero_partition_spec

    abstract = transformer.abstract_params(cfg, layout)
    zs = layout.effective_zero_stage()
    m = max(layout.microbatches, 1)
    param_b = sharded_bytes(abstract, layout)

    def grad_param(p):
        spec = zero_partition_spec(p, layout) if zs >= 2 else p.spec
        return _dc.replace(p, spec=spec,
                           dtype="float32" if m > 1 else p.dtype)
    grad_b = sharded_bytes(tree_map_params(grad_param, abstract), layout)
    opt_b = sharded_bytes(opt_state_abstract(abstract, layout, opt_cfg),
                          layout)
    lay0 = _dc.replace(layout, zero_stage=0)
    opt_b0 = sharded_bytes(opt_state_abstract(abstract, lay0, opt_cfg), lay0)

    stack = model_registry.get_stack(cfg.family)
    bsh = _math.prod(layout.size(a) for a in layout.batch_axes) or 1
    ssh = _math.prod(layout.size(a) for a in layout.seq_axes) \
        * layout.size("y")
    b_dev = max(shape.global_batch / m / bsh, 1)
    s_dev = shape.seq_len / ssh
    n_blocks = len(stack.layer_plan(cfg))
    resident = -(-n_blocks // layout.n_stages)       # stage slots (ceil)
    act_b = (resident * stack.act_bytes(cfg, layout, b_dev, s_dev)
             + stack.carry_bytes(cfg, layout, b_dev))
    return {
        "zero_stage": zs,
        "param_gib": param_b / 2**30,
        "grad_gib": grad_b / 2**30,
        "opt_gib": opt_b / 2**30,
        "opt_replicated_gib": opt_b0 / 2**30,
        "opt_savings_x": round(opt_b0 / max(opt_b, 1), 2),
        "act_est_gib": act_b / 2**30,
    }


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              strategy: str = "3d", compile_: bool = True,
              force_window: int = 0, n_pp: int = 1, microbatches: int = 1,
              zero_stage: int = 1, overlap: bool = False,
              overlap_chunks: int = 4):
    cfg = get(arch)
    if force_window and not cfg.window:
        # sliding-window VARIANT of a full-attention arch: makes long_500k
        # applicable (the spec's dense-arch carve-out); reported as
        # "<arch>+swa", never as the assigned config itself.
        import dataclasses as _dc
        cfg = _dc.replace(cfg, window=force_window)
        arch_tag = arch + "+swa"
    else:
        arch_tag = arch
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and arch not in LONG_OK and not cfg.window:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "SKIP", "reason": "full quadratic attention; "
                "sub-quadratic required (DESIGN.md §4)"}
    if n_pp > 1 and shape.kind != "train":
        from repro.core.plan import pipeline_mode_error
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "SKIP",
                "reason": pipeline_mode_error(n_pp, shape.kind)}
    if n_pp > 1:
        # every family pipelines through the BlockStack registry; the only
        # remaining rejections are config-level (mtp head, too few blocks)
        from repro.models.registry import pipeline_unsupported_reason
        reason = pipeline_unsupported_reason(cfg, n_pp)
        if reason:
            return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                    "status": "SKIP", "reason": reason}
    layout = build_layout(arch, shape_name, multi_pod, strategy, n_pp,
                          microbatches, zero_stage)
    if overlap:
        import dataclasses as _dc
        layout = _dc.replace(layout, overlap=True,
                             overlap_chunks=overlap_chunks)
    specs = transformer.input_specs(cfg, layout, shape)
    params = abstract_arrays(transformer.abstract_params(cfg, layout), layout)

    t0 = time.time()
    if shape.kind == "train":
        opt_cfg = OptimConfig(name="adafactor" if arch == "deepseek-v3-671b"
                              else "adamw")
        opt = abstract_arrays(
            opt_state_abstract(transformer.abstract_params(cfg, layout),
                               layout, opt_cfg), layout)
        step = make_train_step(cfg, layout, opt_cfg)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt, *specs)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, layout)
        lowered = jax.jit(step).lower(params, *specs)
    else:
        step = make_decode_step(cfg, layout)
        batch, cache = specs
        lowered = jax.jit(step, donate_argnums=(2,)).lower(params, batch, cache)
    t_lower = time.time() - t0

    res = {"arch": arch_tag, "shape": shape_name, "multi_pod": multi_pod,
           "strategy": strategy, "status": "LOWERED",
           "mesh": dict(layout.mesh.shape), "t_lower_s": round(t_lower, 1)}
    if n_pp > 1:
        from repro.core.pipeline import pipeline_report
        res["pipeline"] = pipeline_report(n_pp, microbatches)
    if shape.kind == "train":
        res["memory_model"] = memory_model(cfg, layout, shape, opt_cfg)
    if not compile_:
        return res

    t0 = time.time()
    compiled = lowered.compile()
    res["t_compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    res["memory"] = {
        "argument_gib": mem.argument_size_in_bytes / 2**30,
        "output_gib": mem.output_size_in_bytes / 2**30,
        "temp_gib": mem.temp_size_in_bytes / 2**30,
        "alias_gib": mem.alias_size_in_bytes / 2**30,
        "peak_gib": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30,
    }
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    # XLA's cost_analysis counts while bodies once; HloCost multiplies
    # in-loop dots/collectives/outputs by their trip counts (scan layers).
    from repro.launch.hlo_cost import HloCost
    hc = HloCost(compiled.as_text())
    res["cost"] = {"flops": hc.flops(),
                   "bytes_accessed": hc.bytes_accessed(),
                   "xla_flops_raw": float(ca.get("flops", -1)),
                   "xla_bytes_raw": float(ca.get("bytes accessed", -1))}
    res["collectives"] = hc.collective_bytes()
    res["status"] = "OK"
    return res


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry run")
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + ["all"])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--strategy", default="3d", choices=["3d", "2d", "1d"])
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (carved out of the data axis)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="pipeline microbatches m (bubble = (pp-1)/m); "
                         "default: 8 when --pp > 1, else 1 (the seed's "
                         "single-shot train step)")
    ap.add_argument("--zero", type=int, default=-1, choices=[-1, 0, 1, 2],
                    help="ZeRO stage for the optimizer-state memory model "
                         "and lowering (0 replicated, 1 sharded m/v, 2 + "
                         "sharded grad accumulation); default: auto (1)")
    ap.add_argument("--overlap", action="store_true",
                    help="async-TP: chunked 3-D island collectives overlapping"
                         " the partial matmuls (strategy=3d only)")
    ap.add_argument("--overlap-chunks", type=int, default=4,
                    help="chunks per overlapped island matmul")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--force-window", type=int, default=0,
                    help="run a sliding-window VARIANT of full-attention archs")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    args = ap.parse_args()

    if not args.microbatch:
        args.microbatch = 8 if args.pp > 1 else 1
    archs = ARCH_IDS if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape in (None, "all") else [args.shape]
    pods = []
    if args.single_pod or not args.multi_pod:
        pods.append(False)
    if args.multi_pod:
        pods.append(True)

    # sanity: the prescribed production mesh builds
    for mp in pods:
        mesh = make_production_mesh(multi_pod=mp)
        print(f"production mesh multi_pod={mp}: {dict(mesh.shape)}", flush=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch} x {shape} x {'2pod' if mp else '1pod'} [{args.strategy}]"
                if args.pp > 1:
                    tag += f" pp={args.pp} m={args.microbatch}"
                if args.zero >= 0:
                    tag += f" zero={args.zero}"
                try:
                    res = lower_one(arch, shape, multi_pod=mp,
                                    strategy=args.strategy,
                                    compile_=not args.lower_only,
                                    force_window=args.force_window,
                                    n_pp=args.pp,
                                    microbatches=args.microbatch,
                                    zero_stage=1 if args.zero < 0
                                    else args.zero,
                                    overlap=args.overlap,
                                    overlap_chunks=args.overlap_chunks)
                except Exception as e:
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "strategy": args.strategy, "status": "FAIL",
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                line = f"{tag:60s} {res['status']}"
                if res["status"] == "OK":
                    line += (f" peak={res['memory']['peak_gib']:.2f}GiB"
                             f" flops={res['cost']['flops']:.3e}"
                             f" comm={res['collectives']['bytes_per_device']/2**30:.3f}GiB"
                             f" (lower {res['t_lower_s']}s compile {res['t_compile_s']}s)")
                if "pipeline" in res:
                    pl = res["pipeline"]
                    line += (f" bubble={pl['bubble_fraction']:.3f}"
                             f" eff={pl['efficiency']:.3f}")
                elif res["status"] == "SKIP":
                    line += f" ({res['reason']})"
                print(line, flush=True)
                if "memory_model" in res:
                    mm = res["memory_model"]
                    for part, key in (("params", "param_gib"),
                                      ("grads", "grad_gib"),
                                      ("opt", "opt_gib"),
                                      ("act(est)", "act_est_gib")):
                        note = ""
                        if part == "opt":
                            rep = mm["opt_replicated_gib"]
                            note = (f"  [replicated {rep:.3f} GiB -> "
                                    f"{mm['opt_savings_x']}x saved, "
                                    f"zero={mm['zero_stage']}]")
                        print(f"    mem/device {part:8s} "
                              f"{mm[key]:9.3f} GiB{note}", flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(res) + "\n")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
