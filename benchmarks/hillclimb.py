"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> compare,
on the three chosen (arch x shape) pairs.

  P1 qwen3-4b x train_4k      — most representative of the paper's technique
  P2 moonshot x train_4k      — most collective-bound (MoE all-to-all)
  P3 mixtral x decode_32k     — collective-bound decode (weight gathers)

Each experiment re-lowers with a config/layout variant and reports the
three roofline terms + peak memory.  Results land in hillclimb_results.jsonl
and EXPERIMENTS.md §Perf.
"""
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# dryrun selects 512 emulated CPU devices when imported, before jax loads
from repro.launch import dryrun                      # noqa: E402

PEAK_FLOPS, HBM_BW, LINK_BW = 197e12, 819e9, 50e9
OUT = os.path.join(os.path.dirname(__file__), "..", "hillclimb_results.jsonl")


def run(tag, arch, shape, *, cfg_patch=None, layout_patch=None):
    import repro.configs.registry as reg
    base_get = dryrun.get
    if cfg_patch:
        cfg0 = base_get(arch)
        patched = dataclasses.replace(cfg0, **cfg_patch(cfg0))
        dryrun.get = lambda a: patched if a == arch else base_get(a)
    base_build = dryrun.build_layout
    if layout_patch:
        def build2(a, s, mp, st):
            lay = base_build(a, s, mp, st)
            return dataclasses.replace(lay, **layout_patch)
        dryrun.build_layout = build2
    try:
        r = dryrun.lower_one(arch, shape, multi_pod=False)
    finally:
        dryrun.get = base_get
        dryrun.build_layout = base_build
    if r["status"] != "OK":
        print(f"{tag}: {r['status']} {r.get('error','')[:200]}")
        return None
    terms = {
        "compute_s": r["cost"]["flops"] / PEAK_FLOPS,
        "memory_s": r["cost"]["bytes_accessed"] / HBM_BW,
        "collective_s": r["collectives"]["bytes_per_device"] / LINK_BW,
        "peak_gib": r["memory"]["peak_gib"],
        "comm_gib": r["collectives"]["bytes_per_device"] / 2**30,
    }
    rec = {"tag": tag, "arch": arch, "shape": shape, **terms,
           "by_kind": {k: v / 2**30 for k, v in
                       r["collectives"]["by_kind"].items()}}
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(f"{tag:42s} comp={terms['compute_s']:.3f}s mem={terms['memory_s']:.3f}s "
          f"coll={terms['collective_s']:.3f}s peak={terms['peak_gib']:.2f}GiB",
          flush=True)
    return rec


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("p1", "all"):
        run("P1.base qwen3 train", "qwen3-4b", "train_4k")
        run("P1.i1 no-remat", "qwen3-4b", "train_4k",
            cfg_patch=lambda c: {"remat": False})
        run("P1.i2 gspmd-linears", "qwen3-4b", "train_4k",
            layout_patch={"gspmd_linears": True})
    if which in ("p2", "all"):
        run("P2.base moonshot train", "moonshot-v1-16b-a3b", "train_4k")
        run("P2.i1 capacity 1.0", "moonshot-v1-16b-a3b", "train_4k",
            cfg_patch=lambda c: {"moe": dataclasses.replace(
                c.moe, capacity_factor=1.0)})
        run("P2.i2 no-remat", "moonshot-v1-16b-a3b", "train_4k",
            cfg_patch=lambda c: {"remat": False})
    if which in ("p3", "all"):
        run("P3.base mixtral decode", "mixtral-8x7b", "decode_32k")
        run("P3.i1 inference-opt weights", "mixtral-8x7b", "decode_32k",
            layout_patch={"inference_opt": True})
    if which == "p3x":
        run("P3.i2 deepseek decode inference-opt", "deepseek-v3-671b",
            "decode_32k", layout_patch={"inference_opt": True})
        run("P3.i2base deepseek decode", "deepseek-v3-671b", "decode_32k")


if __name__ == "__main__":
    main()
