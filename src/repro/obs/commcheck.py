"""Measured-vs-analytic communication accounting per parallel plan.

The paper's central claim is a communication-cost claim: per-device comm
volume for 1-D (Megatron) tensor parallelism stays O(1) in p, 2-D (Optimus)
falls as O(1/sqrt(p)), and the 3-D cube as O(1/p^(2/3)) — the tables in
docs/architecture.md.  Until now the repo stated those numbers only
analytically.  This module closes the loop:

  * **measured** — compile ``grad(forward)`` for a plan, parse the HLO with
    ``launch/hlo_cost.py`` (while-loop trip counts applied), and sum the
    ring-model bytes each collective moves per device.
  * **analytic** — the same alpha-beta per-matmul formulas as
    ``benchmarks/analytic.py`` (kept in sync by a tier-1 test; benchmarks/
    is not importable from src/), instantiated on the config's actual
    matmul shapes instead of the paper's 4h MLP.

``check()`` emits one report across 1-D / 2-D / 3-D plans and evaluates the
ordering criterion ``3d < 2d < 1d`` on the *measured* per-device bytes —
the first empirical check of the paper's cost tables on this codebase.

CLI (``--host-devices`` selects the CPU before importing jax)::

    PYTHONPATH=src python -m repro.obs.commcheck --host-devices 8 \
        --out commcheck.json

On 8 host devices the 2-D plan runs at p=4 (Optimus needs a square model
degree; 8 is not one) — each plan is compared against the analytic model at
its own (strategy, p), so measured-vs-analytic stays apples-to-apples.

**Shape regime.** The ordering claim is asymptotic in p and holds per
layer only where token traffic dominates weight traffic.  Work the
formulas through for one layer with d_ff = alpha*h at the degenerate
degrees above and the window where the model itself predicts
``3d < 2d < 1d`` is ``t in ((6+3a)h/(9.5-1.5a), (2+a)h)`` tokens — for
the paper's alpha=4 a sliver (5.14h..6h, ~1% margins), for alpha=1 a wide
band (1.125h..3h).  The defaults therefore run the paper transformer with
``d_ff = d_model``, a 4096 vocab (so the untiled LM head doesn't swamp a
4-layer stack), and t = 2h tokens: measured margins are ~10-30%, not
knife-edge.  Override any of it to explore; the report always prints both
measured and analytic orderings.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Dict, List, Optional, Tuple

BYTES_BF16 = 2

# ---------------------------------------------------------------------------
# Analytic side: per-device comm bytes for one C = AB, fwd + bwd.
# These mirror benchmarks/analytic.py (comm_1d/comm_2d/comm_3d) — M tokens,
# N input features, K output features, p model-parallel devices.
# tests/test_obs.py pins the two implementations equal.
# ---------------------------------------------------------------------------
def comm_1d(M, N, K, p, bytes_per=BYTES_BF16):
    if K > N:                       # up-projection (col-parallel): no comm
        return 0.0
    ar = 2 * bytes_per * M * K * (p - 1) / p
    return 2 * ar                   # fwd + bwd all-reduce


def comm_2d(M, N, K, p, bytes_per=BYTES_BF16):
    q = int(round(math.sqrt(p)))
    ag_x = bytes_per * (M * N / p) * (q - 1)
    ag_w = bytes_per * (N * K / p) * (q - 1)
    fwd = ag_x + ag_w
    return fwd + 2 * fwd            # dX and dW each re-gather


def comm_3d(M, N, K, p, bytes_per=BYTES_BF16):
    c = round(p ** (1 / 3))
    ag_a = bytes_per * (M * N / (c * c)) * (c - 1) / c
    ag_b = bytes_per * (N * K / (c * c)) * (c - 1) / c
    rs_c = bytes_per * (M * K / (c * c)) * (c - 1) / c
    return 3 * (ag_a + ag_b + rs_c)


COMM = {"1d": comm_1d, "2d": comm_2d, "3d": comm_3d}


def config_matmuls(cfg, batch: int, seq: int) -> List[Tuple[int, int, int]]:
    """(M, N, K) per Transformer layer for this config's actual shapes:
    fused qkv + attention out-projection + the MLP pair (gated acts carry
    two up-projections)."""
    t = batch * seq
    h = cfg.d_model
    dh = cfg.head_dim
    qkv = (cfg.n_heads + 2 * cfg.n_kv) * dh
    up = (2 if cfg.act in ("silu", "gelu") else 1) * cfg.d_ff
    return [(t, h, qkv), (t, cfg.n_heads * dh, h), (t, h, up),
            (t, cfg.d_ff, h)]


def analytic_bytes(cfg, strategy: str, p: int, batch: int, seq: int) -> float:
    """Per-device collective bytes for one fwd+bwd over the layer stack
    (embedding / LM head / norms excluded — the measured side includes
    them, which the report's ratio column makes visible)."""
    mm = config_matmuls(cfg, batch, seq)
    return sum(COMM[strategy](M, N, K, p) for M, N, K in mm) * cfg.n_layers


# ---------------------------------------------------------------------------
# Measured side: compile grad(forward) and read the HLO.
# ---------------------------------------------------------------------------
def measure_plan(cfg, strategy: str, n_model: int, batch: int, seq: int):
    """Compile one plan's grad step on the current device set and return the
    HLO-extracted collective accounting (requires enough devices — run
    under ``--host-devices`` / XLA_FLAGS on CPU)."""
    import jax
    from ..config import ShapeConfig
    from ..core.params import abstract_arrays
    from ..core.topology import make_layout
    from ..launch.hlo_cost import HloCost
    from ..models import transformer

    lay = make_layout(1, 1, n_model, strategy)
    ap = abstract_arrays(transformer.abstract_params(cfg, lay), lay)
    shape = ShapeConfig("commcheck", seq, batch, "train")
    specs = transformer.input_specs(cfg, lay, shape)

    def fwd(p, b):
        loss, _ = transformer.forward(cfg, lay, p, b, mode="train")
        return loss

    compiled = jax.jit(jax.grad(fwd)).lower(ap, *specs).compile()
    cost = HloCost(compiled.as_text())
    meas = cost.collective_bytes()
    detail = sorted(cost.collectives_detail(),
                    key=lambda r: -r["moved_bytes"])
    return lay, meas, detail


def check(arch: str = "paper-transformer", batch: int = 12, seq: int = 512,
          n_layers: int = 4, d_ff: int = 0, vocab: int = 4096,
          plans: Optional[Dict[str, int]] = None) -> dict:
    """The measured-vs-analytic report across 1-D/2-D/3-D plans on the
    current device set.  Returns a dict (JSON-ready) whose
    ``ordering_measured_3d_2d_1d`` bool is the acceptance criterion.
    ``d_ff=0`` means d_model (the wide-window regime, see module doc);
    ``vocab=0`` keeps the arch's own vocabulary."""
    import dataclasses
    from ..configs.registry import get

    cfg = get(arch)
    cfg = dataclasses.replace(cfg, n_layers=n_layers,
                              d_ff=d_ff or cfg.d_model,
                              vocab=vocab or cfg.vocab)
    if plans is None:
        plans = {"1d": 8, "2d": 4, "3d": 8}      # 2d needs a square degree
    report: dict = {"arch": cfg.arch, "batch": batch, "seq": seq,
                    "n_layers": cfg.n_layers, "d_ff": cfg.d_ff,
                    "vocab": cfg.vocab, "tokens": batch * seq, "plans": {}}
    for strat, p in plans.items():
        lay, meas, detail = measure_plan(cfg, strat, p, batch, seq)
        ana = analytic_bytes(cfg, strat, p, batch, seq)
        report["plans"][strat] = {
            "n_model": p, "cube": list(lay.cube),
            "measured_bytes_per_device": meas["bytes_per_device"],
            "measured_by_kind": meas["by_kind"],
            "measured_counts": meas["counts"],
            "analytic_bytes_per_device": ana,
            "ratio_measured_over_analytic": (
                meas["bytes_per_device"] / ana if ana else float("inf")),
            "top_collectives": detail[:5],
        }
    got = {s: r["measured_bytes_per_device"]
           for s, r in report["plans"].items()}
    if {"1d", "2d", "3d"} <= set(got):
        report["ordering_measured_3d_2d_1d"] = \
            got["3d"] < got["2d"] < got["1d"]
        report["ordering_analytic_3d_2d_1d"] = (
            report["plans"]["3d"]["analytic_bytes_per_device"]
            < report["plans"]["2d"]["analytic_bytes_per_device"]
            < report["plans"]["1d"]["analytic_bytes_per_device"])
    return report


def format_report(rep: dict) -> str:
    lines = [f"commcheck: {rep['arch']} batch={rep['batch']} "
             f"seq={rep['seq']} layers={rep['n_layers']}"
             + (f" d_ff={rep['d_ff']} vocab={rep['vocab']}"
                if "d_ff" in rep else "")
             + " (per-device collective bytes, fwd+bwd)",
             f"{'plan':<14}{'p':>3}  {'measured':>12}  {'analytic':>12}"
             f"  {'ratio':>6}  counts"]
    for strat in ("1d", "2d", "3d"):
        r = rep["plans"].get(strat)
        if r is None:
            continue
        counts = " ".join(f"{k.split('-')[-1]}={v}"
                          for k, v in r["measured_counts"].items() if v)
        cube = "x".join(str(c) for c in r["cube"])
        lines.append(f"{strat + ' (' + cube + ')':<14}{r['n_model']:>3}  "
                     f"{r['measured_bytes_per_device']:>12.3e}  "
                     f"{r['analytic_bytes_per_device']:>12.3e}  "
                     f"{r['ratio_measured_over_analytic']:>6.2f}  {counts}")
    if "ordering_measured_3d_2d_1d" in rep:
        ok = rep["ordering_measured_3d_2d_1d"]
        lines.append("measured per-device volume ordering 3d < 2d < 1d: "
                     + ("OK" if ok else "VIOLATED"))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="paper-transformer")
    ap.add_argument("--batch", type=int, default=12)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=0,
                    help="override d_ff (0 = d_model, the wide-window "
                         "regime; see module docstring)")
    ap.add_argument("--vocab", type=int, default=4096,
                    help="override vocab (0 = the arch's own)")
    ap.add_argument("--out", default="",
                    help="also write the report as JSON here")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU emulation: run on the CPU platform split "
                         "into N host devices (set before JAX is imported; "
                         "the default plans need 8)")
    args = ap.parse_args(argv)
    if args.host_devices:
        from repro.launch.runtime import emulate_host_devices
        emulate_host_devices(args.host_devices)
    rep = check(args.arch, args.batch, args.seq, args.layers,
                d_ff=args.d_ff, vocab=args.vocab)
    print(format_report(rep))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}", file=sys.stderr)
    if not rep.get("ordering_measured_3d_2d_1d", False):
        sys.exit("measured comm ordering violated (expected 3d < 2d < 1d)")


if __name__ == "__main__":
    main()
