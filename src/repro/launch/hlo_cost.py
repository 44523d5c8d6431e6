"""HLO cost extraction with while-loop trip-count accounting.

XLA's ``cost_analysis()`` counts a while body ONCE, but scan-over-layers puts
almost all compute inside while loops, so FLOPs/bytes/collective volumes
would be undercounted by ~n_layers.  This module re-derives the three
roofline inputs from ``compiled.as_text()``:

  * flops: 2 * prod(dot output dims) * prod(contracted dims), x trip counts
  * bytes: sum of instruction output sizes (written once, read ~once -> x2),
    x trip counts — an HBM-traffic estimate of the same flavour XLA uses
  * collective bytes: ring formulas per op, x trip counts

Trip counts come from the jax-emitted while pattern: the condition compares
the induction variable against a constant.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
               "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def pipeline_time_model(t_compute: float, n_stages: int,
                        microbatches: int) -> Dict[str, float]:
    """Analytic 1F1B step-time model on top of a measured/derived per-step
    compute time: with m microbatches over pp stages the schedule runs
    m + pp - 1 stage-ticks, so the step takes t_compute * (1 + (pp-1)/m)
    — the classic pipeline bubble (arXiv 2104.04473 §2.2)."""
    from ..core.topology import bubble_fraction
    m = max(microbatches, 1)
    bubble = bubble_fraction(n_stages, m)
    return {
        "n_stages": n_stages,
        "microbatches": m,
        "bubble_fraction": bubble,
        "t_ideal": t_compute,
        "t_with_bubble": t_compute * (1.0 + bubble),
    }

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
                     r"(\(?)([a-z0-9]+)\[([0-9,]*)\]")
_WHILE_RE = re.compile(r"while\(.*?\).*?condition=%?([\w.\-]+),\s*"
                       r"body=%?([\w.\-]+)")
_WHILE_RE2 = re.compile(r"while\(.*?\).*?body=%?([\w.\-]+),\s*"
                        r"condition=%?([\w.\-]+)")
_CONST_RE = re.compile(r"[su]\d+\[\]\s+constant\((\d+)\)")
_CALL_RE = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
_DOT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DOT_LHS_RE = re.compile(r" dot\(%?(?P<name>[\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


class HloCost:
    def __init__(self, hlo: str):
        self.comps: Dict[str, list] = {}
        self.defs: Dict[str, Tuple[str, str]] = {}   # instr -> (dtype, dims)
        self._parse(hlo)
        # execution multipliers (while trip counts; calls traversed) for
        # flops/collectives, and memory multipliers (fusion/reduce bodies
        # excluded — their internals are registers, not HBM traffic)
        self.mult = self._multipliers(include_calls=True)
        self.mult_mem = self._multipliers(include_calls=False)

    def _parse(self, hlo: str):
        cur = None
        for line in hlo.splitlines():
            mc = _COMP_RE.match(line)
            if mc and not line.startswith(" "):
                cur = mc.group(1)
                self.comps[cur] = []
                continue
            if line.startswith("}"):
                continue
            md = _DEF_RE.match(line)
            if md and cur is not None:
                name, tup, dt, dims = md.groups()
                if not tup:                      # skip tuple-typed defs
                    self.defs[name] = (dt, dims)
                self.comps[cur].append((md.group(1), dt if not tup else None,
                                        dims if not tup else None, line))

    def _trip_count(self, cond: str) -> int:
        for _, _, _, line in self.comps.get(cond, []):
            m = _CONST_RE.search(line)
            if m:
                return max(1, int(m.group(1)))
        return 1

    def _multipliers(self, include_calls=True) -> Dict[str, float]:
        # edges: computation -> (child computation, factor)
        edges = []
        for comp, instrs in self.comps.items():
            for _, _, _, line in instrs:
                mw = _WHILE_RE.search(line) or _WHILE_RE2.search(line)
                if mw:
                    if "condition=" in mw.group(0) and \
                            mw.re is _WHILE_RE:
                        cond, body = mw.group(1), mw.group(2)
                    else:
                        body, cond = mw.group(1), mw.group(2)
                    trips = self._trip_count(cond)
                    edges.append((comp, body, trips))
                    edges.append((comp, cond, trips))
                else:
                    for callee in _CALL_RE.findall(line):
                        edges.append((comp, callee, 1 if include_calls else 0))
        mult = {c: 0.0 for c in self.comps}
        roots = set(self.comps) - {b for _, b, _ in edges}
        for r in roots:
            mult[r] = 1.0
        # propagate (few levels deep; iterate to fixpoint)
        for _ in range(32):
            changed = False
            new = {c: 0.0 for c in self.comps}
            for r in roots:
                new[r] = 1.0
            for parent, child, f in edges:
                new[child] = new.get(child, 0.0) + mult.get(parent, 0.0) * f
            if any(abs(new[c] - mult[c]) > 1e-9 for c in self.comps):
                changed = True
            mult = new
            if not changed:
                break
        return mult

    # -- costs ---------------------------------------------------------------
    def flops(self) -> float:
        total = 0.0
        for comp, instrs in self.comps.items():
            m = self.mult.get(comp, 1.0)
            if m == 0:
                continue
            for name, dt, dims, line in instrs:
                if " dot(" not in line or dims is None:
                    continue
                out_elems = _shape_elems(dims)
                md = _DOT_DIMS_RE.search(line)
                contract = 1
                ma = _DOT_LHS_RE.search(line)
                if md and ma and ma.group("name") in self.defs:
                    # the lhs operand is a bare name: its dims come from
                    # the operand's def
                    ldims = self.defs[ma.group("name")][1].split(",")
                    for di in md.group(1).split(","):
                        if di:
                            contract *= int(ldims[int(di)])
                total += m * 2.0 * out_elems * contract
        return total

    def bytes_accessed(self) -> float:
        total = 0.0
        for comp, instrs in self.comps.items():
            m = self.mult_mem.get(comp, 1.0)
            if m == 0:
                continue
            for name, dt, dims, line in instrs:
                if dt is None:
                    continue
                # skip pure metadata ops
                if any(f" {op}(" in line for op in
                       ("parameter", "constant", "get-tuple-element",
                        "tuple", "bitcast")):
                    continue
                total += m * 2.0 * _shape_bytes(dt, dims)   # write + read
        return total

    def collectives_detail(self):
        """One record per collective instruction in the compiled module:
        ``{name, kind, comp, dtype, shape, out_bytes, group, mult,
        moved_bytes}`` where ``moved_bytes`` applies the ring formula x the
        execution multiplier.  ``collective_bytes()`` is the reduction of
        this; obs/commcheck.py consumes the detail rows directly so the
        measured-vs-analytic report can show *which* ops carry the volume."""
        rows = []
        for comp, instrs in self.comps.items():
            m = self.mult.get(comp, 1.0)
            if m == 0:
                continue
            for name, dt, dims, line in instrs:
                kind = None
                for c in COLLECTIVES:
                    if f" {c}(" in line or f" {c}-start(" in line:
                        kind = c
                        break
                if kind is None or dt is None:
                    continue
                out_bytes = _shape_bytes(dt, dims)
                n = 2
                g2 = _GROUPS2_RE.search(line)
                g1 = _GROUPS_RE.search(line)
                if g2:
                    n = max(2, int(g2.group(2)))
                elif g1:
                    first = g1.group(1).strip("{}")
                    n = max(2, len([t for t in first.split(",") if t.strip()]))
                if kind == "all-gather":
                    moved = out_bytes * (n - 1) / n
                elif kind == "all-reduce":
                    moved = 2 * out_bytes * (n - 1) / n
                elif kind == "reduce-scatter":
                    moved = out_bytes * (n - 1)
                elif kind == "all-to-all":
                    moved = out_bytes * (n - 1) / n
                else:
                    moved = out_bytes
                rows.append({"name": name, "kind": kind, "comp": comp,
                             "dtype": dt, "shape": dims,
                             "out_bytes": out_bytes, "group": n,
                             "mult": m, "moved_bytes": m * moved})
        return rows

    def collective_bytes(self):
        per_op = {c: 0.0 for c in COLLECTIVES}
        count = {c: 0 for c in COLLECTIVES}
        for r in self.collectives_detail():
            per_op[r["kind"]] += r["moved_bytes"]
            count[r["kind"]] += int(r["mult"])
        return {"bytes_per_device": sum(per_op.values()),
                "by_kind": per_op, "counts": count}
