"""Reduction of a ``jax.profiler`` trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it back.  Each TPU is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
that ran and whose line ``XLA Modules`` holds one event per program run;
the host is the plane ``/host:CPU``, where the benchmark's own spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) land on the same clock.

What comes out (``Reduced``), per device and clipped to the window that the
``bench.window`` span marks:

* ``ops``: (name, start, end) of every operation; their union is the time
  the device was busy;
* ``modules``: (name, start, end) of every program run;
* host spans: (name, start, end) of the benchmark's spans, to attribute
  each idle gap to what the host was doing.

Times are in seconds from the start of the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]

WINDOW = "bench.window"
OPCODE = re.compile(r"[\]\}\)] ([a-z][\w\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> str:
    """``opcode:name`` of an op whose trace name is its HLO instruction
    (``%fusion.3 = bf16[...] fusion(...), ...``); a custom call adds its
    target.  Other names come back unchanged."""
    if " = " not in text:
        return text
    name = text.split(" = ", 1)[0].lstrip("%")
    m = OPCODE.search(text)
    op = m.group(1) if m else "?"
    t = TARGET.search(text)
    return f"{op}:{name}" + (f":{t.group(1)}" if t else "")


def is_container(name: str) -> bool:
    """Control flow whose event spans the ops it runs (a layer scan)."""
    return name.split(":", 1)[0] in CONTAINERS


@dataclasses.dataclass
class Device:
    ops: List[Interval]
    modules: List[Interval]


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: Dict[str, Device]
    host: List[Interval]


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) pairs covering the given intervals."""
    out: List[List[float]] = []
    for s, e in sorted((iv[-2], iv[-1]) for iv in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def overlap(a: Tuple[float, float], merged) -> float:
    """Length of (start, end) ``a`` that the merged intervals cover."""
    s, e = a
    return sum(max(0.0, min(e, me) - max(s, ms)) for ms, me in merged
               if me > s and ms < e)


def busy_s(dev: Device) -> float:
    return covered(dev.ops)


def gaps(dev: Device) -> List[Tuple[float, float]]:
    """Idle stretches of the device between its first and last op."""
    u = union(dev.ops)
    return [(a[1], b[0]) for a, b in zip(u, u[1:])]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line, short=False):
    for e in line.events:
        name = short_name(e.name) if short else e.name
        yield name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def reduce(path: str) -> Reduced:
    """Read an xplane file and clip everything to the ``bench.window``
    span."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> Reduced:
    host, devices, raw = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(iv for iv in _events(line)
                            if iv[0].startswith("bench."))
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            raw[plane.name] = (
                list(_events(lines["XLA Ops"], short=True))
                if "XLA Ops" in lines else [],
                list(_events(lines["XLA Modules"]))
                if "XLA Modules" in lines else [])
    return clip(host, raw)


def clip(host: List[Interval], raw: Dict[str, tuple]) -> Reduced:
    win = [iv for iv in host if iv[0] == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW} span")
    _, t0, t1 = win[0]

    def cut(ivs):
        return [(n, max(s, t0) - t0, min(e, t1) - t0) for n, s, e in ivs
                if e > t0 and s < t1]

    devices = {name: Device(cut(ops), cut(mods))
               for name, (ops, mods) in sorted(raw.items())}
    return Reduced(window_s=t1 - t0, devices=devices,
                   host=cut(iv for iv in host if iv[0] != WINDOW))


def top_ops(red: Reduced, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` op names that took most device time, mean over devices;
    control flow that only encloses other ops is left out."""
    tot: Dict[str, float] = {}
    for dev in red.devices.values():
        for name, s, e in dev.ops:
            if is_container(name):
                continue
            tot[name] = tot.get(name, 0.0) + (e - s)
    k = max(len(red.devices), 1)
    return sorted(((nm, t / k) for nm, t in tot.items()),
                  key=lambda x: -x[1])[:n]


def idle_by_host(red: Reduced, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of the first device, each named by the
    innermost host span that covers its middle (or "no span")."""
    dev = next(iter(red.devices.values()))
    out = []
    for s, e in gaps(dev):
        mid = 0.5 * (s + e)
        spans = [iv for iv in red.host if iv[1] <= mid <= iv[2]]
        name = min(spans, key=lambda iv: iv[2] - iv[1])[0] if spans \
            else "no span"
        out.append((name, e - s))
    return sorted(out, key=lambda x: -x[1])[:n]

