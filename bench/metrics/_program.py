"""The program's own host spans, with their args, on the clock of the
reduced trace.

``bench/trace.py`` keeps only the benchmark's ``bench.*`` spans.  The
serving engine records each step as ``serve.*`` spans whose args carry
what the step did (``serve.step``: queue depth, occupied slots, the
process's compile count and seconds; ``serve.prepare``: rows, tokens,
padded positions, live positions), and JAX annotates each backend compile
as ``backend_compile_and_load``.  This module reads the run's xplane
(``bench_out/trace`` beside ``bench/``, where ``bench/run.py`` writes it)
once, keeps those host events with their stats, and clips them to the
``bench.window`` span: times are seconds from the window's start, as in
``ctx["trace"]``.  A program that records no such spans gives an empty
list, and the readers then return None.  A test hands its spans in as
``ctx["program"]``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time
from typing import Dict, List

from bench import spec, trace

TRACE_DIR = os.path.join(spec.ROOT, "bench_out", "trace")
PREFIX = "serve."
COMPILE = "backend_compile_and_load"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    args: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.end - self.start


def from_profile(pd) -> List[Span]:
    """The kept host events of a ``ProfileData``, clipped to the window
    and sorted by start."""
    window, kept = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == trace.WINDOW:
                    window = (e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                elif name.startswith(PREFIX) or name == COMPILE:
                    kept.append((name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9,
                                 dict(e.stats)))
    if window is None:
        raise ValueError(f"the trace holds no {trace.WINDOW} span")
    t0, t1 = window
    return sorted((Span(n, max(s, t0) - t0, min(e, t1) - t0, a)
                   for n, s, e, a in kept if e > t0 and s < t1),
                  key=lambda sp: sp.start)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData
    t = time.perf_counter()
    out = tuple(from_profile(ProfileData.from_file(path)))
    print(f"program spans read in {time.perf_counter() - t:.1f} s",
          file=sys.stderr)
    return out


def spans(ctx) -> List[Span]:
    """The program's spans of this run: ``ctx["program"]`` when given,
    else those of the run's xplane, read once."""
    if "program" in ctx:
        return list(ctx["program"])
    try:
        path = trace.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return []
    return list(_load(path, os.stat(path).st_mtime_ns))


def named(sp: List[Span], name: str) -> List[Span]:
    return [s for s in sp if s.name == name]


def inside(outer: Span, sp: List[Span]) -> List[Span]:
    """The spans of ``sp`` that lie within ``outer``."""
    return [s for s in sp if outer.start <= s.start and s.end <= outer.end]
