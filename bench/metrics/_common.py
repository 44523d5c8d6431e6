"""Helpers the per-layer readers share: program runs by name, device time
and the first device of a reduced trace."""
from __future__ import annotations


def runs(dev, name: str):
    """(start, end) of the runs of programs whose name holds ``name``."""
    return [(s, e) for n, s, e in dev.modules if name in n]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def first_device(ctx):
    return next(iter(ctx["trace"].devices.values()))
