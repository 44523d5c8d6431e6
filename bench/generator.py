"""The one generator of serving traffic: requests from a mix's parameters
and a seed.

Every seed replays the same schedule.  The arrival times and the (prompt
length, output length) of each request are drawn once from the mix's own
``schedule_seed``; the run's seed draws only the prompts' token ids.  So two
seeds offer the same requests at the same times, and the work that falls
inside the measured window is the same for every seed.  (Permuting the
schedule by the run's seed moved which long requests fell inside the
window, and with it the window's output tokens by a fifth.)

Mix parameters (all in the traffic file):

    rate_per_s       mean arrival rate (open loop: arrivals do not wait)
    arrivals         "poisson": exponential gaps, rescaled so that the
                     requests due before ``ramp_s + seconds`` number
                     round(rate * (ramp_s + seconds))
    ramp_s           seconds of arrivals before the measured window opens
    prompt, output   {"median", "sigma", "min", "max"}: lognormal lengths,
                     rounded and clipped
    schedule_seed    seed of the schedule above
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Due:
    uid: int
    at: float               # seconds after the loop starts
    prompt: List[int]
    max_new: int


def lognormal_lengths(rng, n: int, p: dict) -> np.ndarray:
    z = rng.standard_normal(n)
    x = np.rint(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(x, p["min"], p["max"]).astype(np.int64)


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Due]:
    """The requests due in the first ``ramp_s + seconds`` seconds, in
    arrival order."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    horizon = mix["ramp_s"] + seconds
    n = max(1, int(round(mix["rate_per_s"] * horizon)))
    base = np.random.default_rng(mix["schedule_seed"])
    gaps = base.exponential(1.0 / mix["rate_per_s"], n)
    gaps *= horizon / gaps.sum()
    plen = lognormal_lengths(base, n, mix["prompt"])
    olen = lognormal_lengths(base, n, mix["output"])

    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = np.random.default_rng(seed)
    return [Due(uid=i, at=float(at[i]),
                prompt=rng.integers(0, vocab, int(plen[i])).tolist(),
                max_new=int(olen[i])) for i in range(n)]
