"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix file (``bench/traffic/<name>.json``) says how requests arrive and
what they ask for:

  arrivals     "backlog": every request due when the window opens, more
               than the window can serve;  "poisson": open-loop arrivals
               at ``rate_per_s`` requests per second of wall time.
  num_steps, guidance     the sampler's Euler steps and guidance scale.
  num_classes  classes drawn uniformly from [0, num_classes).
  gap_seed     (poisson) seed of the fixed set of inter-arrival gaps.
  check_requests  how many served requests the correctness check takes.

Every seed gets the same multiset of gaps, scaled so their mean is
exactly 1 / rate, in an order of its own: runs on different seeds then
offer the same load, and differ only in when its bursts fall.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of a run's seed (any integer)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def classes(mix: dict, seed: int, n: int, stream: int = 0) -> List[int]:
    return rng(seed, 100 + stream).integers(
        0, mix["num_classes"], size=n).tolist()


def arrival_offsets(mix: dict, seed: int, seconds: float
                    ) -> Optional[List[float]]:
    """Seconds after the window opens at which each request is due; None
    for a backlog (everything due at once)."""
    if mix["arrivals"] == "backlog":
        return None
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(int(mix["gap_seed"])).exponential(
        1.0 / rate, size=n)
    gaps *= (n / rate) / gaps.sum()
    gaps = rng(seed, 1).permutation(gaps)
    return (np.cumsum(gaps) - gaps[0]).tolist()
