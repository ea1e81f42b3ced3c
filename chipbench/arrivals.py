"""Arrival schedules of a traffic mix.

An open loop sends on a schedule whatever the server does.  The schedule
is Poisson-like but the same for every run: ``n = round(rate * seconds)``
gaps at the exponential distribution's quantiles
``(i + 0.5) / n``, in an order drawn once from the mix's
``schedule_seed``.  Every run therefore offers the same load with the same
bursts, and ``--seed`` changes only what is sent (prompts and weights),
not the work's shape: queueing at four fifths of capacity would otherwise
make the tail swing by tens of percent from seed to seed.
"""
from __future__ import annotations

import numpy as np


def open_schedule(rate_per_s: float, seconds: float,
                  schedule_seed: int) -> np.ndarray:
    """Due times (seconds after the window opens) inside ``[0, seconds)``."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate_per_s} and seconds {seconds} must be > 0")
    n = max(1, round(rate_per_s * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    gaps = np.random.default_rng(schedule_seed).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due[due < seconds]

