"""A fleet of tenants, each syncing every period from its own phase.

Periods are dealt in equal numbers of tenants over ``periods_s``, and the
tenants are as many as make the aggregate rate ``rate_per_s``. An upload
holds ``period`` seconds of new samples plus ``overlap_samples`` re-sent
ones, cut from one pool signal of ``pool_s`` seconds. The phases are one
fixed draw, so every seed has the same arrivals; the seed deals them to
the tenants and picks the data."""
from __future__ import annotations

import numpy as np

from bench.traffic import Traffic, Upload


def fleet_size(mix: dict) -> int:
    """Tenants whose syncs add up to ``rate_per_s`` uploads a second, in
    equal numbers per period."""
    periods = mix["periods_s"]
    per_set = sum(1.0 / p for p in periods)
    return len(periods) * max(1, round(mix["rate_per_s"] / per_set))


def make(mix: dict, sr: int, seed: int, seconds: float, signal) -> Traffic:
    rng = np.random.default_rng(seed)
    periods = mix["periods_s"]
    n = fleet_size(mix)
    each = n // len(periods)
    overlap = mix["overlap_samples"]
    pool_n = int(mix["pool_s"] * sr)
    fixed = np.random.default_rng(0)
    ups = []
    for i, p in enumerate(periods):
        tenants = i * each + rng.permutation(each)
        length = p * sr + overlap
        for tenant, ph in zip(tenants, p * fixed.random(each)):
            for t in np.arange(ph, seconds, p):
                ups.append(Upload(float(t), int(tenant), 0,
                                  int(rng.integers(0, pool_n - length + 1)),
                                  length))
    ups.sort(key=lambda u: u.due_s)
    pool = [signal(np.random.default_rng([seed, 0]), pool_n, sr)]
    return Traffic("open_loop", n, pool, ups,
                   tuple(sorted(p * sr + overlap for p in periods)))
