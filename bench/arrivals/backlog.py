"""Recordings uploaded in bulk: ``pool`` recordings of ``recording_s``
seconds, each drawn from the seed, all due at t=0 and served first in,
first out, cycling the pool, until the window closes."""
from __future__ import annotations

import numpy as np

from bench.traffic import Traffic


def make(mix: dict, sr: int, seed: int, seconds: float, signal) -> Traffic:
    n = int(mix["recording_s"] * sr)
    pool = [signal(np.random.default_rng([seed, i]), n, sr)
            for i in range(mix["pool"])]
    return Traffic("backlog", 1, pool, [], (n,))
