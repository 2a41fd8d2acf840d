"""Seeded respiration-like signal: a slow breathing sinusoid of its own rate
and phase, a cardiac bleed and sensor noise (the program's
`core/biosignal.py:synthetic_respiration`, one channel)."""
from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, n_samples: int,
         sample_rate: float) -> np.ndarray:
    t = np.arange(n_samples, dtype=np.float64) / sample_rate
    rate = 0.3 * (1 + 0.3 * rng.standard_normal())
    sig = np.sin(2 * np.pi * rate * t + rng.uniform(0, 2 * np.pi))
    sig += 0.2 * np.sin(2 * np.pi * 1.1 * t)
    sig += 0.15 * rng.standard_normal(n_samples)
    return sig.astype(np.float32)
