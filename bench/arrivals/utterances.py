"""A backlog of utterances: a pool of ``pool`` utterances whose lengths
are the quantiles (i + 1/2) / pool of a lognormal (``median_s``,
``sigma``), clipped to [``min_s``, ``max_s``] and rounded to ``step_s``.
Every seed gets the same lengths; the data and the order come from the
seed, except that the longest utterance is always ``pool[0]``: set-up
warms each length on a prefix of ``pool[0]``. All are due at t=0 and
served first in, first out, cycling the pool, until the window closes."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from bench.traffic import Traffic


def lengths_s(mix: dict) -> list:
    """The pool's lengths in seconds, longest first."""
    k = mix["pool"]
    norm = NormalDist(0.0, mix["sigma"])
    out = [mix["median_s"] * np.exp(norm.inv_cdf((i + 0.5) / k))
           for i in range(k)]
    step = mix["step_s"]
    return sorted((round(float(np.clip(round(s / step) * step, mix["min_s"],
                                       mix["max_s"])), 6) for s in out),
                  reverse=True)


def make(mix: dict, sr: int, seed: int, seconds: float, signal) -> Traffic:
    secs = lengths_s(mix)
    order = [0] + [1 + int(i) for i in np.random.default_rng(
        [seed, 3]).permutation(len(secs) - 1)]
    ns = [int(round(secs[i] * sr)) for i in order]
    pool = [signal(np.random.default_rng([seed, i]), n, sr)
            for i, n in enumerate(ns)]
    return Traffic("backlog", 1, pool, [], tuple(sorted(set(ns))))
