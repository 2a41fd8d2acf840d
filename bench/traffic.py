"""A run's traffic: the seeded schedule of uploads and the host data they
are cut from.

A mix (``bench/mixes/<traffic>.json``) is data. Its ``arrivals`` key names
the generator that reads it, ``bench/arrivals/<arrivals>.py``, whose
``make(mix, sample_rate, seed, seconds, signal)`` returns a `Traffic`; a
new arrival process is a new file there. The generator also says how the
window drives the uploads (`Traffic.loop`):

* ``backlog``: every upload is due at t=0; they are served first in, first
  out (`Traffic.backlog`) until the window closes;
* ``open_loop``: every upload is due at its own time whatever the system
  does (`Traffic.uploads`, sorted by due time).

A generator gives every seed the same work (lengths, arrivals) in its own
order and with its own data, so the work of a run does not depend on the
seed, and draws lengths from a finite set (`Traffic.lengths`), which
set-up warms.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Upload:
    due_s: float            # seconds after the window opens
    tenant: int
    item: int               # which pool signal the samples come from
    offset: int             # first sample in that signal
    n_samples: int


@dataclasses.dataclass
class Traffic:
    loop: str               # "backlog" | "open_loop"
    tenants: int
    pool: list              # host signals (numpy float32)
    uploads: list           # open loop: the whole schedule; backlog: empty
    lengths: tuple          # every upload length, in samples

    def backlog(self):
        """Backlog uploads, first in first out, for as long as asked."""
        i = 0
        while True:
            yield Upload(0.0, i, i % len(self.pool), 0,
                         self.pool[i % len(self.pool)].shape[0])
            i += 1

    def samples(self, up: Upload) -> np.ndarray:
        return self.pool[up.item][up.offset: up.offset + up.n_samples]


def build(mix: dict, cfg: dict, seed: int, seconds: float, signal,
          arrivals) -> Traffic:
    """The schedule and the host data of one run. ``signal`` is the
    configuration's signal generator (``make(rng, n, sample_rate)``) and
    ``arrivals`` the mix's generator module."""
    tr = arrivals.make(mix, int(cfg["sample_rate_hz"]), seed, seconds, signal)
    if tr.loop not in ("backlog", "open_loop"):
        raise ValueError(f"unknown loop {tr.loop!r}")
    return tr
