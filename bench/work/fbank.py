"""Operations and bytes Kaldi's fbank front end needs, counted from the
graph's shapes: each input sample read once, each frame's mel energies
written once. How the kernel computes them does not enter."""
from __future__ import annotations

import math

import numpy as np

from bench.ref.fbank import mel_banks


def frame_flops(cfg: dict) -> float:
    app = cfg["app"]
    L, n = app["frame_length"], app["fft_size"]
    dc = 2 * L                          # sum, subtract
    preemph = 2 * L
    window = L
    spectrum = 2 * n + 2.5 * n * math.log2(n) + 4 * (n // 2 + 1)
    mel = 2 * int(np.count_nonzero(mel_banks(
        dict(app, sample_rate_hz=cfg["sample_rate_hz"]), n)))
    log = 2 * app["num_mel_bins"]       # floor, log
    return dc + preemph + window + spectrum + mel + log


def counts(cfg: dict, frames: int, samples: int) -> tuple[float, float]:
    """(operations, bytes) for ``frames`` frames cut from ``samples``
    input samples."""
    return (frames * frame_flops(cfg),
            4.0 * samples + frames * 4.0 * cfg["app"]["num_mel_bins"])
