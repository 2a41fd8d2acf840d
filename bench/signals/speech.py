"""Seeded speech-like audio at int16 scale, rounded to whole numbers as a
WAV read gives them.

The signal is a run of stretches: voiced (a glottal pulse train at an f0
of 80-250 Hz, gliding, through three formant resonators), unvoiced
(white noise through a fricative resonator at 2.5-6 kHz) and pauses.
A background noise about 50 dB under the speech runs under all of it, so
no stretch is digital silence."""
from __future__ import annotations

import numpy as np
from scipy.signal import lfilter

SPEECH_RMS = 3000.0     # int16 scale: about -21 dBFS
FLOOR_DB = -50.0        # background noise under the speech
KINDS = ("voiced", "unvoiced", "pause")
SHARE = (0.55, 0.2, 0.25)
DURATION_S = {"voiced": (0.08, 0.4), "unvoiced": (0.04, 0.15),
              "pause": (0.1, 0.5)}


def _resonator(x, freq, bw, sr):
    """Two-pole resonator at ``freq`` Hz with bandwidth ``bw`` Hz."""
    r = np.exp(-np.pi * bw / sr)
    a = [1.0, -2.0 * r * np.cos(2.0 * np.pi * freq / sr), r * r]
    return lfilter([1.0 - r], a, x)


def _voiced(rng, n, sr):
    f0 = rng.uniform(80.0, 250.0) * np.linspace(
        1.0, rng.uniform(0.85, 1.15), n)
    phase = np.cumsum(f0 / sr)
    pulses = np.diff(np.floor(phase), prepend=np.floor(phase[0])) > 0
    x = pulses.astype(np.float64)
    for lo, hi in ((300.0, 900.0), (900.0, 2500.0), (2000.0, 3500.0)):
        x = _resonator(x, rng.uniform(lo, hi), rng.uniform(60.0, 200.0), sr)
    return x


def _unvoiced(rng, n, sr):
    return _resonator(rng.standard_normal(n), rng.uniform(2500.0, 6000.0),
                      rng.uniform(800.0, 2000.0), sr)


def make(rng: np.random.Generator, n_samples: int,
         sample_rate: float) -> np.ndarray:
    sr = float(sample_rate)
    out = np.zeros(n_samples)
    t = 0
    while t < n_samples:
        kind = KINDS[rng.choice(len(KINDS), p=SHARE)]
        n = min(n_samples - t, int(rng.uniform(*DURATION_S[kind]) * sr))
        if kind != "pause":
            x = (_voiced if kind == "voiced" else _unvoiced)(rng, n, sr)
            rms = np.sqrt(np.mean(x * x))
            ramp = np.minimum(1.0, np.minimum(np.arange(n), n - np.arange(n))
                              / (0.01 * sr))     # 10 ms onset and offset
            out[t: t + n] = (SPEECH_RMS * rng.uniform(0.3, 1.5) / max(rms, 1e-12)
                             * x * ramp)
        t += n
    out += SPEECH_RMS * 10 ** (FLOOR_DB / 20) * rng.standard_normal(n_samples)
    return np.clip(np.round(out), -32768, 32767).astype(np.float32)
