"""Plain reference of Kaldi's ``compute-fbank-feats`` (kaldi-asr/kaldi,
src/feat/feature-fbank.h, feature-window.h, mel-computations.h) at the
configuration's settings: framing with snip_edges, remove_dc_offset,
pre-emphasis with Kaldi's first-sample rule, the Povey window, zero
padding to the FFT, the power spectrum, HTK mel banks and log(max(e,
FLT_EPSILON)). numpy in float64, all frames at once; it imports nothing of
the program under test.

``lowp=True`` computes the same steps with every intermediate rounded to
bfloat16: the control that the comparison has to reject.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

# bins under this share of their frame's largest energy are left out of
# log_err: there the float32 FFT's own rounding is what a log reads
LOG_BINS = 1e-3


def _rounder(lowp: bool):
    if not lowp:
        return lambda a: np.asarray(a, np.float64)
    return lambda a: np.asarray(a, np.float64).astype(
        ml_dtypes.bfloat16).astype(np.float64)


def mel(f):
    """HTK mel scale (`MelBanks::MelScale`)."""
    return 1127.0 * np.log(1.0 + np.asarray(f, np.float64) / 700.0)


def mel_banks(params: dict, fft_size: int) -> np.ndarray:
    """(fft_size//2 + 1, num_mel_bins) weights: triangles in the mel domain
    between low_freq and high_freq, peak 1, no normalisation; FFT bin i <
    fft_size/2 at i * sample_rate / fft_size Hz is weighted only strictly
    inside a triangle; the Nyquist bin weighs 0 (`MelBanks::MelBanks`)."""
    nb = params["num_mel_bins"]
    lo, hi = mel(params["low_freq"]), mel(params["high_freq"])
    delta = (hi - lo) / (nb + 1)
    m = mel(np.arange(fft_size // 2) * params["sample_rate_hz"] / fft_size)
    left = lo + delta * np.arange(nb)[None, :]
    centre, right = left + delta, left + 2 * delta
    m = m[:, None]
    w = np.where(m <= centre, (m - left) / delta, (right - m) / delta)
    w = np.where((m > left) & (m < right), w, 0.0)
    return np.concatenate([w, np.zeros((1, nb))], axis=0)


def frames(signal, window: int, hop: int) -> np.ndarray:
    """snip_edges=true: the whole frames only."""
    n = 0 if signal.shape[0] < window else 1 + (signal.shape[0] - window) // hop
    idx = np.arange(n)[:, None] * hop + np.arange(window)[None, :]
    return np.asarray(signal)[idx]


def reference(params: dict, signal, *, window: int, hop: int,
              fft_size: int, lowp: bool = False) -> dict:
    """``fbank`` (n, num_mel_bins): the log mel energies of every (window,
    hop) frame of ``signal``."""
    q = _rounder(lowp)
    x = q(frames(np.asarray(signal, np.float64), window, hop))
    x = q(x - q(x.sum(axis=1, keepdims=True) / window))      # remove_dc_offset
    p = params["preemph_coeff"]
    prev = np.concatenate([x[:, :1], x[:, :-1]], axis=1)      # x[0] -= p x[0]
    x = q(x - q(p * prev))
    i = np.arange(window)
    povey = (0.5 - 0.5 * np.cos(2 * np.pi * i / (window - 1))) \
        ** params["povey_power"]
    x = q(x * q(povey))
    spec = np.fft.rfft(np.pad(x, ((0, 0), (0, fft_size - window))), axis=1)
    power = q(q(spec.real ** 2) + q(spec.imag ** 2))
    energies = q(power @ q(mel_banks(params, fft_size)))
    return {"fbank": q(np.log(np.maximum(energies, params["log_floor"])))}


def compare(got: dict, want: dict, params: dict) -> dict:
    """Errors that judge one upload's features against the reference
    (`merge` turns a list of them into the numbers compared).

    ``energy_err``: the widest error of a mel energy, exp of a feature, as
    a share of the largest mel energy of its frame. A float32 FFT errs by
    about 1e-7 of the frame's energy in every bin, so this reads the same
    for loud and quiet frames and for the steep low bins that pre-emphasis
    leaves near empty. ``log_err``: the widest error of a feature, in
    nepers, over the bins holding at least ``LOG_BINS`` of their frame's
    largest energy (below that the float32 FFT's own error is what the log
    reads). Missing frames or non-finite features fail both."""
    want = np.asarray(want["fbank"], np.float64)
    n = want.shape[0]
    bad = {"frames": n, "energy_err": np.inf, "log_err": np.inf}
    g = got.get("fbank")
    if g is None or np.shape(g) != want.shape:
        return bad
    g = np.asarray(g, np.float64)
    if not np.all(np.isfinite(g)):
        return bad
    if n == 0:
        return {"frames": 0, "energy_err": 0.0, "log_err": 0.0}
    scale = np.exp(want).max(axis=1, keepdims=True)
    energy_err = float(np.max(np.abs(np.exp(g) - np.exp(want)) / scale))
    loud = np.exp(want) >= LOG_BINS * scale
    log_err = float(np.max(np.abs(g - want), where=loud, initial=0.0))
    return {"frames": n, "energy_err": energy_err, "log_err": log_err}


def merge(parts: list[dict]) -> dict:
    """The numbers compared, over every upload checked: the widest
    errors."""
    return {"energy_err": max((p["energy_err"] for p in parts), default=0.0),
            "log_err": max((p["log_err"] for p in parts), default=0.0)}
