"""Plain reference of the MBioTracker application (VWR2A, arXiv:2204.05009
§4.4.2): framing -> causal FIR -> delineation -> interval and band-power
features -> linear SVM. numpy in float64; it imports nothing of the
program under test.

``lowp=True`` computes the same steps with every intermediate rounded to
bfloat16: the control that the comparison has to reject.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy.ndimage import maximum_filter1d, minimum_filter1d

PROMINENCE = 0.3        # amplitude gate, share of the range above the mean
REFRACTORY = 15         # samples an extremum must dominate on each side
N_BANDS = 6


def _rounder(lowp: bool):
    if not lowp:
        return lambda a: np.asarray(a, np.float64)
    return lambda a: np.asarray(a, np.float64).astype(
        ml_dtypes.bfloat16).astype(np.float64)


def lowpass_taps(n_taps: int, cutoff: float) -> np.ndarray:
    """Hamming-windowed sinc low-pass, normalised to unit DC gain."""
    m = n_taps - 1
    t = np.arange(n_taps) - m / 2
    h = np.sinc(2 * cutoff * t) * 2 * cutoff
    h = h * (0.54 - 0.46 * np.cos(2 * np.pi * np.arange(n_taps) / m))
    return (h / h.sum()).astype(np.float32)


def frames(signal, window: int, hop: int) -> np.ndarray:
    n = 0 if signal.shape[0] < window else 1 + (signal.shape[0] - window) // hop
    idx = np.arange(n)[:, None] * hop + np.arange(window)[None, :]
    return np.asarray(signal)[idx]


def fir(x, taps, q):
    """Causal FIR per frame, zero history before the frame's first sample."""
    k, w = len(taps), x.shape[1]
    xp = np.pad(x, ((0, 0), (k - 1, 0)))
    y = np.zeros_like(x)
    for i in range(k):
        y = q(y + q(taps[i] * xp[:, k - 1 - i: k - 1 - i + w]))
    return y


def extrema(x, q):
    """Breath maxima and minima: strict neighbour extremum, amplitude gate,
    refractory window; the first and last samples are never extrema."""
    prev = np.roll(x, 1, axis=1)
    nxt = np.roll(x, -1, axis=1)
    mu = q(x.mean(axis=1, keepdims=True))
    hi = x.max(axis=1, keepdims=True)
    lo = x.min(axis=1, keepdims=True)
    size = 2 * REFRACTORY + 1
    is_max = ((x > prev) & (x >= nxt) & (x > q(mu + q(PROMINENCE * (hi - mu))))
              & (x >= maximum_filter1d(x, size, axis=1, mode="nearest")))
    is_min = ((x < prev) & (x <= nxt) & (x < q(mu - q(PROMINENCE * (mu - lo))))
              & (x <= minimum_filter1d(x, size, axis=1, mode="nearest")))
    for m in (is_max, is_min):
        m[:, 0] = m[:, -1] = False
    return is_max, is_min


def interval_stats(mask) -> np.ndarray:
    """(n, 3): mean, lower median and RMS of the gaps between consecutive
    extrema of each row; zeros where a row has fewer than two."""
    out = np.zeros((mask.shape[0], 3))
    for r, row in enumerate(mask):
        g = np.diff(np.flatnonzero(row)).astype(np.float64)
        if g.size:
            out[r] = (g.mean(), np.sort(g)[(g.size - 1) // 2],
                      np.sqrt(np.mean(g * g)))
    return out


def band_powers(filtered, fft_size: int, q) -> np.ndarray:
    seg = filtered[:, :fft_size]
    seg = q(seg - q(seg.mean(axis=1, keepdims=True)))
    spec = np.fft.rfft(seg, axis=1)
    power = q(q(spec.real ** 2) + q(spec.imag ** 2))
    edges = np.linspace(1, fft_size // 2 + 1, N_BANDS + 1, dtype=int)
    return np.stack([q(np.log1p(q(power[:, a:b].sum(axis=1))))
                     for a, b in zip(edges[:-1], edges[1:])], axis=1)


def reference(params: dict, signal, *, window: int, hop: int,
              fft_size: int, lowp: bool = False) -> dict:
    """Outputs of the application on every (window, hop) frame of
    ``signal``: ``features`` (n, 12), ``margin`` (n, C), ``class`` (n,)."""
    q = _rounder(lowp)
    x = q(frames(np.asarray(signal, np.float32), window, hop))
    filtered = fir(x, q(params["fir_taps"]), q)
    is_max, is_min = extrema(filtered, q)
    feats = q(np.concatenate([interval_stats(is_max), interval_stats(is_min),
                              band_powers(filtered, fft_size, q)], axis=1))
    margin = q(feats @ q(params["svm_w"]) + q(params["svm_b"]))
    return {"features": feats, "margin": margin,
            "class": margin.argmax(axis=1)}


TIME_FEATURES = slice(0, 6)
BAND_FEATURES = slice(6, 12)
NEAR_TIE = 1e-3         # reference margin gap below which a class may flip
# relative gap up to which two frames' time features agree: float32 on the
# same extrema reads under 1e-6, while an extremum moved by one sample at
# either end of the frame moves the mean by 1/2048 of it or more
SAME_EXTREMA = 1e-5


def svm(params: dict, features) -> np.ndarray:
    """The linear SVM's margin, in float64, on the given features."""
    return (np.asarray(features, np.float64) @ np.asarray(params["svm_w"],
                                                          np.float64)
            + np.asarray(params["svm_b"], np.float64))


def compare(got: dict, want: dict, params: dict) -> dict:
    """Counts and errors that judge one upload's outputs against the
    reference (`merge` turns a list of them into the numbers compared).

    ``time_mismatch``: frames whose interval features differ by more than
    float32 arithmetic on the same extrema would (`SAME_EXTREMA`), so this
    counts delineation disagreements, an extremum moved by one sample
    included, and not rounding. ``band_err``: widest scale-relative error
    of the band powers over all frames. ``svm_err``: widest scale-relative
    gap, over all frames, between the margin returned and the reference's
    SVM on the features returned (so the SVM stage is judged on every
    frame, and a delineation disagreement, which moves the features, is
    left to ``time_mismatch``). ``class_flips``: frames whose interval
    features agree and whose class differs from the reference's while the
    reference's margin gap is clear of a tie. Missing or non-finite
    outputs fail every number."""
    n = want["margin"].shape[0]
    bad = {"frames": n, "time_mismatch": n, "band_err": np.inf,
           "svm_err": np.inf, "class_flips": n}
    if any(np.shape(got.get(k, ()))[:1] != (n,)
           for k in ("features", "margin", "class")):
        return bad
    gf = np.asarray(got["features"], np.float64)
    gm = np.asarray(got["margin"], np.float64)
    if not (np.all(np.isfinite(gf)) and np.all(np.isfinite(gm))):
        return bad
    wf = want["features"]
    tdiff = np.abs(gf[:, TIME_FEATURES] - wf[:, TIME_FEATURES])
    agree = np.all(tdiff <= SAME_EXTREMA
                   * np.maximum(1.0, np.abs(wf[:, TIME_FEATURES])), axis=1)
    band = wf[:, BAND_FEATURES]
    band_err = float(np.abs(gf[:, BAND_FEATURES] - band).max(initial=0.0)
                     / max(1.0, float(np.abs(band).max(initial=0.0))))
    sm = svm(params, gf)
    svm_err = float(np.abs(gm - sm).max(initial=0.0)
                    / max(1.0, float(np.abs(sm).max(initial=0.0))))
    wm = want["margin"][agree]
    top2 = np.sort(wm, axis=1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > NEAR_TIE
    flips = np.asarray(got["class"])[agree] != want["class"][agree]
    return {"frames": n, "time_mismatch": int(np.sum(~agree)),
            "band_err": band_err, "svm_err": svm_err,
            "class_flips": int(np.sum(flips & clear))}


def merge(parts: list[dict]) -> dict:
    """The numbers compared, over every upload checked: the share of
    frames with a delineation disagreement, the widest errors, and the
    count of class flips."""
    frames = sum(p["frames"] for p in parts)
    return {"time_mismatch_pct": 100.0 * sum(p["time_mismatch"] for p in parts)
            / max(frames, 1),
            "band_err": max((p["band_err"] for p in parts), default=0.0),
            "svm_err": max((p["svm_err"] for p in parts), default=0.0),
            "class_flips": sum(p["class_flips"] for p in parts)}
