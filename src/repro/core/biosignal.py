"""The MBioTracker biosignal application (paper §4.4.2) on the VWR2A core
library: preprocessing -> delineation -> feature extraction -> SVM.

Pipeline (paper §4.4.2, cognitive-workload estimation from respiration):
  1. *Preprocessing*: 11-tap FIR low-pass over the raw signal.
  2. *Delineation*: detect maxima/minima of the filtered signal to extract
     inspiration/expiration times (the control-intensive step the paper
     highlights — here vectorized into mask algebra, the JAX-native
     equivalent of VWR2A's predicated RC code).
  3. *Feature extraction*: time features (mean, median, RMS of the
     inspiration/expiration intervals) + frequency features from a
     512-point real-valued FFT of the filtered window (band powers).
  4. *Prediction*: linear SVM.

Everything is jit-able; the windowed app is a pure function of the signal.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.fft import rfft_packed
from repro.core.fir import fir_direct, lowpass_taps


# ---------------------------------------------------------------------------
# Delineation
# ---------------------------------------------------------------------------

def _dilate(x, reduce, d: int):
    """Running reduce (max/min) over [t - d, t + d] in log-steps of
    edge-padded shifts — the vectorized morphological dilation that backs
    the delineation refractory window. Shift+select only (Mosaic-safe)."""
    steps, span, s = [], 0, 1
    while span < d:
        steps.append(min(s, d - span))
        span += steps[-1]
        s *= 2
    fwd = bwd = x
    for s in steps:
        fwd = reduce(fwd, jnp.concatenate(
            [fwd[..., s:], fwd[..., -1:].repeat(s, axis=-1)], axis=-1))
        bwd = reduce(bwd, jnp.concatenate(
            [bwd[..., :1].repeat(s, axis=-1), bwd[..., :-s]], axis=-1))
    return reduce(fwd, bwd)


def delineate(x, *, min_prominence: float = 0.3, min_distance: int = 15):
    """Detect local maxima/minima: strict neighbour extremum + amplitude
    gate (x must rise above mean + prominence*(max-mean), resp. below) +
    a +-`min_distance`-sample refractory window (the extremum must
    dominate its neighbourhood — breaths are seconds apart at fs=64 Hz,
    so sensor ripple a few samples wide is not a breath).

    Returns (is_max, is_min): boolean masks over the window. This is the
    paper's 'lots of if conditions' step, recast as vector predicates. The
    refractory gate also bounds the interval density — consecutive
    extrema sit >= min_distance + 1 apart (ties excepted).
    """
    prev = jnp.roll(x, 1, axis=-1)
    nxt = jnp.roll(x, -1, axis=-1)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True)
    lo = jnp.min(x, axis=-1, keepdims=True)
    is_max = (x > prev) & (x >= nxt) & (x > mu + min_prominence * (hi - mu))
    is_min = (x < prev) & (x <= nxt) & (x < mu - min_prominence * (mu - lo))
    if min_distance > 0:
        is_max &= x >= _dilate(x, jnp.maximum, min_distance)
        is_min &= x <= _dilate(x, jnp.minimum, min_distance)
    # edges are never extrema (iota compares, not a scatter: Mosaic-safe)
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    inner = (pos > 0) & (pos < x.shape[-1] - 1)
    return is_max & inner, is_min & inner


def _masked_intervals_sort(mask):
    """Seed reference: mean/median/RMS of gaps between consecutive True
    positions via compaction `sort` + `take_along_axis`. Kept ONLY as the
    equivalence oracle for `_masked_intervals` — `sort`/`take_along_axis`
    are the known Mosaic-compile gap, so nothing on the kernel path may
    call this."""
    S = mask.shape[-1]
    pos = jnp.arange(S)
    idx = jnp.where(mask, pos, S + 1)
    sidx = jnp.sort(idx, axis=-1)
    gaps = jnp.diff(sidx, axis=-1)
    valid = (sidx[..., 1:] <= S) & (sidx[..., :-1] <= S)
    n = jnp.maximum(jnp.sum(valid, axis=-1), 1)
    g = jnp.where(valid, gaps, 0.0).astype(jnp.float32)
    mean = jnp.sum(g, axis=-1) / n
    rms = jnp.sqrt(jnp.sum(jnp.square(g), axis=-1) / n)
    # masked median: middle of the valid prefix of the sorted gap list
    gs = jnp.sort(jnp.where(valid, gaps, jnp.iinfo(jnp.int32).max), axis=-1)
    med = jnp.take_along_axis(gs, ((n - 1) // 2)[..., None], axis=-1)[..., 0]
    med = jnp.where(jnp.sum(valid, axis=-1) > 0, med, 0).astype(jnp.float32)
    return mean, med, rms


def _interval_gaps(mask):
    """Gaps between consecutive True positions as mask algebra: a running
    max of the last-seen True index replaces the seed's compaction sort.
    Returns (gaps, valid) full-window arrays — position i carries the gap
    to its predecessor extremum iff valid[i]."""
    S = mask.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)

    def shift_in(v, s):                  # v[t - s], -1 filled from the left
        return jnp.concatenate(
            [jnp.full(mask.shape[:-1] + (s,), -1, v.dtype), v[..., :-s]],
            axis=-1)

    # running max in log2(S) shift steps (Mosaic has no cummax lowering)
    prev = jnp.where(mask, pos, -1)
    s = 1
    while s < S:
        prev = jnp.maximum(prev, shift_in(prev, s))
        s *= 2
    prev_excl = shift_in(prev, 1)
    valid = mask & (prev_excl >= 0)
    gaps = jnp.where(valid, pos - prev_excl, 0)
    return gaps, valid


def _masked_intervals(mask):
    """Mean/median/RMS of gaps between consecutive True positions (masked
    statistics, fixed shapes — jit-friendly).

    Mosaic-compilable formulation: gap extraction is running-max mask
    algebra (`_interval_gaps`), and the median is an exact counting
    selection. Gaps are integers in [1, S), so the lower median (the k-th
    smallest, k = (n - 1) // 2) is the least v with count(gaps <= v) > k:
    a per-row bisection over [0, S] in ceil(log2 S) static steps, each one
    compare and one lane sum — no sort, gather or branch. Matches
    `_masked_intervals_sort` exactly: every count and every f32 reduction
    of small integers is order-independent."""
    S = mask.shape[-1]
    gaps, valid = _interval_gaps(mask)
    nv = jnp.sum(valid, axis=-1)
    n = jnp.maximum(nv, 1)
    g = jnp.where(valid, gaps, 0).astype(jnp.float32)
    mean = jnp.sum(g, axis=-1) / n
    rms = jnp.sqrt(jnp.sum(jnp.square(g), axis=-1) / n)
    vals = jnp.where(valid, gaps, S + 1)       # invalid slots are never counted
    k = ((n - 1) // 2)[..., None].astype(jnp.float32)
    # invariant: count(vals <= lo) <= k < count(vals <= hi) once nv > 0
    lo = jnp.zeros(k.shape, jnp.int32)
    hi = jnp.full(k.shape, S, jnp.int32)
    for _ in range(max(S - 1, 1).bit_length()):
        mid = (lo + hi) >> 1
        c = jnp.sum((vals <= mid).astype(jnp.float32), axis=-1, keepdims=True)
        take = c > k
        hi = jnp.where(take, mid, hi)
        lo = jnp.where(take, lo, mid)
    med = jnp.where(nv > 0, hi[..., 0], 0).astype(jnp.float32)
    return mean, med, rms


# ---------------------------------------------------------------------------
# Features + SVM
# ---------------------------------------------------------------------------

def interval_time_features(is_max, is_min) -> list:
    """The 6 time features: mean/median/RMS of the inspiration and
    expiration interval lengths (single source — also run inside the fused
    pipeline kernel). Both masks ride ONE counting-selection pass
    (stacked along the batch axis)."""
    if is_max.ndim >= 2:
        both = jnp.concatenate([is_max, is_min], axis=0)
        mean, med, rms = _masked_intervals(both)
        R = is_max.shape[0]
        return [mean[:R], med[:R], rms[:R], mean[R:], med[R:], rms[R:]]
    f_time = []
    for mask in (is_max, is_min):
        mean, med, rms = _masked_intervals(mask)
        f_time += [mean, med, rms]
    return f_time


# Cephes logf: log(1 + m) = m - m^2/2 + m^3 P(m) on [sqrt(1/2) - 1, sqrt(2) - 1]
_LOG_POLY = (7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1,
             -1.2420140846e-1, 1.4249322787e-1, -1.6668057665e-1,
             2.0000714765e-1, -2.4999993993e-1, 3.3333331174e-1)


def log1p(x):
    """log(1 + x) for f32 x >= 0, to an ulp or two, from adds, multiplies
    and bit operations only. The fused kernels take their logs here:
    Mosaic's own log1p is off by up to 2.6e-4 relative on TPU v5e, which
    a power spectrum's log reads as noise.

    1 + x = (1 + f) 2^e with 1 + f in [sqrt(1/2), sqrt(2)): e comes from
    the exponent bits of fl(1 + x), and f = (2^-e - 1) + x 2^-e is formed
    from exact terms, so the low bits of x that fl(1 + x) drops are kept."""
    bits = jax.lax.bitcast_convert_type(1.0 + x, jnp.int32)
    e = (bits >> 23) - 127                           # fl(1 + x) in [2^e, 2^e+1)
    mant = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                        jnp.float32)  # in [1, 2)
    e = jnp.where(mant > 1.41421356, e + 1, e)
    scale = jax.lax.bitcast_convert_type((127 - e) << 23, jnp.float32)
    f = (scale - 1.0) + x * scale                    # 2^-e (1 + x) - 1
    z = f * f
    p = _LOG_POLY[0]
    for c in _LOG_POLY[1:]:
        p = p * f + c
    ef = e.astype(jnp.float32)
    return f + (f * z * p - 2.12194440e-4 * ef - 0.5 * z) + 0.693359375 * ef


def band_power_features(power, fft_size: int) -> list:
    """The 6 log-band powers over a (B, fft/2+1) power spectrum (single
    source — also run inside the fused pipeline kernel)."""
    nb = fft_size // 2 + 1
    bands = np.linspace(1, nb, 7, dtype=int)         # 6 log-ish bands
    return [log1p(jnp.sum(power[..., a:b], axis=-1))
            for a, b in zip(bands[:-1], bands[1:])]


def extract_features(filtered, fft_size: int = 512):
    """(B, S) filtered window -> (B, F) feature matrix (F = 12)."""
    is_max, is_min = delineate(filtered)
    f_time = interval_time_features(is_max, is_min)
    seg = filtered[..., :fft_size]
    seg = seg - jnp.mean(seg, axis=-1, keepdims=True)
    Xr, Xi = rfft_packed(seg)
    power = jnp.square(Xr) + jnp.square(Xi)          # (B, fft/2+1)
    return jnp.stack(f_time + band_power_features(power, fft_size), axis=-1)


def svm_predict(features, w, b):
    """Linear SVM margin + class. w: (F, C), b: (C,)."""
    margin = features @ w + b
    return margin, jnp.argmax(margin, axis=-1)


def svm_fit_least_squares(features, labels, n_classes: int = 2,
                          ridge: float = 1e-3):
    """Tiny ridge-regression 'SVM' fit (tests/examples; the paper runs a
    pre-trained SVM — the prediction path is what executes on VWR2A)."""
    F = features.shape[-1]
    y = jax.nn.one_hot(labels, n_classes) * 2 - 1
    A = features.T @ features + ridge * jnp.eye(F)
    w = jnp.linalg.solve(A, features.T @ y)
    b = jnp.mean(y - features @ w, axis=0)
    return w, b


# ---------------------------------------------------------------------------
# Full application
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BiosignalApp:
    fir_taps: np.ndarray
    svm_w: jnp.ndarray
    svm_b: jnp.ndarray
    fft_size: int = 512

    def __call__(self, signal):
        filtered = fir_direct(signal, jnp.asarray(self.fir_taps))
        feats = extract_features(filtered, self.fft_size)
        margin, cls = svm_predict(feats, self.svm_w, self.svm_b)
        return {"filtered": filtered, "features": feats,
                "margin": margin, "class": cls}


def make_app(cfg=None, seed: int = 0) -> BiosignalApp:
    from repro.configs.vwr2a_biosignal import CONFIG as BIO

    cfg = cfg or BIO
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(12, cfg.svm_classes)).astype(np.float32))
    b = jnp.zeros((cfg.svm_classes,), jnp.float32)
    return BiosignalApp(fir_taps=lowpass_taps(cfg.fir_taps),
                        svm_w=w, svm_b=b, fft_size=cfg.fft_size)


def synthetic_respiration(batch: int, samples: int, *, rate_hz: float = 0.3,
                          fs: float = 64.0, noise: float = 0.15, seed: int = 0):
    """Synthetic respiration-like signal: slow sinusoid + drift + noise."""
    rng = np.random.default_rng(seed)
    t = np.arange(samples) / fs
    rates = rate_hz * (1 + 0.3 * rng.standard_normal((batch, 1)))
    phase = rng.uniform(0, 2 * np.pi, (batch, 1))
    sig = np.sin(2 * np.pi * rates * t[None, :] + phase)
    sig += 0.2 * np.sin(2 * np.pi * 1.1 * t[None, :])     # cardiac bleed
    sig += noise * rng.standard_normal((batch, samples))
    return jnp.asarray(sig.astype(np.float32)), jnp.asarray(
        (rates[:, 0] > rate_hz).astype(np.int32))
