"""Kaldi's log-mel filterbank (``compute-fbank-feats``): the stage graph
``"fbank"``.

Kaldi's front end is the one most deployed speech recognisers are trained
on (its recipes and the WeNet and k2/icefall ones feed 80 bins of it). It
frames 16 kHz audio at 25 ms / 10 ms (400 / 160 samples) and zero-pads
each frame to the 512-point FFT, so a frame is SHORTER than the FFT: the
graph carries ``frame_length`` and the framing reads each frame 512 lanes
wide (`graph.py:frame_width`), the stages making the lanes past the
frame zero. Five registered stages over the same graph machinery:

    fir            identity tap [1]: the framing's mandatory first stage
                   (Kaldi filters nothing across frames)
      -> kaldi_preemph  remove_dc_offset over the frame's samples, then
                        pre-emphasis with Kaldi's first-sample rule
      -> povey          the Povey window on the frame, zero past it (the
                        zero padding to the FFT)
      -> power_spectrum the packed rFFT |X|^2 shared with ``"asr"``
      -> log_fbank      log(max(power @ htk_mel, FLT_EPSILON)), the mel
                        product on the MXU at HIGHEST precision

`fbank_reference` is the plain numpy float64 reference, step by step;
`tests/test_fbank.py` pins the graph to it. The only departure from
Kaldi's defaults besides the 80 bins is dither 0.
"""
from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
from jax import lax
import jax.experimental.pallas.tpu as pltpu

from repro.core.biosignal import log1p
from repro.kernels.pipeline import asr as _asr  # noqa: F401 ("power_spectrum")
from repro.kernels.pipeline.graph import (OutputSpec, build_graph,
                                          register_graph_factory,
                                          stream_frame_count)
from repro.kernels.pipeline.kernel import HIGHEST, fft_tables
from repro.kernels.pipeline.stages import register_stage

__all__ = ["KaldiFbankApp", "fbank_graph", "povey_window", "htk_mel_banks",
           "fbank_reference", "FLT_EPSILON"]

FLT_EPSILON = float(np.finfo(np.float32).eps)   # Kaldi's log floor
POVEY_POWER = 0.85          # Kaldi's "povey" window: a Hann to this power


def povey_window(frame_length: int, fft_size: int) -> np.ndarray:
    """Kaldi's Povey window, (0.5 - 0.5 cos(2 pi i / (L - 1)))^0.85 on the
    frame's L samples, then zeros up to ``fft_size``: the frame's zero
    padding is this table's tail."""
    i = np.arange(frame_length)
    w = (0.5 - 0.5 * np.cos(2.0 * np.pi * i / (frame_length - 1))
         ) ** POVEY_POWER
    return np.pad(w, (0, fft_size - frame_length))


def _mel(f):
    """HTK mel scale, as Kaldi's `MelBanks::MelScale`."""
    return 1127.0 * np.log(1.0 + np.asarray(f, np.float64) / 700.0)


def htk_mel_banks(n_mels: int, fft_size: int, sample_rate: float,
                  low_freq: float, high_freq: float) -> np.ndarray:
    """Kaldi's `MelBanks` weights, (fft_size//2 + 1, n_mels) for
    ``power @ weights``: bin b is a triangle in the mel domain from
    mel(low) + b d to mel(low) + (b + 2) d, d = (mel(high) - mel(low)) /
    (n_mels + 1), peak 1 at its centre, with no area normalisation; an FFT
    bin i < fft_size/2 sits at i sample_rate / fft_size Hz and is weighted
    only strictly inside a triangle; the Nyquist bin weighs 0."""
    lo, hi = _mel(low_freq), _mel(high_freq)
    delta = (hi - lo) / (n_mels + 1)
    mel = _mel(np.arange(fft_size // 2) * (sample_rate / fft_size))
    w = np.zeros((fft_size // 2 + 1, n_mels))
    for b in range(n_mels):
        left, centre, right = (lo + b * delta, lo + (b + 1) * delta,
                               lo + (b + 2) * delta)
        up = (mel - left) / (centre - left)
        down = (right - mel) / (right - centre)
        inside = (mel > left) & (mel < right)
        w[:-1, b] = np.where(inside, np.where(mel <= centre, up, down), 0.0)
    return w


@dataclasses.dataclass(frozen=True)
class KaldiFbankApp:
    """Kaldi ``compute-fbank-feats`` settings (the graph's "app"). The
    defaults are Kaldi's, with 80 bins; dither is 0 and not a setting:
    the kernel adds no noise. The frame shift is the stream's hop."""
    sample_rate: float = 16000.0
    frame_length: int = 400             # 25 ms
    preemph: float = 0.97
    n_mels: int = 80
    low_freq: float = 20.0
    high_freq: float = 8000.0           # Kaldi's 0: the Nyquist frequency

    @property
    def fft_size(self) -> int:
        """--round-to-power-of-two=true: the frame length rounded up to a
        power of two, 512 for 400 samples (Kaldi has no other FFT size)."""
        return 1 << (self.frame_length - 1).bit_length()

    @property
    def povey(self) -> np.ndarray:
        return povey_window(self.frame_length, self.fft_size)

    @property
    def mel_weights(self) -> np.ndarray:
        return htk_mel_banks(self.n_mels, self.fft_size, self.sample_rate,
                             self.low_freq, self.high_freq)


# ---------------------------------------------------------------------------
# Map stages (``fir`` is graph.py's, ``power_spectrum`` asr.py's)
# ---------------------------------------------------------------------------

@register_stage("kaldi_preemph", requires=("filtered",),
                produces=("preemph",))
def _kaldi_preemph_body(state, tables, params):
    """remove_dc_offset (the mean over the frame's ``frame_length``
    samples, not the lanes past them) and pre-emphasis with Kaldi's
    first-sample rule: x[i] -= p x[i-1] for i >= 1, x[0] -= p x[0]."""
    x = state["filtered"]
    length = params["frame_length"]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = x - jnp.sum(jnp.where(lane < length, x, 0.0), axis=1,
                    keepdims=True) / length
    prev = jnp.where(lane == 0, x, pltpu.roll(x, 1, 1))
    return {"preemph": x - params["preemph"] * prev}


@register_stage("povey", operands=("povey",), requires=("preemph",),
                produces=("windowed",))
def _povey_body(state, tables, params):
    """The Povey window, whose zero tail is the frame's padding to the
    FFT: the lanes past the frame hold the next samples, and go to 0."""
    return {"windowed": state["preemph"] * tables["povey"][0]}


def _log(y):
    """Natural log of normal f32 ``y`` > 0 from `core.biosignal.log1p`'s
    adds, multiplies and bit operations (Mosaic's own logs are off by up
    to 2.6e-4 relative on TPU v5e): y = m 2^e with m in [sqrt(1/2),
    sqrt(2)), m - 1 exact, log y = log1p(m - 1) + e ln 2."""
    bits = lax.bitcast_convert_type(y, jnp.int32)
    e = (bits >> 23) - 127
    m = lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                 jnp.float32)            # in [1, 2)
    big = m > 1.41421356
    e = jnp.where(big, e + 1, e).astype(jnp.float32)
    m = jnp.where(big, 0.5 * m, m)
    return log1p(m - 1.0) + (0.693359375 * e - 2.12194440e-4 * e)


@register_stage("log_fbank", operands=("mel_w",), requires=("power",),
                produces=("fbank",))
def _log_fbank_body(state, tables, params):
    """log(max(power @ mel_w, FLT_EPSILON)): the mel product on the MXU
    at HIGHEST precision (a bf16 pass would round its inputs), then
    Kaldi's floor and natural log."""
    e = jnp.dot(state["power"], tables["mel_w"][...], precision=HIGHEST,
                preferred_element_type=jnp.float32)
    return {"fbank": _log(jnp.maximum(e, FLT_EPSILON))}


@functools.lru_cache(maxsize=None)
def fbank_graph(frame_length: int, fft_size: int, n_mels: int,
                preemph: float):
    """The Kaldi filterbank `StageGraph`: one output, ``fbank`` (n,
    n_mels). Its frames are ``frame_length`` samples, zero-padded to
    ``fft_size``."""
    return build_graph(
        "fbank",
        ("fir", "kaldi_preemph", "povey", "power_spectrum", "log_fbank"),
        (("fbank", OutputSpec(("n_mels",), "float32")),),
        ("fir_taps", "povey", "twiddle_re", "twiddle_im", "untangle",
         "mel_w"),
        (("n_taps", 1), ("fft_size", int(fft_size)),
         ("frame_length", int(frame_length)), ("n_mels", int(n_mels)),
         ("preemph", float(preemph))))


@functools.lru_cache(maxsize=8)
def _fbank_factory(app: KaldiFbankApp):
    """Graph factory: the graph and its host tables in its operand order
    (the identity tap, the Povey table, the shared twiddle/untangle tables
    of `kernel.py:fft_tables`, the mel weights). Cached per app, so the
    streams of an utterance backlog do not rebuild them; the serving layer
    copies them to a stream's device (`serve/stream.py:StagedTables`)."""
    n = app.fft_size
    tables = (np.ones((1, 1), np.float32),
              app.povey.reshape(1, n).astype(np.float32), *fft_tables(n),
              app.mel_weights.astype(np.float32))
    return (fbank_graph(app.frame_length, n, app.n_mels, app.preemph),
            tables)


register_graph_factory("fbank", _fbank_factory, default_app=KaldiFbankApp)


# ---------------------------------------------------------------------------
# Plain reference: numpy float64, Kaldi's steps one by one
# ---------------------------------------------------------------------------

def fbank_reference(app: KaldiFbankApp, signal, *, hop: int) -> np.ndarray:
    """Kaldi fbank features of a raw 1-D signal, (n, n_mels) float64, one
    frame at a time, with no kernel and no batching."""
    x = np.asarray(signal, np.float64)
    L, N = app.frame_length, app.fft_size
    # --frame-length=25 --frame-shift=10 --snip-edges=true: whole frames only
    n = stream_frame_count(x.shape[0], L, hop)
    window = povey_window(L, N)[:L]
    mel_w = htk_mel_banks(app.n_mels, N, app.sample_rate, app.low_freq,
                          app.high_freq)
    out = np.zeros((n, app.n_mels))
    for t in range(n):
        frame = x[t * hop: t * hop + L].copy()
        # --dither=0 (Kaldi's default is 1; decoding recipes turn it off)
        # --remove-dc-offset=true: subtract the frame's mean
        frame -= frame.sum() / L
        # --preemphasis-coefficient=0.97, Kaldi's `Preemphasize`: for i
        # from L - 1 down to 1, x[i] -= p x[i-1] (each x[i-1] read before
        # it changes), then x[0] -= p x[0]
        frame[1:] -= app.preemph * frame[:-1]
        frame[0] -= app.preemph * frame[0]
        # --window-type=povey
        frame *= window
        # --round-to-power-of-two=true: zero-pad to 512; --use-power=true
        spec = np.fft.rfft(np.pad(frame, (0, N - L)))
        power = spec.real ** 2 + spec.imag ** 2
        # --num-mel-bins=80 --low-freq=20 --high-freq=0 (Nyquist), HTK mels
        energies = power @ mel_w
        # --use-log-fbank=true, --use-energy=false: floor at FLT_EPSILON
        out[t] = np.log(np.maximum(energies, FLT_EPSILON))
    return out
