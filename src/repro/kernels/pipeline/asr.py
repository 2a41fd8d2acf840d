"""Streaming ASR feature front-end: the SECOND registered stage graph.

The paper's flexibility claim — one substrate, many kernels — needs more
than one workload to mean anything. A log-mel filterbank front-end (what
feeds every Whisper-style encoder) has exactly the biosignal pipeline's
shape: framing -> causal FIR (pre-emphasis) -> rFFT -> matmul epilogue.
So it is FOUR registered stages over the same graph machinery
(`graph.py:StageGraph`), compiled into the same single-`pallas_call`
entries with the in-kernel framing, `outputs=` elision and ring grid of
`kernel.py` completely unchanged:

    fir (pre-emphasis, taps [1, -preemph])
      -> hann  (periodic Hann on the first fft_size samples)
      -> power_spectrum (the packed rFFT of `kernel.py:_packed_rfft`,
                         |X|^2 — NO mean subtraction, unlike the
                         biosignal band-power stage)
      -> logmel (log1p(power @ mel_w), a slaney-style mel filterbank)

Invariants (pinned by `tests/test_asr.py`):

* **f32 tolerance vs the host reference.** `asr_reference` computes the
  same features with frame-local numpy (np.fft.rfft, float64 twiddles);
  the fused kernel matches it to scale-relative f32 tolerance for
  dividing and non-dividing (window, hop, n_samples), including the
  zero-frame and tail-pad cases.
* **Hop-alignment.** The graph rides `graph.py:graph_stream_call`
  framing, so feeding raw hop-aligned chunks is bit-identical to
  host-framed windows — the property the serving layer
  (`serve/stream.py`) relies on for requeue/replay.
* ``log1p`` (not ``log``) keeps the reference comparison well-posed for
  near-zero mel bins, mirroring `core.biosignal.band_power_features`.

`asr_staged` is the 4-launch baseline (host framing gather + the
standalone FIR/FFT kernels) that `benchmarks/table5_app.py` pairs
against the fused graph for the `run.py --check-asr` gate. The serving
path: `ops.py:graph_pipeline_stream` with graph ``"asr"``, and
`serve/frontend.py:AsrTranscribe` feeds the features to the
`whisper_medium` enc-dec engine as the third traffic class.
"""
from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np

from repro.core.biosignal import log1p
from repro.kernels.pipeline.graph import (OutputSpec, build_graph,
                                          register_graph_factory,
                                          stream_frame_count)
from repro.kernels.pipeline.kernel import (HIGHEST, _packed_rfft,
                                           _table_operands)
from repro.kernels.pipeline.stages import register_stage

__all__ = ["AsrFrontendApp", "make_asr_frontend", "mel_filterbank",
           "hann_window", "asr_graph", "asr_reference",
           "asr_reference_frames", "asr_staged"]


# ---------------------------------------------------------------------------
# Host-side constant tables (computed once, staged as VMEM operands)
# ---------------------------------------------------------------------------

def hann_window(n: int) -> np.ndarray:
    """Periodic Hann window (the STFT convention librosa/scipy use for
    ``sym=False``): 0.5 * (1 - cos(2*pi*k/n))."""
    return (0.5 * (1.0 - np.cos(2.0 * np.pi * np.arange(n) / n))
            ).astype(np.float32)


def _hz_to_mel(f):
    """Slaney mel scale: linear below 1 kHz, log above."""
    f = np.asarray(f, np.float64)
    mel = f / (200.0 / 3.0)
    log_step = np.log(6.4) / 27.0
    return np.where(f >= 1000.0, 15.0 + np.log(np.maximum(f, 1e-10)
                                               / 1000.0) / log_step, mel)


def _mel_to_hz(m):
    m = np.asarray(m, np.float64)
    log_step = np.log(6.4) / 27.0
    return np.where(m >= 15.0, 1000.0 * np.exp(log_step * (m - 15.0)),
                    m * (200.0 / 3.0))


def mel_filterbank(fft_size: int = 512, n_mels: int = 64,
                   sample_rate: float = 16000.0, fmin: float = 0.0,
                   fmax: float | None = None) -> np.ndarray:
    """Slaney-style triangular mel filterbank, area-normalized — the
    librosa ``filters.mel(norm="slaney")`` construction, implemented
    in-repo (no librosa dependency). Returned TRANSPOSED as
    ``(fft_size//2 + 1, n_mels)`` so the kernel's epilogue is a plain
    ``power @ mel_w`` matmul on the MXU (`asr.py:_logmel_body`)."""
    fmax = sample_rate / 2.0 if fmax is None else fmax
    n_bins = fft_size // 2 + 1
    fft_hz = np.arange(n_bins) * (sample_rate / fft_size)
    mel_pts = _mel_to_hz(np.linspace(_hz_to_mel(fmin), _hz_to_mel(fmax),
                                     n_mels + 2))
    fb = np.zeros((n_mels, n_bins))
    for i in range(n_mels):
        lo, mid, hi = mel_pts[i], mel_pts[i + 1], mel_pts[i + 2]
        up = (fft_hz - lo) / max(mid - lo, 1e-10)
        down = (hi - fft_hz) / max(hi - mid, 1e-10)
        fb[i] = np.maximum(0.0, np.minimum(up, down))
        fb[i] *= 2.0 / (hi - lo)                      # slaney area norm
    return fb.T.astype(np.float32)


# ---------------------------------------------------------------------------
# The three ASR map stages (the "fir" stage is shared — graph.py)
# ---------------------------------------------------------------------------

@register_stage("hann", operands=("hann",), requires=("filtered",),
                produces=("windowed",))
def _hann_body(state, tables, params):
    """Periodic Hann on the first fft_size samples of each pre-emphasized
    frame. Windowing only the FFT segment (not the full frame) keeps the
    stage valid for any window >= fft_size, like the biosignal band-power
    stage."""
    return {"windowed":
            state["filtered"][:, :params["fft_size"]] * tables["hann"][0]}


@register_stage("power_spectrum",
                operands=("twiddle_re", "twiddle_im", "untangle"),
                requires=("windowed",), produces=("power",))
def _power_body(state, tables, params):
    """|rFFT|^2 of the windowed segment via the shared packed-rFFT helper
    (`kernel.py:_packed_rfft`) — same radix-2 stages and staged twiddle/
    untangle tables as the biosignal graph, WITHOUT its mean subtraction
    (spectral features keep the DC bin)."""
    Xr, Xi = _packed_rfft(state["windowed"], tables["twiddle_re"],
                          tables["twiddle_im"], tables["untangle"],
                          fft_size=params["fft_size"])
    return {"power": jnp.square(Xr) + jnp.square(Xi)}


@register_stage("logmel", operands=("mel_w",), requires=("power",),
                produces=("logmel",))
def _logmel_body(state, tables, params):
    """log1p(power @ mel_w): the mel matmul epilogue on the MXU. ``log1p``
    not ``log`` so silent frames (power -> 0) stay finite and the host
    comparison is well-posed at f32."""
    return {"logmel": log1p(jnp.dot(
        state["power"], tables["mel_w"][...], precision=HIGHEST,
        preferred_element_type=jnp.float32))}


@functools.lru_cache(maxsize=None)
def asr_graph(n_taps: int, fft_size: int, n_mels: int):
    """The ASR front-end `StageGraph`. ``filtered`` (the pre-emphasized
    frames, the big elidable write) and ``logmel`` (the (n, n_mels)
    features the encoder consumes) are its two outputs."""
    return build_graph(
        "asr",
        ("fir", "hann", "power_spectrum", "logmel"),
        (("filtered", OutputSpec(("window",), "input")),
         ("logmel", OutputSpec(("n_mels",), "float32"))),
        ("fir_taps", "hann", "twiddle_re", "twiddle_im", "untangle",
         "mel_w"),
        (("n_taps", int(n_taps)), ("fft_size", int(fft_size)),
         ("n_mels", int(n_mels))))


@dataclasses.dataclass(frozen=True)
class AsrFrontendApp:
    """Streaming ASR feature front-end parameters (the graph's "app").

    Exposes ``fir_taps`` (pre-emphasis ``[1, -preemph]``; `core.fir`
    convention ``y[t] = sum taps[i] * x[t-i]``) and ``fft_size`` so the
    serving layer's app contract (`serve/stream.py` asserts
    ``window >= app.fft_size``) holds unchanged."""
    preemph: float = 0.97
    fft_size: int = 512
    n_mels: int = 64
    sample_rate: float = 16000.0
    fmin: float = 0.0
    fmax: float | None = None

    @property
    def fir_taps(self) -> np.ndarray:
        return np.array([1.0, -self.preemph], np.float32)

    @property
    def hann(self) -> np.ndarray:
        return hann_window(self.fft_size)

    @property
    def mel_weights(self) -> np.ndarray:
        return mel_filterbank(self.fft_size, self.n_mels, self.sample_rate,
                              self.fmin, self.fmax)

    def __call__(self, frames):
        """Host reference on pre-framed windows (`asr_reference_frames`)."""
        return asr_reference_frames(self, frames)


def make_asr_frontend(**kw) -> AsrFrontendApp:
    """Default ASR front-end: 16 kHz, 512-pt FFT, 64 slaney mel bands —
    the whisper-style configuration `examples/asr_frontend.py` serves."""
    return AsrFrontendApp(**kw)


def _asr_factory(app: AsrFrontendApp):
    """Graph factory (`graph.py:register_graph_factory`): stage the app's
    tables in the graph's operand binding order. Reuses the biosignal
    twiddle/untangle staging (`kernel.py:_table_operands`) so both graphs
    share one table-construction path."""
    base, _ = _table_operands(app.fir_taps, np.zeros((1, 1), np.float32),
                              np.zeros((1,), np.float32), app.fft_size)
    taps, wr, wi, u = base[0], base[1], base[2], base[3]
    operands = (taps, jnp.asarray(app.hann).reshape(1, app.fft_size),
                wr, wi, u, jnp.asarray(app.mel_weights))
    return asr_graph(2, app.fft_size, app.n_mels), operands


register_graph_factory("asr", _asr_factory, default_app=make_asr_frontend)


# ---------------------------------------------------------------------------
# Host reference (independent numerics: numpy float64 FFT) + staged baseline
# ---------------------------------------------------------------------------

def asr_reference_frames(app: AsrFrontendApp, frames) -> dict:
    """Librosa-style host oracle on pre-framed (n, window) windows:
    frame-local pre-emphasis (zero history per frame, the `core.fir`
    convention), periodic Hann, ``np.fft.rfft`` (float64 twiddles —
    numerics independent of the kernel's packed radix-2 path), slaney
    mel matmul, log1p. The fused graph matches this to scale-relative
    f32 tolerance — the `tests/test_asr.py` pin."""
    x = np.asarray(frames, np.float32)
    n, window = x.shape
    taps = app.fir_taps
    k = len(taps)
    xp = np.pad(x, ((0, 0), (k - 1, 0)))
    filt = np.zeros_like(x)
    for i in range(k):
        filt += taps[i] * xp[:, k - 1 - i: k - 1 - i + window]
    windowed = filt[:, :app.fft_size] * app.hann
    power = np.abs(np.fft.rfft(windowed, axis=-1)) ** 2
    logmel = np.log1p(power.astype(np.float32) @ app.mel_weights)
    return {"filtered": filt, "logmel": logmel.astype(np.float32)}


def host_frames(signal, window: int, hop: int) -> np.ndarray:
    """Host-side (window, hop) framing gather — the HBM-heavy layout the
    in-kernel framing exists to avoid (each sample duplicated ~window/hop
    times)."""
    sig = np.asarray(signal)
    n = stream_frame_count(sig.shape[0], window, hop)
    idx = np.arange(n)[:, None] * hop + np.arange(window)[None, :]
    return sig[idx] if n else np.zeros((0, window), sig.dtype)


def asr_reference(app: AsrFrontendApp, signal, *, window: int,
                  hop: int) -> dict:
    """Host oracle over a raw 1-D signal: frame on the host, then
    `asr_reference_frames`. Zero-frame signals return empty (0, ...)
    results matching `graph.py:graph_empty_outputs`."""
    return asr_reference_frames(app, host_frames(signal, window, hop))


def asr_staged(app: AsrFrontendApp, signal, *, window: int, hop: int):
    """The 4-launch staged baseline the fused graph is benchmarked
    against (`benchmarks/table5_app.py`, gate ``run.py --check-asr``):
    host framing gather -> standalone FIR kernel (`kernels/fir/ops.py`)
    -> jitted Hann -> standalone packed-rFFT kernel
    (`kernels/fft/ops.py`) -> jitted mel/log1p. Every arrow is an HBM
    round trip; the fused graph is ONE `pallas_call` over the raw
    signal."""
    import jax

    from repro.kernels.fft.ops import rfft
    from repro.kernels.fir.ops import fir

    frames = jnp.asarray(host_frames(signal, window, hop))
    if frames.shape[0] == 0:
        return {"filtered": jnp.zeros((0, window), frames.dtype),
                "logmel": jnp.zeros((0, app.n_mels), jnp.float32)}
    filt = fir(frames, jnp.asarray(app.fir_taps))
    hann = jnp.asarray(app.hann)
    windowed = jax.jit(lambda f, h: f[:, :app.fft_size] * h)(filt, hann)
    Xr, Xi = rfft(windowed)
    mel_w = jnp.asarray(app.mel_weights)

    @jax.jit
    def finish(xr, xi, w):
        return jnp.log1p(jnp.dot(jnp.square(xr) + jnp.square(xi), w,
                                 preferred_element_type=jnp.float32))

    return {"filtered": filt, "logmel": finish(Xr, Xi, mel_w)}
