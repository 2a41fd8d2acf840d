"""Streaming ASR feature front-end (`kernels/pipeline/asr.py`): the fused
``"asr"`` stage graph must match the independent host oracle
(`asr_reference`: frame-local numpy FIR, np.fft.rfft with float64
twiddles, slaney mel matmul) to scale-relative f32 tolerance on every
(window, hop, n_samples) shape — dividing and non-dividing hops,
window > fft_size, single-frame, zero-frame, and tail-pad — and the
graph must ride the shared machinery exactly like the biosignal graph:
`outputs=` elision bit-identical to the full run, the serving runtime
(`serve/stream.py:StreamConfig(graph="asr")`) equal to the one-call
kernel, and graph-scoped autotune keys."""
import numpy as np
import pytest

from repro.core import autotune
from repro.kernels.pipeline.asr import (AsrFrontendApp, asr_reference,
                                        asr_reference_frames, asr_staged,
                                        hann_window, host_frames,
                                        make_asr_frontend, mel_filterbank)
from repro.kernels.pipeline.graph import stream_frame_count
from repro.kernels.pipeline.ops import (default_app, graph_pipeline,
                                        graph_pipeline_stream)
from repro.serve.stream import BiosignalStream, StreamConfig


def _audio(n, seed):
    """Synthetic speech-band stand-in: a chirp + noise, f32 in [-1, 1]."""
    rng = np.random.default_rng(seed)
    t = np.arange(n) / 16000.0
    x = np.sin(2 * np.pi * (200 + 40 * t) * t) + 0.1 * rng.standard_normal(n)
    return x.astype(np.float32)


def _assert_close(out, ref, tol=1e-5):
    assert sorted(out) == sorted(ref), (sorted(out), sorted(ref))
    for k in ref:
        a = np.asarray(ref[k], np.float64)
        b = np.asarray(out[k], np.float64)
        assert a.shape == b.shape, (k, a.shape, b.shape)
        if a.size == 0:
            continue
        scale = max(1.0, float(np.abs(a).max()))
        assert float(np.abs(a - b).max()) / scale < tol, k


# ---------------------------------------------------------------------------
# Fused graph vs the independent host oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,hop,n_samples", [
    (512, 160, 512 * 10 + 37),   # whisper-style hop, ragged tail
    (512, 512, 2048),            # hop == window (no tail specs)
    (1024, 256, 5000),           # window > fft_size: hann on the prefix
    (512, 128, 512),             # exactly one frame
    (512, 160, 5000),            # hop does not divide window, tail pad
])
def test_fused_matches_host_reference(window, hop, n_samples):
    app = make_asr_frontend()
    raw = _audio(n_samples, seed=window + hop)
    out = graph_pipeline_stream("asr", app, raw, window=window, hop=hop)
    ref = asr_reference(app, raw, window=window, hop=hop)
    n = stream_frame_count(n_samples, window, hop)
    assert out["logmel"].shape == (n, app.n_mels)
    _assert_close(out, ref)


def test_zero_frame_shapes():
    app = make_asr_frontend()
    out = graph_pipeline_stream("asr", app, _audio(100, seed=1),
                                window=512, hop=160)
    assert out["filtered"].shape == (0, 512)
    assert out["logmel"].shape == (0, app.n_mels)
    assert out["logmel"].dtype == np.float32
    ref = asr_reference(app, _audio(100, seed=1), window=512, hop=160)
    assert ref["logmel"].shape == (0, app.n_mels)


def test_framed_entry_matches_reference_frames():
    app = make_asr_frontend()
    frames = host_frames(_audio(512 * 8 + 91, seed=3), 512, 256)
    out = graph_pipeline("asr", app, frames)
    _assert_close(out, asr_reference_frames(app, frames))
    # the app's __call__ is the host reference on frames
    _assert_close(out, app(frames))


def test_staged_baseline_matches_fused():
    """The 4-launch `asr_staged` baseline the `--check-asr` gate pairs
    against computes the same numbers as the fused graph."""
    app = make_asr_frontend()
    raw = _audio(512 * 6 + 17, seed=5)
    fused = graph_pipeline_stream("asr", app, raw, window=512, hop=160)
    staged = asr_staged(app, raw, window=512, hop=160)
    _assert_close(fused, staged, tol=1e-5)


@pytest.mark.parametrize("block_frames", [None, 4, 32])
def test_block_frames_tile_without_seams(block_frames):
    app = make_asr_frontend()
    raw = _audio(512 * 12 + 13, seed=7)
    out = graph_pipeline_stream("asr", app, raw, window=512, hop=160,
                                block_frames=block_frames)
    _assert_close(out, asr_reference(app, raw, window=512, hop=160))


def test_outputs_elision_bit_identical():
    app = make_asr_frontend()
    raw = _audio(512 * 5, seed=9)
    full = graph_pipeline_stream("asr", app, raw, window=512, hop=160)
    only_mel = graph_pipeline_stream("asr", app, raw, window=512, hop=160,
                                     outputs=("logmel",))
    assert sorted(only_mel) == ["logmel"]
    np.testing.assert_array_equal(np.asarray(only_mel["logmel"]),
                                  np.asarray(full["logmel"]))
    only_filt = graph_pipeline_stream("asr", app, raw, window=512, hop=160,
                                      outputs=("filtered",))
    assert sorted(only_filt) == ["filtered"]
    np.testing.assert_array_equal(np.asarray(only_filt["filtered"]),
                                  np.asarray(full["filtered"]))


# ---------------------------------------------------------------------------
# Table construction properties
# ---------------------------------------------------------------------------

def test_hann_window_properties():
    h = hann_window(512)
    assert h.shape == (512,) and h.dtype == np.float32
    assert h[0] == 0.0                       # periodic, not symmetric
    np.testing.assert_allclose(h[256], 1.0, atol=1e-6)   # peak mid-window
    np.testing.assert_allclose(h[1:], h[1:][::-1], atol=1e-6)


def test_mel_filterbank_properties():
    fb = mel_filterbank(512, 64, 16000.0)
    assert fb.shape == (257, 64) and fb.dtype == np.float32
    assert float(fb.min()) >= 0.0
    # every filter has support; every filter is a contiguous triangle
    assert (np.count_nonzero(fb, axis=0) >= 1).all()
    # slaney area norm: filter weight sums shrink as bands widen upward
    # only in hz terms; just pin totals are finite and positive
    sums = fb.sum(axis=0)
    assert (sums > 0).all() and np.isfinite(sums).all()


def test_default_app_registered():
    app = default_app("asr")
    assert isinstance(app, AsrFrontendApp)
    assert app.fft_size == 512 and app.n_mels == 64
    taps = app.fir_taps
    np.testing.assert_allclose(taps, [1.0, -0.97], rtol=1e-6)
    # app=None resolves the registered default inside the entry
    raw = _audio(2048, seed=13)
    out = graph_pipeline_stream("asr", None, raw, window=512, hop=160)
    ref = graph_pipeline_stream("asr", app, raw, window=512, hop=160)
    np.testing.assert_array_equal(np.asarray(out["logmel"]),
                                  np.asarray(ref["logmel"]))


# ---------------------------------------------------------------------------
# Serving integration: graph="asr" through the stream runtime
# ---------------------------------------------------------------------------

def test_stream_runtime_serves_asr_graph():
    """`StreamConfig(graph="asr")` drives the SAME batched runtime as the
    biosignal class and equals the one-call fused kernel bitwise (batch
    boundaries are hop-aligned — the requeue/replay invariant)."""
    app = make_asr_frontend()
    raw = _audio(512 * 9 + 77, seed=15)
    cfg = StreamConfig(window=512, hop=160, batch_windows=8, graph="asr")
    stream = BiosignalStream(app, cfg)
    out = stream.process(raw)
    ref = graph_pipeline_stream("asr", app, raw, window=512, hop=160)
    assert sorted(out) == sorted(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(ref[k]), err_msg=k)


def test_stream_runtime_asr_outputs_and_default_app():
    cfg = StreamConfig(window=512, hop=160, batch_windows=4, graph="asr",
                       outputs=("logmel",))
    stream = BiosignalStream(None, cfg)      # default app resolves
    assert isinstance(stream.app, AsrFrontendApp)
    raw = _audio(512 * 4 + 100, seed=17)
    out = stream.process(raw)
    assert sorted(out) == ["logmel"]
    ref = graph_pipeline_stream("asr", stream.app, raw, window=512,
                                hop=160, outputs=("logmel",))
    np.testing.assert_array_equal(np.asarray(out["logmel"]),
                                  np.asarray(ref["logmel"]))
    # zero-frame degenerate path keeps the selected keys/shapes
    empty = stream.process(raw[:100])
    assert sorted(empty) == ["logmel"]
    assert empty["logmel"].shape == (0, stream.app.n_mels)


def test_asr_graph_is_single_column():
    with pytest.raises(AssertionError, match="single-column"):
        BiosignalStream(None, StreamConfig(window=512, hop=160,
                                           graph="asr", n_columns=2))


def test_autotune_key_is_graph_scoped(tmp_path):
    autotune.clear_cache()
    app = make_asr_frontend()
    raw = _audio(512 * 6, seed=21)
    out = graph_pipeline_stream("asr", app, raw, window=512, hop=160,
                                autotune=True, outputs=("logmel",))
    ref = asr_reference(app, raw, window=512, hop=160)
    _assert_close({"logmel": out["logmel"]}, {"logmel": ref["logmel"]})
    cache = autotune.cache_snapshot()
    (key, rb), = cache.items()
    assert key[0] == "asr_pipeline_stream"
    assert key[2:5] == (512, 160, ("logmel",))
    assert rb in autotune.candidate_stream_block_frames(key[1], 512, 160)
    autotune.clear_cache()
