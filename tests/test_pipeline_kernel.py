"""Fused application-pipeline kernel + streaming window runtime: the fused
single-`pallas_call` pipeline must match the staged `BiosignalApp` on every
output, across batch/window shapes, and the streaming runtime must equal
one-shot batch execution on overlapping frames."""
import numpy as np
import pytest

from repro.core.biosignal import make_app, synthetic_respiration
from repro.kernels.pipeline.kernel import pipeline_pallas
from repro.kernels.pipeline.ops import app_pipeline
from repro.kernels.pipeline.ref import pipeline_staged
from repro.serve.stream import (BiosignalStream, StreamConfig, frame_count,
                                frame_signal)


def _assert_matches(out, ref, tol=1e-4):
    for k in ("filtered", "features", "margin"):
        a = np.asarray(ref[k], np.float64)
        b = np.asarray(out[k], np.float64)
        scale = max(1.0, float(np.abs(a).max()))
        assert a.shape == b.shape, (k, a.shape, b.shape)
        assert float(np.abs(a - b).max()) / scale < tol, k
    np.testing.assert_array_equal(np.asarray(out["class"]),
                                  np.asarray(ref["class"]))


@pytest.mark.parametrize("batch,samples", [(4, 2048), (8, 1024), (3, 512)])
def test_fused_matches_staged_app(batch, samples):
    app = make_app()
    sig, _ = synthetic_respiration(batch, samples, seed=batch)
    _assert_matches(app_pipeline(app, sig), app(sig))


def test_fused_matches_kernel_staged():
    """Fused == the kernel-at-a-time staged reference (the bench baseline)."""
    app = make_app()
    sig, _ = synthetic_respiration(6, 1024, seed=11)
    ref = pipeline_staged(sig, app.fir_taps, app.svm_w, app.svm_b,
                          fft_size=app.fft_size)
    _assert_matches(app_pipeline(app, sig), ref)


@pytest.mark.parametrize("block_rows", [1, 2, 4])
def test_fused_interpret_multi_block_grid(block_rows):
    """Explicit row-blocking: grid > 1 must tile the batch without seams."""
    app = make_app()
    sig, _ = synthetic_respiration(8, 1024, seed=13)
    out = pipeline_pallas(sig, app.fir_taps, app.svm_w, app.svm_b,
                          fft_size=app.fft_size, interpret=True,
                          block_rows=block_rows)
    _assert_matches(out, app(sig))


def test_fused_single_pallas_call(monkeypatch):
    """The whole window batch runs in exactly ONE pallas_call: the ops
    entry, lowered for the TPU, holds one Mosaic kernel. Counting in the
    lowered text (not by patching `pallas_call`) keeps the count free of
    whatever the jit caches already hold."""
    import jax
    import repro.kernels.pipeline.ops as ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    app = make_app()
    sig, _ = synthetic_respiration(7, 512, seed=17)
    lowered = jax.jit(lambda s: app_pipeline(app, s)).trace(sig).lower(
        lowering_platforms=("tpu",))
    n = lowered.as_text().count("tpu_custom_call")
    assert n == 1, f"expected 1 pallas_call, lowered {n}"


def test_streaming_matches_one_shot():
    """Windowed streaming output == one-shot batch over the same frames
    (frame count deliberately not a multiple of batch_windows)."""
    app = make_app()
    sig, _ = synthetic_respiration(1, 1024 * 5 + 333, seed=19)
    sig = sig[0]
    cfg = StreamConfig(window=1024, hop=320, batch_windows=4)
    out = BiosignalStream(app, cfg).process(sig)
    frames = frame_signal(sig, cfg.window, cfg.hop)
    assert frames.shape[0] == frame_count(sig.shape[0], cfg.window, cfg.hop)
    assert frames.shape[0] % cfg.batch_windows != 0
    _assert_matches(out, app(frames))


def test_streaming_short_signal():
    app = make_app()
    out = BiosignalStream(app, StreamConfig()).process(np.zeros(100, np.float32))
    assert all(v.shape[0] == 0 for v in out.values())


def test_frame_signal_overlap():
    x = np.arange(32, dtype=np.float32)
    f = np.asarray(frame_signal(x, window=8, hop=4))
    assert f.shape == (7, 8)
    np.testing.assert_array_equal(f[0], x[0:8])
    np.testing.assert_array_equal(f[1], x[4:12])
    np.testing.assert_array_equal(f[-1], x[24:32])


def test_autotune_matches_static_and_caches():
    from repro.core import autotune
    from repro.kernels.fft.ops import fft as kfft

    autotune.clear_cache()
    rng = np.random.default_rng(23)
    re = rng.normal(size=(8, 128)).astype(np.float32)
    im = rng.normal(size=(8, 128)).astype(np.float32)
    a = kfft(re, im)
    b = kfft(re, im, autotune=True)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]), atol=1e-6)
    cache = autotune.cache_snapshot()
    assert len(cache) == 1
    (key, rb), = cache.items()
    assert key[0] == "fft" and rb in autotune.candidate_block_rows(8)
    # second call hits the cache (no new keys, same answer)
    kfft(re, im, autotune=True)
    assert autotune.cache_snapshot() == cache


def test_candidate_block_rows_divide_rows():
    from repro.core.autotune import candidate_block_rows

    for rows in (1, 3, 8, 22, 64, 96):
        cands = candidate_block_rows(rows)
        assert cands and all(rows % c == 0 for c in cands)
        assert rows in cands or any(c % 8 == 0 for c in cands)
