"""MBioTracker application: delineation properties, feature sanity, SVM
end-to-end accuracy on synthetic respiration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.biosignal import (delineate, extract_features, make_app,
                                  svm_fit_least_squares, svm_predict,
                                  synthetic_respiration)
from repro.core.fir import fir_direct, lowpass_taps


def test_delineate_finds_sine_peaks():
    t = np.arange(512) / 64.0
    x = jnp.asarray(np.sin(2 * np.pi * 0.5 * t).astype(np.float32))[None]
    is_max, is_min = delineate(x)
    # 0.5 Hz over 8 s => ~4 maxima and ~4 minima
    assert 3 <= int(is_max.sum()) <= 5
    assert 3 <= int(is_min.sum()) <= 5
    # maxima are where the signal is high
    assert float(x[is_max].min()) > 0.8


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_delineate_max_min_disjoint(seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(2, 256)).astype(np.float32))
    is_max, is_min = delineate(x)
    assert not bool((is_max & is_min).any())
    assert not bool(is_max[..., 0].any()) and not bool(is_max[..., -1].any())


def test_features_finite_and_fixed_width():
    sig, _ = synthetic_respiration(8, 1024)
    filtered = fir_direct(sig, jnp.asarray(lowpass_taps(11)))
    f = extract_features(filtered)
    assert f.shape == (8, 12)
    assert bool(jnp.isfinite(f).all())


@pytest.mark.slow
def test_svm_learns_rate_classes():
    sig, labels = synthetic_respiration(96, 2048, seed=5)
    filtered = fir_direct(sig, jnp.asarray(lowpass_taps(11)))
    feats = extract_features(filtered)
    w, b = svm_fit_least_squares(feats[:64], labels[:64])
    _, pred = svm_predict(feats[64:], w, b)
    acc = float((pred == labels[64:]).mean())
    assert acc >= 0.7, acc


def test_full_app_jit():
    app = make_app()
    sig, _ = synthetic_respiration(4, 2048)
    out = jax.jit(app.__call__)(sig)
    assert out["class"].shape == (4,)
    assert bool(jnp.isfinite(out["margin"]).all())


def test_delineate_refractory_spacing():
    """The refractory gate: consecutive extrema sit > min_distance apart,
    so noise ripple near a breath peak yields ONE extremum — this spacing
    is also what keeps the interval median on its fixed-size network."""
    sig, _ = synthetic_respiration(8, 2048, seed=1)
    filtered = fir_direct(sig, jnp.asarray(lowpass_taps(11)))
    for mask in delineate(filtered):
        for row in np.asarray(mask):
            pos = np.flatnonzero(row)
            if len(pos) > 1:
                assert np.diff(pos).min() > 15, np.diff(pos).min()


def test_network_sort_matches_np_sort():
    """Batcher odd-even merge network == np.sort for every power of two,
    in a leading-batch layout and a 1-D one."""
    from repro.core.biosignal import network_sort

    rng = np.random.default_rng(0)
    for n in (1, 2, 4, 16, 128, 512):
        x = rng.integers(-1000, 1000, size=(5, n)).astype(np.int32)
        want = np.sort(x, axis=-1)
        got = np.asarray(jax.jit(network_sort)(jnp.asarray(x)))
        np.testing.assert_array_equal(got, want)
        got1 = np.asarray(jax.jit(network_sort)(jnp.asarray(x[0])))
        np.testing.assert_array_equal(got1, want[0])


def test_log1p_within_two_ulps():
    """The bit-level log1p the fused kernels use (Mosaic's own is off by
    up to 2.6e-4 relative on v5e) stays within 2 ulps of float64 log1p
    from 0 to 1e7, including the inputs fl(1 + x) rounds away."""
    from repro.core.biosignal import log1p

    x = np.concatenate([[0.0], np.logspace(-12, 7, 4096),
                        np.linspace(0.0, 50.0, 4096)]).astype(np.float32)
    want = np.log1p(x.astype(np.float64))
    got = np.asarray(jax.jit(log1p)(jnp.asarray(x)), np.float64)
    assert got[0] == 0.0
    ulp = np.spacing(want[1:].astype(np.float32)).astype(np.float64)
    assert float(np.max(np.abs(got[1:] - want[1:]) / ulp)) <= 2.0


def test_masked_intervals_matches_sort_reference():
    """Ref-equivalence of the sorting-network masked-median against the
    seed's sort/take_along_axis path, across densities that exercise BOTH
    the fixed-size fast path and the full-length fallback (plus empty,
    single-extremum, and all-True masks)."""
    from repro.core.biosignal import _masked_intervals, _masked_intervals_sort

    rng = np.random.default_rng(7)
    cases = []
    for S in (7, 64, 300, 2048):
        dense = rng.random((4, S)) < 0.4          # collisions -> fallback
        sparse = np.zeros((4, S), bool)           # fits the 128-slot buffer
        pos = np.unique(rng.integers(0, S, size=max(S // 64, 1)))
        sparse[:, pos] = True
        corner = np.zeros((3, S), bool)
        corner[1, S // 2] = True                  # single extremum: no gaps
        corner[2] = True                          # pathological all-True
        cases += [dense, sparse, corner]
    for m in cases:
        got = [np.asarray(v) for v in _masked_intervals(jnp.asarray(m))]
        want = [np.asarray(v) for v in _masked_intervals_sort(jnp.asarray(m))]
        for g, w, name in zip(got, want, ("mean", "median", "rms")):
            np.testing.assert_array_equal(g, w, err_msg=name)


def test_masked_intervals_sparse2_matches_sort_reference():
    """The sparse2=True pre-fold — the path `interval_time_features`
    actually runs — must match the seed sort reference both when the
    caller's no-adjacent-Trues promise holds AND when it is violated
    (adjacent Trues trip the guard onto the exact full-length network)."""
    from repro.core.biosignal import _masked_intervals, _masked_intervals_sort

    rng = np.random.default_rng(11)
    for S in (64, 512, 2048):
        honest = np.zeros((4, S), bool)      # >=2-apart, promise holds
        pos = np.sort(rng.choice(S // 2, size=S // 40 + 1,
                                 replace=False)) * 2
        honest[:, pos] = True
        broken = honest.copy()               # adjacent pair: promise broken
        broken[:, S // 2] = broken[:, S // 2 + 1] = True
        dense = rng.random((4, S)) < 0.5     # many adjacent pairs
        for m in (honest, broken, dense):
            got = [np.asarray(v) for v in
                   _masked_intervals(jnp.asarray(m), sparse2=True)]
            want = [np.asarray(v) for v in
                    _masked_intervals_sort(jnp.asarray(m))]
            for g, w, name in zip(got, want, ("mean", "median", "rms")):
                np.testing.assert_array_equal(g, w, err_msg=(S, name))


def test_interval_features_no_sort_primitives():
    """Acceptance: the delineation/median stage must not lower to XLA
    `sort` or gather (`take_along_axis`) — the Mosaic-compile gap."""
    from repro.core.biosignal import interval_time_features

    def run(mask):
        return tuple(interval_time_features(mask, jnp.roll(mask, 5, -1)))

    m = jnp.asarray(np.random.default_rng(0).random((4, 2048)) < 0.01)
    hlo = jax.jit(run).lower(m).as_text()
    assert " sort(" not in hlo and " gather(" not in hlo, (
        "sort/gather leaked into the interval feature stage")
