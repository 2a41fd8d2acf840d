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
    so noise ripple near a breath peak yields ONE extremum."""
    sig, _ = synthetic_respiration(8, 2048, seed=1)
    filtered = fir_direct(sig, jnp.asarray(lowpass_taps(11)))
    for mask in delineate(filtered):
        for row in np.asarray(mask):
            pos = np.flatnonzero(row)
            if len(pos) > 1:
                assert np.diff(pos).min() > 15, np.diff(pos).min()


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


def _mixed_masks():
    rng = np.random.default_rng(7)
    cases = []
    for S in (7, 64, 300, 2048):
        dense = rng.random((4, S)) < 0.4          # many short, tied gaps
        sparse = np.zeros((4, S), bool)           # a few long gaps
        pos = np.unique(rng.integers(0, S, size=max(S // 64, 1)))
        sparse[:, pos] = True
        corner = np.zeros((3, S), bool)
        corner[1, S // 2] = True                  # single extremum: no gaps
        corner[2] = True                          # pathological all-True
        cases += [dense, sparse, corner]
    return cases


def _spaced_masks():
    rng = np.random.default_rng(11)
    cases = []
    for S in (64, 512, 2048):
        spaced = np.zeros((4, S), bool)           # >=2 apart, as extrema sit
        pos = np.sort(rng.choice(S // 2, size=S // 40 + 1,
                                 replace=False)) * 2
        spaced[:, pos] = True
        adjacent = spaced.copy()                  # one adjacent pair
        adjacent[:, S // 2] = adjacent[:, S // 2 + 1] = True
        dense = rng.random((4, S)) < 0.5          # many adjacent pairs
        cases += [spaced, adjacent, dense]
    return cases


def _assert_matches_sort_reference(m, err_msg=""):
    from repro.core.biosignal import _masked_intervals, _masked_intervals_sort

    got = [np.asarray(v) for v in jax.jit(_masked_intervals)(jnp.asarray(m))]
    want = [np.asarray(v) for v in _masked_intervals_sort(jnp.asarray(m))]
    for g, w, name in zip(got, want, ("mean", "median", "rms")):
        np.testing.assert_array_equal(g, w, err_msg=f"{err_msg} {name}")


@pytest.mark.parametrize("make", [_mixed_masks, _spaced_masks],
                         ids=["mixed", "spaced"])
def test_masked_intervals_matches_sort_reference(make):
    """The counting-selection median (and the mean/RMS beside it) equals
    the seed's sort/take_along_axis reference bit for bit: dense masks full
    of tied gaps, sparse ones with a few long gaps, empty, single-extremum
    and all-True rows, extrema spaced as delineation leaves them, and
    adjacent Trues, at window widths that are and are not powers of two."""
    for m in make():
        _assert_matches_sort_reference(m, err_msg=str(m.shape))


@pytest.mark.parametrize("S,gap_lists", [
    # ties: the median sits inside a run of equal gaps
    (2048, [[16] * 40, [16] * 20 + [40] * 20, [5, 5, 5, 9, 9, 9, 9],
            [3] * 7 + [1] * 6]),
    # odd and even valid counts: the LOWER median of an even count
    (300, [[9, 3, 7], [9, 3, 7, 5], [1, 2], [2, 1], [4, 4, 8, 8],
           [8, 8, 4, 4, 6]]),
    # extrema at both ends: the one gap is S - 1, the largest there is
    (7, [[6]]),
    (2048, [[2047]]),
    # one valid gap, and rows with no gap at all
    (300, [[17], [1], [], [0]]),
], ids=["ties", "odd-even-count", "end-to-end-7", "end-to-end-2048",
        "one-or-no-gap"])
def test_counting_median_edge_cases(S, gap_lists):
    """Hand-built gap lists at the counting selection's edges; `[]` is an
    all-False row and `[0]` a single extremum, both median 0."""
    m = np.zeros((len(gap_lists), S), bool)
    for r, gaps in enumerate(gap_lists):
        if gaps == [0]:
            m[r, S // 2] = True
        elif gaps:
            m[r, np.cumsum([0] + gaps)] = True
    _assert_matches_sort_reference(m, err_msg=f"S={S}")
    from repro.core.biosignal import _masked_intervals
    med = np.asarray(_masked_intervals(jnp.asarray(m))[1])
    for r, gaps in enumerate(gap_lists):
        real = [g for g in gaps if g > 0]
        want = sorted(real)[(len(real) - 1) // 2] if real else 0
        assert med[r] == want, (r, gaps, med[r])


@pytest.mark.parametrize("S", [7, 300, 2048])
def test_interval_time_features_1d_matches_batched(S):
    """The 1-D mask branch of `interval_time_features` (one selection per
    mask) gives the same six features as the batched (stacked) branch."""
    from repro.core.biosignal import interval_time_features

    rng = np.random.default_rng(S)
    is_max = rng.random((3, S)) < 0.1
    is_min = rng.random((3, S)) < 0.1
    batched = [np.asarray(v) for v in interval_time_features(
        jnp.asarray(is_max), jnp.asarray(is_min))]
    for r in range(3):
        one = [np.asarray(v) for v in interval_time_features(
            jnp.asarray(is_max[r]), jnp.asarray(is_min[r]))]
        for f, (a, b) in enumerate(zip(one, batched)):
            assert a.shape == ()
            np.testing.assert_array_equal(a, b[r], err_msg=f"feature {f}")
        _assert_matches_sort_reference(is_max[r], err_msg=f"row {r}")


def _primitives(jaxpr) -> set:
    """Names of every primitive in a jaxpr, nested jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names |= _primitives(inner)
    return names


def test_interval_features_no_sort_primitives():
    """Acceptance: the delineation/median stage must not lower to `sort`
    or gather (`take_along_axis`) — the Mosaic-compile gap — nor to a
    `cond`: the median is one branch-free path for every mask."""
    from repro.core.biosignal import (_masked_intervals_sort,
                                      interval_time_features)

    def run(mask):
        return tuple(interval_time_features(mask, jnp.roll(mask, 5, -1)))

    m = jnp.asarray(np.random.default_rng(0).random((4, 2048)) < 0.01)
    prims = _primitives(jax.make_jaxpr(jax.jit(run))(m).jaxpr)
    leaked = prims & {"sort", "gather", "cond"}
    assert not leaked, f"{leaked} leaked into the interval feature stage"
    # the walk sees through jit: the sort oracle trips it
    oracle = jax.make_jaxpr(jax.jit(_masked_intervals_sort))(m).jaxpr
    assert {"sort", "gather"} <= _primitives(oracle)
