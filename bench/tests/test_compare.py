"""The comparison that decides `correct`: float32 rounding passes; a
delineation disagreement counts as one and does not read as an SVM error;
a margin that is not the SVM of the features returned does."""
import numpy as np

from bench.ref import biosignal as ref


def _case(seed=0, n=40):
    rng = np.random.default_rng(seed)
    params = {"svm_w": rng.normal(size=(12, 2)), "svm_b": rng.normal(size=2)}
    feats = np.concatenate([rng.uniform(100, 250, (n, 6)),
                            rng.uniform(0.5, 12, (n, 6))], axis=1)
    margin = ref.svm(params, feats)
    want = {"features": feats, "margin": margin,
            "class": margin.argmax(axis=1)}
    got = {k: np.asarray(v, np.float32 if k != "class" else np.int32)
           for k, v in want.items()}
    return params, got, want


def test_float32_rounding_agrees():
    params, got, want = _case()
    r = ref.compare(got, want, params)
    assert r["time_mismatch"] == 0 and r["class_flips"] == 0
    assert r["svm_err"] < 1e-6 and r["band_err"] < 1e-6


def test_an_extremum_moved_by_one_sample_is_a_mismatch():
    """The last maximum one sample later: the mean interval of 9 gaps grows
    by 1/9, a relative change under 1e-3, and the margin moves with it."""
    params, got, want = _case()
    got["features"][7, 0] += np.float32(1 / 9)
    got["margin"][7] = ref.svm(params, got["features"][7])
    r = ref.compare(got, want, params)
    assert r["time_mismatch"] == 1
    assert r["svm_err"] < 1e-6


def test_a_margin_off_its_features_is_an_svm_error():
    params, got, want = _case()
    got["margin"][3, 1] += 0.5
    r = ref.compare(got, want, params)
    assert r["time_mismatch"] == 0
    assert r["svm_err"] > 1e-4
