"""The work counts against hand counts at small shapes."""
import json
import math

from bench.registry import BENCH, Registry

REG = Registry({"workloads": [], "end_to_end": [], "per_layer": []}, (BENCH,))


def test_biosignal_counts_by_hand():
    work = REG.module("work", "biosignal")
    cfg = {"app": {"fir_taps": 3, "fft_size": 8, "svm_features": 12,
                   "svm_classes": 2},
           "stream": {"window": 16, "hop": 4,
                      "outputs": ["features", "margin", "class"]}}
    fir = 2 * 3 * 16
    delineation = 16 * 16
    spectrum = 2 * 8 + 2.5 * 8 * 3 + 4 * 5
    svm = 2 * 12 * 2 + 2
    ops, nbytes = work.counts(cfg, frames=5, samples=32)
    assert ops == 5 * (fir + delineation + spectrum + svm)
    # each sample read once; 12 + 2 + 1 words written per frame
    assert nbytes == 4 * 32 + 5 * 4 * 15


def test_the_cell_is_memory_bound_on_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    cfg = REG.config("mbiotracker")
    ops, nbytes = REG.module("work", "biosignal").counts(
        cfg, 1000, 1000 * cfg["stream"]["hop"])
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks["flops_per_s"]
    assert math.isfinite(ops) and ops > 0
