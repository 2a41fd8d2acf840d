"""Operations and bytes the MBioTracker application needs, counted from
the graph's shapes: each input sample read once, each requested output
written once. How the kernel computes them does not enter."""
from __future__ import annotations

import math


def frame_flops(app: dict, stream: dict) -> float:
    w, k, n = stream["window"], app["fir_taps"], app["fft_size"]
    f, c = app["svm_features"], app["svm_classes"]
    fir = 2 * k * w
    delineation = (3 + 6 + 6 + 1) * w   # mean/max/min, extremum and
    #                                     amplitude tests, van Herk-Gil-Werman
    #                                     refractory max/min, gap pass
    spectrum = 2 * n + 2.5 * n * math.log2(n) + 4 * (n // 2 + 1)
    svm = 2 * f * c + c
    return fir + delineation + spectrum + svm


def output_bytes(app: dict, stream: dict) -> int:
    words = {"filtered": stream["window"], "features": app["svm_features"],
             "margin": app["svm_classes"], "class": 1}
    return 4 * sum(words[o] for o in stream["outputs"])


def counts(cfg: dict, frames: int, samples: int) -> tuple[float, float]:
    """(operations, bytes) for ``frames`` frames cut from ``samples``
    input samples."""
    app, stream = cfg["app"], cfg["stream"]
    return (frames * frame_flops(app, stream),
            4.0 * samples + frames * output_bytes(app, stream))
