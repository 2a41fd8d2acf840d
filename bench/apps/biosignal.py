"""The MBioTracker application as the program under test takes it.

`params` draws the numbers the deployment supplies (FIR taps, SVM
weights) from the seed; the program's app and the plain reference are both
built from them, so the reference takes nothing the program made."""
from __future__ import annotations

import numpy as np

from bench.ref.biosignal import lowpass_taps

# names the stage-graph stream kernel's operation can carry in a device
# trace: XLA names the Mosaic call after the function that jits it
# (`pipeline_stream_pallas` on this path, `graph_stream_call` inside it)
KERNEL = ("pipeline_stream_pallas", "graph_stream_call", "graph_stream_kernel")


def params(cfg: dict, rng: np.random.Generator) -> dict:
    app = cfg["app"]
    c = app["svm_classes"]
    return {"fir_taps": lowpass_taps(app["fir_taps"], app["fir_cutoff"]),
            "svm_w": rng.normal(size=(app["svm_features"], c)
                                ).astype(np.float32),
            "svm_b": (0.1 * rng.normal(size=(c,))).astype(np.float32)}


def build(cfg: dict, p: dict):
    import jax.numpy as jnp

    from repro.core.biosignal import BiosignalApp

    return BiosignalApp(fir_taps=p["fir_taps"], svm_w=jnp.asarray(p["svm_w"]),
                        svm_b=jnp.asarray(p["svm_b"]),
                        fft_size=cfg["app"]["fft_size"])
