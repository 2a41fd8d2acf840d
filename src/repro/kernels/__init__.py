"""Pallas kernels of the paper's compute hot spots (FFT, FIR, shuffle
unit, the fused stage-graph pipeline) plus the LM-side RoPE and flash
attention. Each kernel package holds ``kernel.py`` (the `pallas_call`),
``ops.py`` (the public entry) and ``ref.py`` (a pure-jnp oracle)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether the ops entries run their kernels in the Pallas
    interpreter: everywhere but the TPU, where they compile to Mosaic.
    The one place that decision is made."""
    return jax.default_backend() != "tpu"
