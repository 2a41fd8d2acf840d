"""Public jit'd API for the RoPE kernel."""
from __future__ import annotations

import jax

from repro.kernels import interpret_mode
from repro.kernels.rope.kernel import rope_pallas



def rope(x, positions, *, theta: float = 10000.0,
         layout: str = "interleaved"):
    """Apply rotary embedding. x: (..., S, H, dh) or (R, dh);
    positions broadcastable to the row dims."""
    if x.ndim == 2:
        return rope_pallas(x, positions, theta=theta, layout=layout,
                           interpret=interpret_mode())
    shape = x.shape
    dh = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    pos = jax.numpy.broadcast_to(
        positions[..., None] if positions.ndim == x.ndim - 2 else positions,
        shape[:-1]).reshape(rows)
    out = rope_pallas(x.reshape(rows, dh), pos, theta=theta, layout=layout,
                      interpret=interpret_mode())
    return out.reshape(shape)
