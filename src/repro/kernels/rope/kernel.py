"""Pallas TPU kernel: RoPE via the shuffle-unit dataflow (DESIGN.md §3).

Interleaved (GPT-J) rotary IS the paper's shuffle algebra: every lane
rotates with its pair partner, which a one-word circular shift brings
alongside (x1 c - x2 s on even lanes, x2 c + x1 s on odd ones). The neox
(rotate-half) layout splits halves instead.
cos/sin are computed in-kernel from the staged position block (transcendental
VPU ops) — no HBM-resident rotary table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.vwr import VWRSpec


def rope_kernel(x_ref, pos_ref, o_ref, *, theta: float, layout: str):
    x = x_ref[...].astype(jnp.float32)       # (rb, dh)
    pos = pos_ref[...].astype(jnp.float32)   # (rb, 1)
    dh = x.shape[-1]
    if layout == "interleaved":
        # lane l rotates with pair j = l // 2; the pair partner arrives by
        # a lane rotate (Mosaic has no (rb, dh/2, 2) relayout):
        # out[2j] = x1 c - x2 s, out[2j+1] = x2 c + x1 s
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, dh), 1)
        c, s = _cos_sin(pos, lane >> 1, dh, theta)
        even = (lane & 1) == 0
        partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                            jnp.roll(x, 1, axis=-1))
        out = x * c + partner * s
    else:
        c, s = _cos_sin(pos, jax.lax.broadcasted_iota(
            jnp.int32, (1, dh // 2), 1), dh, theta)
        x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
        out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    o_ref[...] = out.astype(o_ref.dtype)


def _cos_sin(pos, pair, dh: int, theta: float):
    """cos/sin of pos * inv_freq[pair], inv-freq built in-kernel from an
    integer iota (no captured constants; Mosaic iotas are integer)."""
    inv = jnp.exp(pair.astype(jnp.float32) * (2.0 / dh) * (-np.log(theta)))
    ang = pos * inv
    return jnp.cos(ang), jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("theta", "layout", "interpret"))
def rope_pallas(x, positions, *, theta: float = 10000.0,
                layout: str = "interleaved", interpret: bool = True):
    """x: (R, dh); positions: (R,) int32. Returns rotated x."""
    R, dh = x.shape
    spec = VWRSpec()
    rb = max(1, min(R, spec.max_block_bytes(4) // max(1, dh * 4)))
    while R % rb:
        rb -= 1
    pos2 = positions.reshape(R, 1).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(rope_kernel, theta=theta, layout=layout),
        out_shape=jax.ShapeDtypeStruct((R, dh), x.dtype),
        in_specs=[
            pl.BlockSpec((rb, dh), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, dh), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        grid=(R // rb,),
        interpret=interpret,
    )(x, pos2)
