"""Pallas TPU kernel: batched radix-2 FFT (paper §3.4 dataflow).

One grid step = one (rows x N) batch block staged into VMEM. The whole
log2(N)-stage pipeline runs on the staged block: in-place butterflies on
the VPU with their operands brought alongside by lane rotates, then the
shuffle unit's bit-reversal — data makes ONE HBM->VMEM round trip for the
entire FFT, which is precisely the paper's SPM->VWR->datapath staging
claim, transplanted. Twiddles are a (log2 N, N) table in the flat layout,
computed host-side in f64 and staged once (the paper
stores them in the SPM; the FFT accelerator it compares against burns ROMs).

Working set: re + im + twiddles = 3 "VWR" blocks (core/vwr.py budget).
Compute is f32 regardless of I/O dtype (the 18-bit dynamic-scaling trick of
the paper's fixed-function rival lives in archsim only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.shuffle import bit_reverse_lanes
from repro.core.vwr import VWRSpec, resolve_block_rows


def twiddle_table(n: int, inverse: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """(stages, n) twiddles in the flat layout of
    `radix2_stages`: at stage s (group length L = n >> s) lane p in the
    upper half of its group holds w_L^((p mod L) - L/2); lower-half lanes
    hold 0 and are never read."""
    stages = int(np.log2(n))
    wr = np.zeros((stages, n), np.float32)
    wi = np.zeros((stages, n), np.float32)
    p = np.arange(n)
    for s in range(stages):
        m = n >> s               # current group length
        upper = (p % m) >= m // 2
        j = (p % m) - m // 2
        ang = -2.0 * np.pi * j / m
        if inverse:
            ang = -ang
        wr[s] = np.where(upper, np.cos(ang), 0.0).astype(np.float32)
        wi[s] = np.where(upper, np.sin(ang), 0.0).astype(np.float32)
    return wr, wi


def radix2_stages(re, im, wr_ref, wi_ref, stages: int):
    """The log2(N) radix-2 DIF stages on (rb, N) planes in the paper's
    literal in-place dataflow (§3.4): stage s splits each group of
    L = N >> s lanes into halves a, b; lower lanes get a + b, upper lanes
    (a - b) * w — two lane rotates per plane, the butterfly and a select,
    on lane-dense vectors only (the grouped (rb, groups, half) layout
    pads every tiny trailing dim to a full tile and overflows VMEM). The
    result leaves the stages in bit-reversed order, and the final
    `bit_reverse_lanes` shuffle restores natural order. Every butterfly
    sees the same operands and twiddle as in the self-sorting form, so the
    output is bit-identical to it."""
    n_total = re.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, re.shape, re.ndim - 1)
    h = n_total // 2
    for s in range(stages):
        upper = (pos & h) != 0
        wr = wr_ref[s: s + 1, :]
        wi = wi_ref[s: s + 1, :]
        ar = jnp.where(upper, jnp.roll(re, h, axis=-1), re)
        ai = jnp.where(upper, jnp.roll(im, h, axis=-1), im)
        br = jnp.where(upper, re, jnp.roll(re, -h, axis=-1))
        bi = jnp.where(upper, im, jnp.roll(im, -h, axis=-1))
        t0r, t0i = ar + br, ai + bi
        dr, di = ar - br, ai - bi
        t1r = dr * wr - di * wi
        t1i = dr * wi + di * wr
        re = jnp.where(upper, t1r, t0r)
        im = jnp.where(upper, t1i, t0i)
        h //= 2
    return bit_reverse_lanes(re), bit_reverse_lanes(im)


def fft_kernel(re_ref, im_ref, wr_ref, wi_ref, ore_ref, oim_ref, *,
               stages: int):
    re, im = radix2_stages(re_ref[...].astype(jnp.float32),
                             im_ref[...].astype(jnp.float32),
                             wr_ref, wi_ref, stages)
    ore_ref[...] = re.astype(ore_ref.dtype)
    oim_ref[...] = im.astype(oim_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("inverse", "interpret", "block_rows"))
def fft_pallas(re, im, *, inverse: bool = False, interpret: bool = True,
               block_rows: int | None = None):
    """Batched complex FFT. re/im: (R, N), N a power of two.

    ``block_rows`` overrides the static VWRSpec budget (core/autotune.py
    feeds a measured winner through here)."""
    R, N = re.shape
    stages = int(np.log2(N))
    assert 1 << stages == N, f"N={N} not a power of 2"
    wr, wi = twiddle_table(N, inverse)
    rb = resolve_block_rows(R, N * 4, spec=VWRSpec(n_vwrs=3),
                            override=block_rows)
    out = pl.pallas_call(
        functools.partial(fft_kernel, stages=stages),
        out_shape=(jax.ShapeDtypeStruct((R, N), re.dtype),
                   jax.ShapeDtypeStruct((R, N), re.dtype)),
        in_specs=[
            pl.BlockSpec((rb, N), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, N), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((stages, N), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((stages, N), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((rb, N), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, N), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ),
        grid=(R // rb,),
        interpret=interpret,
    )(re, im, jnp.asarray(wr), jnp.asarray(wi))
    rr, ri = out
    if inverse:
        rr, ri = rr / N, ri / N
    return rr, ri
