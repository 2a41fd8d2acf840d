"""Radix-2 FFT on the VWR2A shuffle-unit dataflow (paper §3.4), in JAX.

The paper's kernel: log2(N) identical stages of butterflies; the shuffle
unit's *words interleaving* fixes the data layout between stages and a final
*bit-reversal* shuffle restores natural order. We implement exactly that
dataflow (decimation-in-frequency):

    stage:  a, b = x[:n/2], x[n/2:]          (two VWRs)
            t0 = a + b
            t1 = (a - b) * w(n)              (butterflies on the RC array)
            x  = regroup[t0; t1]             (shuffle-unit interleave)
    after log2(N) stages the result is in BIT-REVERSED order;
    a final bit-reversal shuffle (paper: "the shuffle unit is again used to
    reorder the data") yields natural order.

Real-valued input uses the paper's packing trick: N reals -> N/2 complex
(evens + i*odds), one N/2 FFT, then an untangle pass — "approximately a
factor of 2" saving (paper §3.4).

Arrays are kept as separate (re, im) float planes — the TPU-friendly layout
used by the Pallas kernel (kernels/fft); complex dtypes appear only in tests.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.shuffle import bit_reverse_indices


def _twiddle(n: int, dtype=np.float32):
    """w_n^j = exp(-2*pi*i*j/n), j < n/2, in f64 then cast (precision)."""
    j = np.arange(n // 2)
    ang = -2.0 * np.pi * j / n
    return np.cos(ang).astype(dtype), np.sin(ang).astype(dtype)


def fft_stages(re, im, *, inverse: bool = False):
    """DIF butterfly stages; output in bit-reversed order. re/im: (..., N)."""
    n_total = re.shape[-1]
    assert (n_total & (n_total - 1)) == 0, f"N={n_total} not a power of 2"
    g = 1
    re = re[..., None, :]
    im = im[..., None, :]
    n = n_total
    while n > 1:
        ar, ai = re[..., :, : n // 2], im[..., :, : n // 2]
        br, bi = re[..., :, n // 2:], im[..., :, n // 2:]
        wr_np, wi_np = _twiddle(n, np.float32)
        wr = jnp.asarray(wr_np)
        wi = jnp.asarray(-wi_np if inverse else wi_np)
        t0r, t0i = ar + br, ai + bi
        dr, di = ar - br, ai - bi
        t1r = dr * wr - di * wi
        t1i = dr * wi + di * wr
        # regroup == shuffle-unit interleave to per-stage layout
        re = jnp.concatenate([t0r[..., None, :, :], t1r[..., None, :, :]],
                             axis=-3).reshape(*re.shape[:-2], 2 * g, n // 2)
        im = jnp.concatenate([t0i[..., None, :, :], t1i[..., None, :, :]],
                             axis=-3).reshape(*im.shape[:-2], 2 * g, n // 2)
        g *= 2
        n //= 2
    return re.reshape(*re.shape[:-2], n_total), im.reshape(
        *im.shape[:-2], n_total)


def fft(re, im=None, *, inverse: bool = False, natural_order: bool = True):
    """Complex radix-2 FFT. re/im: (..., N) float. Returns (re, im).

    The staged interleave-regroup is SELF-SORTING (Stockham): the shuffle
    applied every stage progressively realizes the bit-reversal, so the
    output is already in natural order — the TPU-native form of the paper's
    dataflow (DESIGN.md §2 deviation 1). ``fft_bitrev`` below is the paper's
    literal in-place variant (bit-reversed order + explicit final shuffle).
    """
    if im is None:
        im = jnp.zeros_like(re)
    rr, ri = fft_stages(re, im, inverse=inverse)
    if inverse:
        rr = rr / rr.shape[-1]
        ri = ri / ri.shape[-1]
    return rr, ri


def fft_bitrev(re, im=None, *, inverse: bool = False):
    """The paper's in-place mapping: DIT butterflies on bit-reversed input
    (the explicit `bit_reverse` shuffle-unit pass), natural-order output.
    Numerically identical to fft(); exercised by archsim and tests."""
    if im is None:
        im = jnp.zeros_like(re)
    n_total = re.shape[-1]
    rev = jnp.asarray(bit_reverse_indices(n_total))
    re, im = re[..., rev], im[..., rev]            # shuffle-unit bit-reversal
    n = 2
    while n <= n_total:
        rr = re.reshape(*re.shape[:-1], n_total // n, n)
        ri = im.reshape(*im.shape[:-1], n_total // n, n)
        ar, ai = rr[..., : n // 2], ri[..., : n // 2]
        br, bi = rr[..., n // 2:], ri[..., n // 2:]
        wr_np, wi_np = _twiddle(n, np.float32)
        wr = jnp.asarray(wr_np)
        wi = jnp.asarray(-wi_np if inverse else wi_np)
        tbr = br * wr - bi * wi
        tbi = br * wi + bi * wr
        re = jnp.concatenate([ar + tbr, ar - tbr], axis=-1).reshape(re.shape)
        im = jnp.concatenate([ai + tbi, ai - tbi], axis=-1).reshape(im.shape)
        n *= 2
    if inverse:
        re = re / n_total
        im = im / n_total
    return re, im


def _negate_index(z):
    """z[..., (m - k) % m] for a power-of-two m, by rotates and selects
    (Mosaic lowers no gather or lane reversal): flipping every index bit
    reverses the axis, and one rotate turns m-1-k into m-k."""
    m = z.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1)
    b = 1
    while b < m:                                   # z[p] <- z[p ^ b]
        z = jnp.where((pos & b) != 0, jnp.roll(z, b, axis=-1),
                      jnp.roll(z, -b, axis=-1))
        b *= 2
    return jnp.roll(z, 1, axis=-1)


def untangle_rfft(Zr, Zi, wr, wi):
    """Untangle the packed N/2 spectrum Z into the length-(N/2 + 1) rfft:
    X[k] = (Z[k]+conj(Z[-k]))/2 - i/2 * e^{-2pi i k/N} (Z[k]-conj(Z[-k])),
    Nyquist bin X[N/2] = Re(Z[0]) - Im(Z[0]).

    wr/wi: the (m,) cos/sin of -2*pi*k/N. The single source of the epilogue
    math — shared by this module, kernels/fft/ops.py, and the fused
    application kernel (kernels/pipeline)."""
    Zcr, Zci = _negate_index(Zr), -_negate_index(Zi)   # conj(Z[-k])
    er, ei = (Zr + Zcr) * 0.5, (Zi + Zci) * 0.5
    or_, oi = (Zr - Zcr) * 0.5, (Zi - Zci) * 0.5
    # prod = w * o; then (-i*prod).re = prod.im, (-i*prod).im = -prod.re
    pr = wr * or_ - wi * oi
    pi = wr * oi + wi * or_
    nyq = Zr[..., :1] - Zi[..., :1]
    Xr = jnp.concatenate([er + pi, nyq], axis=-1)
    Xi = jnp.concatenate([ei - pr, jnp.zeros_like(nyq)], axis=-1)
    return Xr, Xi


def rfft_packed(x, *, natural_order: bool = True):
    """Real-valued FFT via the paper's N-real -> N/2-complex packing.

    x: (..., N) real. Returns (re, im) of length N//2 + 1 (like np.fft.rfft).
    """
    n = x.shape[-1]
    zr, zi = x[..., 0::2], x[..., 1::2]            # pack: z = even + i*odd
    Zr, Zi = fft(zr, zi, natural_order=natural_order)
    m = n // 2
    ang = -2.0 * np.pi * np.arange(m) / n
    wr, wi = jnp.asarray(np.cos(ang), x.dtype), jnp.asarray(np.sin(ang), x.dtype)
    return untangle_rfft(Zr, Zi, wr, wi)


def fft_reference(x_complex):
    """Oracle via jnp.fft (tests only)."""
    X = jnp.fft.fft(x_complex)
    return jnp.real(X), jnp.imag(X)
