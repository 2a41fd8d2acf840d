"""The VWR2A shuffle unit (paper §3.3.1) as pure-jnp primitives.

The hardware takes VWRs A and B (128 words each), applies a hardcoded
permutation to their concatenation, and writes one VWR's worth (or selects
the upper/lower half of a 2N result) into VWR C. Four operations:

  * words interleaving        [a0,b0,a1,b1,...]            (2N -> half)
  * even / odd index pruning  keep odd / even indices of A and B  (N out)
  * bit-reversal              concat permuted by bit-reversed index (2N -> half)
  * circular shift            concat rotated up by `amount` words  (2N -> half)

All primitives operate on the LAST axis and are batched over leading axes.
These are the semantic oracles for kernels/shuffle (Pallas) and the dataflow
building blocks of core/fft.py. The TPU generalization (DESIGN.md §2): the
shift amount is a static parameter (default 32 = the paper's hardcoded value).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

HALF_LOWER = "lower"
HALF_UPPER = "upper"


def _take_half(x2n, half: str):
    n = x2n.shape[-1] // 2
    if half == HALF_LOWER:
        return x2n[..., :n]
    if half == HALF_UPPER:
        return x2n[..., n:]
    if half == "both":
        return x2n
    raise ValueError(half)


def interleave(a, b, half: str = "both"):
    """[a0,b0,a1,b1,...] — the paper's 'words interleaving'."""
    assert a.shape == b.shape
    out = jnp.stack([a, b], axis=-1).reshape(*a.shape[:-1], -1)
    return _take_half(out, half)


def prune(a, b, *, drop: str = "even"):
    """Drop even- or odd-indexed words of A and B; concat the survivors.

    drop='even' keeps odd indices (a1,a3,...,b1,b3,...); output is N words.
    """
    start = 1 if drop == "even" else 0
    return jnp.concatenate([a[..., start::2], b[..., start::2]], axis=-1)


def bit_reverse_indices(n: int) -> np.ndarray:
    bits = int(np.log2(n))
    assert 1 << bits == n, f"{n} not a power of two"
    idx = np.arange(n)
    rev = np.zeros(n, np.int64)
    for b in range(bits):
        rev |= ((idx >> b) & 1) << (bits - 1 - b)
    return rev


def bit_reverse_lanes(x):
    """x[..., bit_reverse_indices(n)] by rotates and selects (Mosaic has
    no lane gather): reversing the index bits is log2(n)/2 swaps of bit
    pairs (i, j), and each swap moves the words whose two bits differ by
    +-(2^j - 2^i)."""
    n = x.shape[-1]
    bits = int(np.log2(n))
    assert 1 << bits == n, f"{n} not a power of two"
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    for i in range(bits // 2):
        j = bits - 1 - i
        d = (1 << j) - (1 << i)
        bi, bj = (pos >> i) & 1, (pos >> j) & 1
        x = jnp.where((bi == 1) & (bj == 0), jnp.roll(x, -d, axis=-1),
                      jnp.where((bi == 0) & (bj == 1),
                                jnp.roll(x, d, axis=-1), x))
    return x


def bit_reverse(a, b, half: str = "both"):
    """Bit-reversal permutation of concat(A, B)."""
    x = jnp.concatenate([a, b], axis=-1)
    rev = jnp.asarray(bit_reverse_indices(x.shape[-1]))
    return _take_half(x[..., rev], half)


def circular_shift(a, b, amount: int = 32, half: str = "both"):
    """Rotate concat(A,B) up by `amount` words (paper hardcodes 32: the upper
    32 words move to the lower 32). Generalized to any static amount."""
    x = jnp.concatenate([a, b], axis=-1)
    return _take_half(jnp.roll(x, amount, axis=-1), half)


def compact_even(x):
    """y[..., j] = x[..., 2j] for j < L/2; the upper half is don't-care.

    Even-index pruning as log2(L/2) rotate + select steps, because Mosaic
    has no lane-strided slice. Step b moves each kept word whose
    destination has bit b set left by 2^b; before step b every kept word
    sits in an even 2^b-block, so no step overwrites one."""
    L = x.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    b = 0
    while (1 << b) < L // 2:
        x = jnp.where(((pos >> b) & 1) == 1,
                      jnp.roll(x, -(1 << b), axis=-1), x)
        b += 1
    return x


def deinterleave(x):
    """Inverse of interleave: (..., 2N) -> even stream, odd stream.
    Rotates and selects only, so it runs inside Mosaic kernels too."""
    n = x.shape[-1] // 2
    return (compact_even(x)[..., :n],
            compact_even(jnp.roll(x, -1, axis=-1))[..., :n])
