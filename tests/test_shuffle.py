"""Shuffle-unit kernel: sweeps vs oracle + algebraic properties."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.shuffle import (bit_reverse, circular_shift, deinterleave,
                                interleave, prune)
from repro.kernels.shuffle.ops import shuffle, shuffle_ref

OPS = ["interleave", "prune_even", "prune_odd", "bit_reverse",
       "circular_shift"]


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("shape", [(8, 128), (16, 64), (1, 256), (64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_kernel_matches_oracle(op, shape, dtype, rng):
    a = jnp.asarray(rng.integers(-100, 100, shape)).astype(dtype)
    b = jnp.asarray(rng.integers(-100, 100, shape)).astype(dtype)
    halves = ["both"] if op.startswith("prune") else ["lower", "upper", "both"]
    for half in halves:
        got = shuffle(a, b, op, half=half)
        want = shuffle_ref(a, b, op, half=half)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(0, 2 ** 31 - 1))
def test_interleave_deinterleave_roundtrip(logn, seed):
    n = 1 << logn
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.normal(size=(3, n)).astype(np.float32))
    b = jnp.asarray(r.normal(size=(3, n)).astype(np.float32))
    ev, od = deinterleave(interleave(a, b))
    np.testing.assert_array_equal(np.asarray(ev), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(od), np.asarray(b))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(0, 2 ** 31 - 1))
def test_bit_reverse_involution(logn, seed):
    n = 1 << logn
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.normal(size=(n,)).astype(np.float32))
    b = jnp.asarray(r.normal(size=(n,)).astype(np.float32))
    once = bit_reverse(a, b)
    twice = bit_reverse(once[..., :n], once[..., n:])
    np.testing.assert_array_equal(np.asarray(twice[..., :n]), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(twice[..., n:]), np.asarray(b))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(0, 63), st.integers(0, 63))
def test_circular_shift_composes(logn, s1, s2):
    n = 1 << logn
    a = jnp.arange(n, dtype=jnp.float32)
    b = a + 1000
    one = circular_shift(a, b, amount=(s1 + s2) % (2 * n))
    two_a = circular_shift(a, b, amount=s1 % (2 * n))
    two = circular_shift(two_a[..., :n], two_a[..., n:],
                         amount=s2 % (2 * n))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


def test_prune_keeps_survivors(rng):
    a = jnp.arange(16.0)
    b = jnp.arange(16.0) + 100
    out = prune(a, b, drop="even")
    np.testing.assert_array_equal(np.asarray(out[:8]), np.asarray(a[1::2]))
    np.testing.assert_array_equal(np.asarray(out[8:]), np.asarray(b[1::2]))


@pytest.mark.parametrize("n", [2, 8, 128, 512, 640])
def test_rotate_forms_match_strided_indexing(n, rng):
    """The rotate + select forms the Mosaic kernels use are exact
    permutations: even-index compaction, deinterleave and (for powers of
    two) the bit-reversal shuffle equal plain strided/gather indexing."""
    from repro.core.shuffle import (bit_reverse_indices, bit_reverse_lanes,
                                    compact_even)

    x = jnp.asarray(rng.standard_normal((3, n)).astype(np.float32))
    xn = np.asarray(x)
    np.testing.assert_array_equal(np.asarray(compact_even(x))[:, : n // 2],
                                  xn[:, 0::2])
    ev, od = deinterleave(x)
    np.testing.assert_array_equal(np.asarray(ev), xn[:, 0::2])
    np.testing.assert_array_equal(np.asarray(od), xn[:, 1::2])
    if n & (n - 1) == 0:
        np.testing.assert_array_equal(np.asarray(bit_reverse_lanes(x)),
                                      xn[:, bit_reverse_indices(n)])
