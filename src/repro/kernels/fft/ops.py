"""Public jit'd API for the FFT kernel + real-FFT packing wrapper."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.fft.kernel import fft_pallas



def fft(re, im=None, *, inverse: bool = False,
        block_rows: int | None = None, autotune: bool = False):
    """Batched complex FFT (R, N) via the Pallas kernel.

    ``autotune=True`` picks the row-block from measured candidates (cached
    per shape) instead of the static VWRSpec budget."""
    if im is None:
        im = jnp.zeros_like(re)
    interp = interpret_mode()
    if autotune and block_rows is None:
        from repro.core.autotune import tuned_block_rows

        R, N = re.shape
        block_rows = tuned_block_rows(
            "fft", R, (N, str(re.dtype), inverse),
            lambda rb: fft_pallas(re, im, inverse=inverse, interpret=interp,
                                  block_rows=rb))
    return fft_pallas(re, im, inverse=inverse, interpret=interp,
                      block_rows=block_rows)


def rfft(x):
    """Real FFT via the paper's N-real -> N/2-complex packing; untangle on
    the host side of the kernel (cheap O(N) epilogue)."""
    from repro.core.fft import untangle_rfft

    n = x.shape[-1]
    zr, zi = x[..., 0::2], x[..., 1::2]
    Zr, Zi = fft(zr, zi)
    m = n // 2
    ang = -2.0 * np.pi * np.arange(m) / n
    wr = jnp.asarray(np.cos(ang), Zr.dtype)
    wi = jnp.asarray(np.sin(ang), Zr.dtype)
    return untangle_rfft(Zr, Zi, wr, wi)
