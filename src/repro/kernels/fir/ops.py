"""Public jit'd API for the FIR kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.fir.kernel import fir_pallas



def fir(x, taps, *, seq_block: int = 2048,
        block_rows: int | None = None, autotune: bool = False):
    """Causal FIR along the last axis. x: (R, S) or (S,).

    ``autotune=True`` picks the row-block from measured candidates (cached
    per shape) instead of the static VWRSpec budget."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    interp = interpret_mode()
    if autotune and block_rows is None:
        from repro.core.autotune import tuned_block_rows

        R, S = x.shape
        block_rows = tuned_block_rows(
            "fir", R, (S, seq_block, str(x.dtype), int(taps.shape[0])),
            lambda rb: fir_pallas(x, taps, seq_block=seq_block,
                                  interpret=interp, block_rows=rb))
    y = fir_pallas(x, taps, seq_block=seq_block, interpret=interp,
                   block_rows=block_rows)
    return y[0] if squeeze else y
