"""Public jit'd API for the shuffle kernel (auto interpret off-TPU)."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.shuffle.kernel import shuffle_pallas
from repro.kernels.shuffle.ref import shuffle_ref  # noqa: F401



def shuffle(a, b, op: str, *, half: str = "both", amount: int = 32):
    """VWR2A shuffle-unit op on (R, N) blocks (N = power of two)."""
    return shuffle_pallas(a, b, op=op, half=half, amount=amount,
                          interpret=interpret_mode())
