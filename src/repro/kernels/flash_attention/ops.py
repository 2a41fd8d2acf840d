"""Public jit'd API for the flash-attention kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_pallas



def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    q_chunk: int = 256, kv_chunk: int = 256):
    """Flash attention with GQA and sliding-window support.
    q: (B,Sq,H,dh); k,v: (B,Skv,KV,dh)."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk,
                                  interpret=interpret_mode())
