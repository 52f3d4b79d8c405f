"""jit'd wrapper for the flash-attention kernel (model layout (B,S,H,D))."""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, S, H, D); k, v: (B, S, KVH, D) -> (B, S, H, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_kernel(qt, kt, vt, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out.transpose(0, 2, 1, 3)
