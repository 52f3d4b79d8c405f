"""jit'd model-layout wrapper for the paged-attention kernel.

Model layout (what serving/decoder.py uses):
    q (B, H, D);  k/v_pool (P, ps, KVH, D);  page_table (B, NP); lengths (B,)
Kernel layout:
    q (B, KVH, G, D);  k/v_pool (KVH, P, ps, D)

The kernel is compiled for the TPU; pass ``interpret=True`` to run it
through the Pallas interpreter on another backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import paged_attention_kernel


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    window: int = 0, interpret: bool = False):
    B, H, D = q.shape
    P, ps, KVH, _ = k_pool.shape
    G = H // KVH
    qk = q.reshape(B, KVH, G, D)
    kp = k_pool.transpose(2, 0, 1, 3)          # (KVH, P, ps, D)
    vp = v_pool.transpose(2, 0, 1, 3)
    out = paged_attention_kernel(qk, kp, vp, page_table.astype(jnp.int32),
                                 lengths.astype(jnp.int32), window=window,
                                 interpret=interpret)
    return out.reshape(B, H, D)
