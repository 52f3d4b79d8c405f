"""GQA attention layer: projections + RoPE + qk-norm + SWA + paged decode."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain
from repro.models.attention_ops import (flash_attention_xla,
                                        paged_attention_xla,
                                        ring_buffer_attention)
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_head_norm


def init_attention(key, cfg: ModelConfig, dtype):
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * hd, dtype),
        "wk": dense_init(ks[1], d, KVH * hd, dtype),
        "wv": dense_init(ks[2], d, KVH * hd, dtype),
        "wo": dense_init(ks[3], H * hd, d, dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KVH * hd,), dtype)
        p["bv"] = jnp.zeros((KVH * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _qkv(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KVH, hd)
    v = v.reshape(B, S, KVH, hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "q_seq", "heads", "head_dim")
    k = constrain(k, "batch", "kv_seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "kv_seq", "kv_heads", "head_dim")
    return q, k, v


def apply_attention(p, cfg: ModelConfig, x, positions, *,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    return_kv: bool = False, causal: bool = True):
    """Training / prefill attention (causal, optionally sliding-window)."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = flash_attention_xla(q, k, v, causal=causal,
                              window=cfg.sliding_window if causal else 0,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = constrain(out, "batch", "q_seq", "heads", "head_dim")
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim) @ p["wo"]
    out = constrain(out, "batch", "seq", "embed")
    if return_kv:
        return out, (k, v)
    return out


def _paged_update_and_attend(q1, k1, v1, k_pool, v_pool, page_table,
                             lengths, window: int):
    """Write the new token's K/V into its page, then attend."""
    ps = k_pool.shape[1]
    pos = lengths - 1
    page_slot = pos // ps
    offset = pos % ps
    frame = jnp.take_along_axis(page_table, page_slot[:, None], axis=1)[:, 0]
    frame = jnp.maximum(frame, 0)
    k_pool = k_pool.at[frame, offset].set(k1)
    v_pool = v_pool.at[frame, offset].set(v1)
    out = paged_attention_xla(q1, k_pool, v_pool, page_table, lengths,
                              window=window)
    return out, k_pool, v_pool


def _paged_update_and_attend_dist(q1, k1, v1, k_pool, v_pool, page_table,
                                  lengths, window: int):
    """Locality-explicit variant (the §Perf decode iteration).

    Pool pages and batch rows are co-sharded over the data axes (the
    engine's identity page layout guarantees sequence b's pages live on
    b's shard).  GSPMD cannot prove that, so the plain gather becomes a
    full-pool masked reduce per page step — TB-scale HBM traffic and ~half
    the step in collectives (measured; see EXPERIMENTS.md §Perf).  Under
    shard_map the gather is local: page-table frames are rebased to the
    shard-local pool slice and no collective is emitted at all.
    """
    from jax.sharding import PartitionSpec as P
    import numpy as _np
    from repro.distributed import logical

    mesh = logical.current_mesh()
    daxes = logical.rule("batch")
    B = q1.shape[0]
    P_pages = k_pool.shape[0]
    if mesh is None or daxes is None:
        return _paged_update_and_attend(q1, k1, v1, k_pool, v_pool,
                                        page_table, lengths, window)
    axes = daxes if isinstance(daxes, tuple) else (daxes,)
    dsize = int(_np.prod([mesh.shape[a] for a in axes]))
    if dsize <= 1 or B % dsize or P_pages % dsize:
        return _paged_update_and_attend(q1, k1, v1, k_pool, v_pool,
                                        page_table, lengths, window)
    p_local = P_pages // dsize
    # also split heads over 'model' inside the region when both the query
    # and KV head counts divide it (keeps GQA grouping shard-local and the
    # pool tensor-parallel — without this the pool replicates over model
    # inside the region, a measured 16× per-layer transient for MHA archs)
    msize = mesh.shape.get("model", 1)
    H, KVH = q1.shape[1], k1.shape[1]
    head_tp = "model" in mesh.shape and H % msize == 0 and KVH % msize == 0

    def local_fn(q_l, k1_l, v1_l, kp_l, vp_l, pt_l, len_l):
        rank = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        pt_local = jnp.where(pt_l >= 0, pt_l - rank * p_local, pt_l)
        return _paged_update_and_attend(q_l, k1_l, v1_l, kp_l, vp_l,
                                        pt_local, len_l, window)

    d = daxes
    h = "model" if head_tp else None
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(d, h), P(d, h), P(d, h),
                  P(d, None, h), P(d, None, h), P(d), P(d)),
        out_specs=(P(d, h), P(d, None, h), P(d, None, h)),
        check_vma=False)
    return fn(q1, k1, v1, k_pool, v_pool, page_table, lengths)


def apply_attention_decode_paged(p, cfg: ModelConfig, x, k_pool, v_pool,
                                 page_table, lengths):
    """One-token decode through the paged KV pool.

    x: (B, 1, d).  ``lengths`` counts tokens *including* the current one.
    Each row's new K/V is written at that row's own position (rows of a
    continuous batch hold sequences of different lengths), then attention
    reads the whole context through the page table.
    Returns (out, k_pool, v_pool).
    """
    B = x.shape[0]
    pos = lengths - 1                                     # (B,) current index
    q, k, v = _qkv(p, cfg, x, pos[:, None])
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
    out, k_pool, v_pool = _paged_update_and_attend_dist(
        q1, k1, v1, k_pool, v_pool, page_table, lengths, cfg.sliding_window)
    out = out.reshape(B, cfg.n_heads * cfg.head_dim) @ p["wo"]
    return out[:, None, :], k_pool, v_pool


def apply_attention_decode_ring(p, cfg: ModelConfig, x, k_ring, v_ring,
                                lengths):
    """One-token decode over a sliding-window ring buffer (SWA archs).

    The ring IS the resident set: everything older than the window has
    been "swapped out" — re-touching it is impossible by construction,
    which is why SWA archs run long_500k with a bounded pool.
    Returns (out, k_ring, v_ring).
    """
    B = x.shape[0]
    W = k_ring.shape[1]
    pos = lengths - 1
    q, k, v = _qkv(p, cfg, x, pos[:, None])
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
    rows, slot = jnp.arange(B), pos % W
    k_ring = k_ring.at[rows, slot].set(k1)
    v_ring = v_ring.at[rows, slot].set(v1)
    out = ring_buffer_attention(q1, k_ring, v_ring, lengths, cfg.sliding_window)
    out = out.reshape(B, cfg.n_heads * cfg.head_dim) @ p["wo"]
    return out[:, None, :], k_ring, v_ring
