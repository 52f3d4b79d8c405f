"""Multi-head Latent Attention (DeepSeek-V3) with a paged *latent* cache.

The KV cache stores only the compressed latent ``c_kv`` (kv_lora_rank) and
the shared RoPE key (qk_rope_head_dim) per token — 576 dims/token for the
assigned config instead of n_heads × (d_k + d_v).  This makes MLA the
best-case architecture for the thesis' paged-memory technique: the latent
pages are small, uniform, and read through the page table exactly like the
GQA pool (DESIGN.md §4).

Decode uses the *absorbed* form: W_UK is folded into the query and W_UV
into the output so attention runs entirely in latent space and never
expands per-head keys/values for the context.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.attention_ops import NEG_INF, flash_attention_xla
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, init_norm, apply_norm


def init_mla(key, cfg: ModelConfig, dtype):
    d, H = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], d, rq, dtype),
        "q_norm": init_norm(rq),
        "wq_b": dense_init(ks[1], rq, H * (nope + rope), dtype),
        "wkv_a": dense_init(ks[2], d, rkv + rope, dtype),
        "kv_norm": init_norm(rkv),
        "wk_b": dense_init(ks[3], rkv, H * nope, dtype),
        "wv_b": dense_init(ks[4], rkv, H * vh, dtype),
        "wo": dense_init(ks[5], H * vh, d, dtype),
    }


def _latents(p, cfg: ModelConfig, x, positions):
    """Shared projection path: q heads + (c_kv, k_rope) latents."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = apply_norm(p["q_norm"], x @ p["wq_a"], "rms", cfg.norm_eps)
    q = (cq @ p["wq_b"]).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"]
    c_kv = apply_norm(p["kv_norm"], kv[..., :cfg.kv_lora_rank], "rms",
                      cfg.norm_eps)
    k_rope = kv[..., cfg.kv_lora_rank:][:, :, None, :]       # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def apply_mla(p, cfg: ModelConfig, x, positions, *, q_chunk=512, kv_chunk=512):
    """Training / prefill: expand per-head K/V and run flash attention."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope, c_kv, k_rope = _latents(p, cfg, x, positions)
    k_nope = (c_kv @ p["wk_b"]).reshape(B, S, H, nope)
    v = (c_kv @ p["wv_b"]).reshape(B, S, H, vh)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, H, rope))], axis=-1)
    # pad v to the qk head_dim so flash kernels see one head size; strip after
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rope - vh)))
    out = flash_attention_xla(q, k, v_p, causal=True, q_chunk=q_chunk,
                              kv_chunk=kv_chunk)[..., :vh]
    return out.reshape(B, S, H * vh) @ p["wo"]


def _mla_update_and_attend(q_abs, q_rope, c_new, kr_new, ckv_pool,
                           krope_pool, page_table, lengths, *, scale: float):
    """Pool write + absorbed-latent page scan (shard_map-able body)."""
    B, H, rkv = q_abs.shape
    ps = ckv_pool.shape[1]
    pos = lengths - 1
    page_slot = pos // ps
    offset = pos % ps
    frame = jnp.take_along_axis(page_table, page_slot[:, None], axis=1)[:, 0]
    frame = jnp.maximum(frame, 0)
    ckv_pool = ckv_pool.at[frame, offset].set(c_new)
    krope_pool = krope_pool.at[frame, offset].set(kr_new)
    max_pages = page_table.shape[1]

    def page_step(carry, j):
        m, l, acc = carry
        idx = page_table[:, j]
        safe = jnp.maximum(idx, 0)
        c_pg = ckv_pool[safe].astype(jnp.float32)             # (B, ps, rkv)
        r_pg = krope_pool[safe].astype(jnp.float32)           # (B, ps, rope)
        s = (jnp.einsum("bhr,bkr->bhk", q_abs, c_pg)
             + jnp.einsum("bhr,bkr->bhk", q_rope.astype(jnp.float32), r_pg))
        s = s * scale
        posk = j * ps + jnp.arange(ps)
        valid = (posk[None, :] < lengths[:, None]) & (idx >= 0)[:, None]
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        pw = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pw.sum(axis=-1)
        ctx = jnp.einsum("bhk,bkr->bhr", pw, c_pg)
        acc_new = acc * corr[..., None] + ctx
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, rkv), jnp.float32)
    (m, l, ctx), _ = jax.lax.scan(page_step, (m0, l0, a0),
                                  jnp.arange(max_pages))
    ctx = ctx / jnp.maximum(l[..., None], 1e-30)              # (B, H, rkv)
    return ctx, ckv_pool, krope_pool


def _mla_update_and_attend_dist(q_abs, q_rope, c_new, kr_new, ckv_pool,
                                krope_pool, page_table, lengths, *,
                                scale: float):
    """shard_map variant: batch+pages co-sharded over the data axes, query
    heads split over 'model' (the latent pools have no head dim — they
    transit the region replicated over 'model', one layer slice at a time).
    Same locality argument as the GQA path (EXPERIMENTS.md §Perf iter. 5).
    """
    from jax.sharding import PartitionSpec as P
    import numpy as _np
    from repro.distributed import logical

    mesh = logical.current_mesh()
    daxes = logical.rule("batch")
    B, H, _ = q_abs.shape
    P_pages = ckv_pool.shape[0]
    if mesh is None or daxes is None:
        return _mla_update_and_attend(q_abs, q_rope, c_new, kr_new, ckv_pool,
                                      krope_pool, page_table, lengths,
                                      scale=scale)
    axes = daxes if isinstance(daxes, tuple) else (daxes,)
    dsize = int(_np.prod([mesh.shape[a] for a in axes]))
    if dsize <= 1 or B % dsize or P_pages % dsize:
        return _mla_update_and_attend(q_abs, q_rope, c_new, kr_new, ckv_pool,
                                      krope_pool, page_table, lengths,
                                      scale=scale)
    p_local = P_pages // dsize
    msize = mesh.shape.get("model", 1)
    h = "model" if ("model" in mesh.shape and H % msize == 0) else None

    def local_fn(qa, qr, cn, kn, cp, kp, pt, ln):
        rank = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        pt_local = jnp.where(pt >= 0, pt - rank * p_local, pt)
        return _mla_update_and_attend(qa, qr, cn, kn, cp, kp, pt_local, ln,
                                      scale=scale)

    d = daxes
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(d, h), P(d, h), P(d), P(d), P(d), P(d), P(d), P(d)),
        out_specs=(P(d, h), P(d), P(d)),
        check_vma=False)
    return fn(q_abs, q_rope, c_new, kr_new, ckv_pool, krope_pool,
              page_table, lengths)


def apply_mla_decode_paged(p, cfg: ModelConfig, x, ckv_pool, krope_pool,
                           page_table, lengths):
    """Absorbed-form decode through the paged latent cache.

    ckv_pool:   (P, page_tokens, kv_lora_rank)
    krope_pool: (P, page_tokens, qk_rope_head_dim)
    Returns (out, ckv_pool, krope_pool).
    """
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    pos = lengths - 1
    q_nope, q_rope, c_kv, k_rope = _latents(p, cfg, x, pos[:, None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]               # (B,H,*)
    c_new, kr_new = c_kv[:, 0], k_rope[:, 0]

    # absorb W_UK into q:  q_abs (B,H,rkv)
    wk_b = p["wk_b"].reshape(rkv, H, nope)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = 1.0 / math.sqrt(nope + rope)
    ctx, ckv_pool, krope_pool = _mla_update_and_attend_dist(
        q_abs, q_rope.astype(jnp.float32), c_new, kr_new, ckv_pool,
        krope_pool, page_table, lengths, scale=scale)
    wv_b = p["wv_b"].reshape(rkv, H, vh)
    out = jnp.einsum("bhr,rhv->bhv", ctx, wv_b.astype(jnp.float32))
    out = out.reshape(B, H * vh).astype(x.dtype) @ p["wo"]
    return out[:, None, :], ckv_pool, krope_pool
