"""Decoder-only LM assembly for the dense / moe / mla_moe families.

Scan-over-layers with stacked parameters throughout: the whole depth
compiles as one while loop (constant compile time in n_layers — essential
for the 512-device dry-run) and the roofline harness multiplies loop-body
costs by the annotated trip count.

Public surface (used by training/, serving/, launch/):
    init_params(cfg, key)                      -> params pytree
    forward(params, cfg, tokens)               -> logits, aux
    init_decode_cache(cfg, batch, max_len)     -> cache pytree
    prefill(params, cfg, cache, tokens, length) -> cache (paged K/V pools)
    decode_step(params, cfg, cache, tokens)    -> logits, cache
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.attention import (apply_attention,
                                    apply_attention_decode_paged,
                                    apply_attention_decode_ring,
                                    init_attention, _qkv)
from repro.models.config import ModelConfig
from repro.models.layers import (apply_mlp, apply_norm, dtype_of, embed_init,
                                 init_mlp, init_norm, dense_init)


# ------------------------------------------------------------------- helpers
def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def layer_slice(stacked, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


# ---------------------------------------------------------------------- init
def _init_layer(key, cfg: ModelConfig, dtype, moe: bool):
    k_attn, k_mlp = jax.random.split(key)
    p = {"norm1": init_norm(cfg.d_model, cfg.norm),
         "norm2": init_norm(cfg.d_model, cfg.norm)}
    if cfg.family == "mla_moe":
        p["attn"] = mla_mod.init_mla(k_attn, cfg, dtype)
    else:
        p["attn"] = init_attention(k_attn, cfg, dtype)
    if moe:
        p["moe"] = moe_mod.init_moe(k_mlp, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k_mlp, cfg.d_model, cfg.d_ff, cfg.act, dtype,
                            bias=cfg.mlp_bias)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = dtype_of(cfg.dtype)
    n_dense, n_moe = _layer_split(cfg)
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size,
                                       dtype)

    def stacked(layer_keys, moe):
        # made stacked (one layer per key), never as per-layer copies
        return jax.vmap(lambda k: _init_layer(k, cfg, dtype, moe))(layer_keys)

    if n_dense:
        params["dense_layers"] = stacked(keys[2:2 + n_dense], moe=False)
    if n_moe:
        params["moe_layers"] = stacked(keys[2 + n_dense:2 + cfg.n_layers],
                                       moe=True)
    if cfg.mtp_depth:
        params["mtp"] = _stack(
            [_init_layer(keys[2 + cfg.n_layers + 0], cfg, dtype,
                         moe=(cfg.n_experts > 0))
             for _ in range(cfg.mtp_depth)])
    return params


def _layer_split(cfg: ModelConfig) -> tuple[int, int]:
    """(#dense-mlp layers, #moe layers) — deepseek has first_k_dense."""
    if cfg.family == "dense":
        return cfg.n_layers, 0
    if cfg.family == "moe":
        return 0, cfg.n_layers
    if cfg.family == "mla_moe":
        return cfg.first_k_dense, cfg.n_layers - cfg.first_k_dense
    raise ValueError(cfg.family)


# ------------------------------------------------------------- layer bodies
def _apply_layer(lp, cfg: ModelConfig, x, positions, moe: bool,
                 q_chunk: int, kv_chunk: int, prefill: bool):
    h = apply_norm(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    if cfg.family == "mla_moe":
        attn_out = mla_mod.apply_mla(lp["attn"], cfg, h, positions,
                                     q_chunk=q_chunk, kv_chunk=kv_chunk)
        kv = None
    else:
        res = apply_attention(lp["attn"], cfg, h, positions, q_chunk=q_chunk,
                              kv_chunk=kv_chunk, return_kv=prefill)
        attn_out, kv = res if prefill else (res, None)
    x = constrain(x + attn_out, "batch", "seq", "embed")
    h = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
    if moe:
        y, aux = moe_mod.apply_moe(lp["moe"], cfg, h, dropless=prefill)
    else:
        y, aux = apply_mlp(lp["mlp"], h, cfg.act), 0.0
    return constrain(x + y, "batch", "seq", "embed"), aux, kv


def _run_layers(params, cfg: ModelConfig, x, *, q_chunk: int = 512,
                kv_chunk: int = 512, remat: bool = False,
                prefill: bool = False):
    """Every layer over x (B, S, d) at positions 0..S-1 -> (x, aux, kv).

    With ``prefill``, ``kv`` is each layer's post-RoPE (K, V), each
    (L, B, S, KVH, hd) in layer order, and MoE layers route dropless, as
    ``decode_step`` does: a dropped token would serve other tokens.
    """
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    aux_total = 0.0
    kvs = []

    for name, moe in (("dense_layers", False), ("moe_layers", True)):
        if name not in params:
            continue

        def body(carry, lp, moe=moe):
            x, aux = carry
            x, aux_l, kv = _apply_layer(lp, cfg, x, positions, moe,
                                        q_chunk, kv_chunk, prefill)
            return (x, aux + aux_l), kv

        if remat:
            body = jax.checkpoint(body)   # store layer boundaries only
        (x, aux_total), kv = jax.lax.scan(body, (x, aux_total), params[name])
        kvs.append(kv)

    kv = (tuple(jnp.concatenate(part) for part in zip(*kvs))
          if prefill else None)
    return x, aux_total, kv


# -------------------------------------------------------------------- forward
def forward(params, cfg: ModelConfig, tokens, *, q_chunk: int = 512,
            kv_chunk: int = 512, embeddings: Optional[jax.Array] = None,
            remat: bool = False):
    """tokens: (B, S) int32 -> (logits (B, S, V), aux).

    ``embeddings`` overrides the token embedding (modality-frontend stub
    path for the VLM/audio archs — precomputed patch/frame embeddings).
    """
    x = params["embed"][tokens] if embeddings is None else embeddings
    x = constrain(x, "batch", "seq", "embed")
    x, aux_total, _ = _run_layers(params, cfg, x, q_chunk=q_chunk,
                                  kv_chunk=kv_chunk, remat=remat)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, aux_total


def loss_fn(params, cfg: ModelConfig, tokens, labels, *, q_chunk: int = 512,
            kv_chunk: int = 512, remat: bool = False):
    logits, aux = forward(params, cfg, tokens, q_chunk=q_chunk,
                          kv_chunk=kv_chunk, remat=remat)
    from repro.models.losses import masked_xent
    return masked_xent(logits, labels, aux)


# ================================================================== decoding
def uses_ring(cfg: ModelConfig) -> bool:
    return cfg.sliding_window > 0


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=None) -> dict:
    """Cache pytree for one-token decode.

    * full-attention archs: paged pools (L, P, page, KVH, hd) + page table;
    * SWA archs: ring buffers (L, B, W, KVH, hd) — the resident window;
    * MLA: paged latent pools (L, P, page, rkv/rope).
    All layouts include ``lengths`` (B,) of tokens seen so far.
    """
    dtype = dtype or dtype_of(cfg.dtype)
    L = cfg.n_layers
    cache: dict[str, Any] = {
        "lengths": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "mla_moe":
        ps = cfg.kv_page_tokens
        n_pages = batch * (-(-max_len // ps))
        cache["ckv_pool"] = jnp.zeros((L, n_pages, ps, cfg.kv_lora_rank), dtype)
        cache["krope_pool"] = jnp.zeros((L, n_pages, ps, cfg.qk_rope_head_dim),
                                        dtype)
        cache["page_table"] = _identity_page_table(batch, max_len, ps)
    elif uses_ring(cfg):
        W = cfg.sliding_window
        cache["k_ring"] = jnp.zeros((L, batch, W, cfg.n_kv_heads, cfg.head_dim),
                                    dtype)
        cache["v_ring"] = jnp.zeros((L, batch, W, cfg.n_kv_heads, cfg.head_dim),
                                    dtype)
    else:
        ps = cfg.kv_page_tokens
        n_pages = batch * (-(-max_len // ps))
        cache["k_pool"] = jnp.zeros((L, n_pages, ps, cfg.n_kv_heads,
                                     cfg.head_dim), dtype)
        cache["v_pool"] = jnp.zeros((L, n_pages, ps, cfg.n_kv_heads,
                                     cfg.head_dim), dtype)
        cache["page_table"] = _identity_page_table(batch, max_len, ps)
    return cache


def _identity_page_table(batch: int, max_len: int, ps: int):
    per_seq = -(-max_len // ps)
    return (jnp.arange(batch * per_seq, dtype=jnp.int32)
            .reshape(batch, per_seq))


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """One decode step.  tokens: (B, 1) int32 -> (logits (B,1,V), cache)."""
    x = params["embed"][tokens]
    lengths = cache["lengths"] + 1
    new_cache = dict(cache, lengths=lengths)
    layer_idx = 0

    for name, moe in (("dense_layers", False), ("moe_layers", True)):
        if name not in params:
            continue
        n = jax.tree_util.tree_leaves(params[name])[0].shape[0]

        if cfg.family == "mla_moe":
            pools = (new_cache["ckv_pool"][layer_idx:layer_idx + n],
                     new_cache["krope_pool"][layer_idx:layer_idx + n])

            def body(x, inp, moe=moe):
                lp, ckv, krope = inp
                h = apply_norm(lp["norm1"], x, cfg.norm, cfg.norm_eps)
                attn, ckv, krope = mla_mod.apply_mla_decode_paged(
                    lp["attn"], cfg, h, ckv, krope, cache["page_table"],
                    lengths)
                x = x + attn
                h = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
                if moe:
                    y, _ = moe_mod.apply_moe(lp["moe"], cfg, h, dropless=True)
                else:
                    y = apply_mlp(lp["mlp"], h, cfg.act)
                return x + y, (ckv, krope)

            x, (ckv_new, krope_new) = jax.lax.scan(
                body, x, (params[name],) + pools)
            new_cache["ckv_pool"] = (new_cache["ckv_pool"]
                                     .at[layer_idx:layer_idx + n].set(ckv_new))
            new_cache["krope_pool"] = (new_cache["krope_pool"]
                                       .at[layer_idx:layer_idx + n]
                                       .set(krope_new))
        elif uses_ring(cfg):
            rings = (new_cache["k_ring"][layer_idx:layer_idx + n],
                     new_cache["v_ring"][layer_idx:layer_idx + n])

            def body(x, inp, moe=moe):
                lp, kr, vr = inp
                h = apply_norm(lp["norm1"], x, cfg.norm, cfg.norm_eps)
                attn, kr, vr = apply_attention_decode_ring(
                    lp["attn"], cfg, h, kr, vr, lengths)
                x = x + attn
                h = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
                if moe:
                    y, _ = moe_mod.apply_moe(lp["moe"], cfg, h, dropless=True)
                else:
                    y = apply_mlp(lp["mlp"], h, cfg.act)
                return x + y, (kr, vr)

            x, (k_new, v_new) = jax.lax.scan(body, x, (params[name],) + rings)
            new_cache["k_ring"] = (new_cache["k_ring"]
                                   .at[layer_idx:layer_idx + n].set(k_new))
            new_cache["v_ring"] = (new_cache["v_ring"]
                                   .at[layer_idx:layer_idx + n].set(v_new))
        else:
            pools = (new_cache["k_pool"][layer_idx:layer_idx + n],
                     new_cache["v_pool"][layer_idx:layer_idx + n])

            def body(x, inp, moe=moe):
                lp, kp, vp = inp
                h = apply_norm(lp["norm1"], x, cfg.norm, cfg.norm_eps)
                attn, kp, vp = apply_attention_decode_paged(
                    lp["attn"], cfg, h, kp, vp, cache["page_table"], lengths)
                x = x + attn
                h = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
                if moe:
                    y, _ = moe_mod.apply_moe(lp["moe"], cfg, h, dropless=True)
                else:
                    y = apply_mlp(lp["mlp"], h, cfg.act)
                return x + y, (kp, vp)

            x, (k_new, v_new) = jax.lax.scan(body, x, (params[name],) + pools)
            new_cache["k_pool"] = (new_cache["k_pool"]
                                   .at[layer_idx:layer_idx + n].set(k_new))
            new_cache["v_pool"] = (new_cache["v_pool"]
                                   .at[layer_idx:layer_idx + n].set(v_new))
        layer_idx += n

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, new_cache


def prefill(params, cfg: ModelConfig, cache, tokens, length):
    """One causal pass over a prompt at batch 1, its K/V written into a
    batch-1 paged decode cache (``k_pool``/``v_pool`` layout only).

    tokens: (1, S) int32, S a whole number of pages: the first ``length``
    are the prompt's, the rest padding.  Padding lies beyond ``lengths``:
    causal masking keeps the real positions from it, paged decode
    attention masks it, and the next decode write takes its first slot.
    Page j of the pass goes to the frame the page table names for slot j
    (``page_pack``'s scatter).  Returns the cache with ``lengths`` =
    ``length``.  No LM head: nothing reads a prompt's logits.
    """
    x = constrain(params["embed"][tokens], "batch", "seq", "embed")
    _, _, (k, v) = _run_layers(params, cfg, x, prefill=True)
    L, _, S, KVH, hd = k.shape
    ps = cache["k_pool"].shape[2]
    frames = cache["page_table"][0, :S // ps]

    def pack(pool, new):
        return pool.at[:, frames].set(new.reshape(L, S // ps, ps, KVH, hd))

    return dict(cache, k_pool=pack(cache["k_pool"], k),
                v_pool=pack(cache["v_pool"], v),
                lengths=jnp.full_like(cache["lengths"], length))
