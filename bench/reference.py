"""Plain reference of the served models, and its lower-precision control.

Written from the published descriptions (pre-norm decoder, grouped-query
attention with rotate-half RoPE, LayerNorm + tanh-GELU MLP with biases for
StarCoder2, RMSNorm + SwiGLU for the Mistral-style H2O-Danube), in
float32 at ``Precision.HIGHEST``, with no cache, paging or batching
tricks: one causal pass over the whole sequence.  It imports nothing of
the program.  Its weights are its own, drawn from the seed by the same
recipe as the served weights (per-tensor normal draws from a key split
per layer, scaled by 1/sqrt(fan-in), stored in the served dtype; biases
zero, norm scales one), so the two hold bit-identical weights without
the reference taking anything the program made.

The control is the same pass with every linear layer computed in fp8
(e4m3): weights scaled per output column and activations per token, the
step from bfloat16 that a later change might be tempted to take.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.model_spec import ModelSpec

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
FP8_MAX = 448.0                       # largest finite float8_e4m3fn


# ------------------------------------------------------------------ weights
def _dtype(m: ModelSpec):
    return {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
            "float32": jnp.float32}[m.dtype]


def _dense(key, d_in: int, d_out: int, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32)
    return (w * (1.0 / np.sqrt(d_in))).astype(dtype)


def _layer_weights(key, m: ModelSpec):
    dt = _dtype(m)
    d, H, KVH, hd, f = m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    w = {"wq": _dense(ka[0], d, H * hd, dt),
         "wk": _dense(ka[1], d, KVH * hd, dt),
         "wv": _dense(ka[2], d, KVH * hd, dt),
         "wo": _dense(ka[3], H * hd, d, dt),
         "norm1": _norm_weights(m), "norm2": _norm_weights(m)}
    if m.mlp == "swiglu":
        w.update(gate=_dense(km[0], d, f, dt), up=_dense(km[1], d, f, dt),
                 down=_dense(km[2], f, d, dt))
    else:
        w.update(fc=_dense(km[0], d, f, dt), proj=_dense(km[1], f, d, dt))
    if m.bias:
        w.update(bq=jnp.zeros((H * hd,), dt), bk=jnp.zeros((KVH * hd,), dt),
                 bv=jnp.zeros((KVH * hd,), dt), bo=jnp.zeros((d,), dt),
                 b_fc=jnp.zeros((f,), dt), b_proj=jnp.zeros((d,), dt))
    return w


def _norm_weights(m: ModelSpec):
    w = {"scale": jnp.ones((m.d_model,), jnp.float32)}
    if m.norm == "layer_norm":
        w["bias"] = jnp.zeros((m.d_model,), jnp.float32)
    return w


@functools.partial(jax.jit, static_argnums=0)
def _init(m: ModelSpec, key):
    keys = jax.random.split(key, m.n_layers + 3)
    w = {"embed": (jax.random.normal(keys[0], (m.vocab, m.d_model),
                                     jnp.float32) * 0.02).astype(_dtype(m)),
         "final_norm": _norm_weights(m),
         "layers": jax.vmap(lambda k: _layer_weights(k, m))(
             keys[2:2 + m.n_layers])}
    if not m.tie_embeddings:
        w["head"] = _dense(keys[1], m.d_model, m.vocab, _dtype(m))
    return w


def init_weights(m: ModelSpec, seed: int):
    """The model's weights drawn from ``seed``, in the served dtype."""
    return _init(m, jax.random.PRNGKey(seed))


# ------------------------------------------------------------------ forward
def _fp8(x, axis: int):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, b, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y if b is None else y + b.astype(jnp.float32)


def _norm(x, w, m: ModelSpec):
    if m.norm == "layer_norm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m.norm_eps) * w["scale"] + w["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + m.norm_eps) * w["scale"]


def _rope(x, positions, theta: float):
    """Rotate-half rotary embedding; x: (B, S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, m: ModelSpec):
    """Causal (and, where the model has one, windowed) attention."""
    B, S, H, hd = q.shape
    G = H // m.n_kv_heads
    qg = q.reshape(B, S, m.n_kv_heads, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   precision=HIGHEST) / math.sqrt(hd)
    i = jnp.arange(S)
    mask = i[:, None] >= i[None, :]
    if m.window:
        mask &= (i[:, None] - i[None, :]) < m.window
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST)
    return o.reshape(B, S, H * hd)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(x, w, m: ModelSpec, positions, fp8: bool):
    B, S, _ = x.shape
    hd = m.head_dim
    h = _norm(x, w["norm1"], m)
    q = _linear(h, w["wq"], w.get("bq"), fp8).reshape(B, S, m.n_heads, hd)
    k = _linear(h, w["wk"], w.get("bk"), fp8).reshape(B, S, m.n_kv_heads, hd)
    v = _linear(h, w["wv"], w.get("bv"), fp8).reshape(B, S, m.n_kv_heads, hd)
    q, k = _rope(q, positions, m.rope_theta), _rope(k, positions, m.rope_theta)
    x = x + _linear(_attention(q, k, v, m), w["wo"], w.get("bo"), fp8)
    h = _norm(x, w["norm2"], m)
    if m.mlp == "swiglu":
        g = _linear(h, w["gate"], None, fp8)
        y = _linear(g * jax.nn.sigmoid(g) * _linear(h, w["up"], None, fp8),
                    w["down"], None, fp8)
    else:
        y = _linear(_gelu_tanh(_linear(h, w["fc"], w.get("b_fc"), fp8)),
                    w["proj"], w.get("b_proj"), fp8)
    return x + y


def _logits_at(w, m: ModelSpec, tokens, positions, fp8: bool):
    """Logits (B, T, V) at ``positions`` of a causal pass over ``tokens``."""
    x = w["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[1])
    x, _ = jax.lax.scan(lambda x, lw: (_block(x, lw, m, pos, fp8), None),
                        x, w["layers"])
    x = _norm(x, w["final_norm"], m)
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    head = w["embed"].T if m.tie_embeddings else w["head"]
    return _linear(x, head, None, fp8)


@functools.partial(jax.jit, static_argnums=(1, 5))
def readings(w, m: ModelSpec, tokens, positions, served, control: bool):
    """Per position: how far the reference logit of the served token lies
    below the reference's best, and (with ``control``) the same gap for the
    token that the fp8 pass puts first.  All (B, T) float32."""
    ref = _logits_at(w, m, tokens, positions, False)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    first = jnp.argmax(_logits_at(w, m, tokens, positions, True), -1)
    return gap, best - jnp.take_along_axis(ref, first[..., None], -1)[..., 0]
