"""Operations and bytes that the model work of serving needs: one decode
step over a batch of rows, and the prefill of one prompt.

The work, not what a particular lowering does: every weight read once per
step or per prompt, each new token's K/V written once, and in a decode
step the K/V of the context that attention may see (the window bounds it)
read once.  A lowering that copies the whole cache, reads pages past a
sequence's length, or feeds a prompt one token at a time does more than
this and so reads below 100% of its roofline.  Norm arithmetic, rotary
embedding and softmax are left out; they are a few operations per element
against the thousands of a matrix product.
"""

from __future__ import annotations

from typing import Sequence

from bench.model_spec import ModelSpec

F32 = 4


def matmul_params(m: ModelSpec) -> int:
    """Weights that multiply every token: projections, MLP and LM head."""
    d, hd = m.d_model, m.head_dim
    attn = d * m.n_heads * hd + 2 * d * m.n_kv_heads * hd + m.n_heads * hd * d
    mlp = (3 if m.mlp == "swiglu" else 2) * d * m.d_ff
    return m.n_layers * (attn + mlp) + d * m.vocab


def param_bytes(m: ModelSpec) -> int:
    """Bytes of the whole parameter tree as the program holds it: matrices,
    embedding and biases in the served dtype, norm parameters in float32."""
    d, hd, L = m.d_model, m.head_dim, m.n_layers
    matrices = matmul_params(m) - (d * m.vocab if m.tie_embeddings else 0)
    matrices += m.vocab * d                               # embedding table
    biases = 0
    if m.bias:   # q, k, v projections and both MLP layers carry biases
        biases = L * (m.n_heads * hd + 2 * m.n_kv_heads * hd + m.d_ff + d)
    per_norm = d * (2 if m.norm == "layer_norm" else 1)
    norms = (2 * L + 1) * per_norm
    return (matrices + biases) * m.dtype_bytes + norms * F32


def kv_bytes_per_token(m: ModelSpec) -> int:
    """K and V of one token over all layers."""
    return 2 * m.n_layers * m.n_kv_heads * m.head_dim * m.dtype_bytes


def visible(m: ModelSpec, context: int) -> int:
    """Tokens a query at ``context`` (tokens including itself) attends to."""
    return min(context, m.window) if m.window else context


def token_flops(m: ModelSpec, context: int) -> int:
    """Operations of one token at ``context``: matrix products plus the
    score and value products of attention over what it may see."""
    attn = 4 * m.n_layers * m.n_heads * m.head_dim * visible(m, context)
    return 2 * matmul_params(m) + attn


def step_flops(m: ModelSpec, contexts: Sequence[int]) -> int:
    """One call of the step over rows at these contexts."""
    return sum(token_flops(m, c) for c in contexts)


def weight_bytes(m: ModelSpec) -> int:
    """Weights read once: every parameter but the embedding table, whose
    rows are counted where they are looked up, and the table whole where it
    is the tied LM head."""
    weights = param_bytes(m) - m.vocab * m.d_model * m.dtype_bytes
    if m.tie_embeddings:          # the head is the table, read whole
        weights += m.vocab * m.d_model * m.dtype_bytes
    return weights


def step_bytes(m: ModelSpec, contexts: Sequence[int]) -> int:
    """Bytes one call needs to move: the weights once, each row's embedding
    row, its visible K/V read and its new K/V written, and the logits
    written."""
    b = m.dtype_bytes
    rows = len(contexts)
    kv = kv_bytes_per_token(m)
    kv_moved = sum(visible(m, c) for c in contexts) * kv + rows * kv
    return weight_bytes(m) + rows * m.d_model * b + kv_moved \
        + rows * m.vocab * b


def prefill_flops(m: ModelSpec, n: int) -> int:
    """Prefill of a prompt of ``n`` tokens: its first ``n - 1`` tokens, each
    at its own context; the last enters through a decode step as a row."""
    return sum(token_flops(m, c) for c in range(1, n))


def prefill_bytes(m: ModelSpec, n: int) -> int:
    """Bytes the prefill of a prompt of ``n`` tokens needs to move: the
    weights once, the ``n - 1`` embedding rows, and their K/V written
    once.  A prompt of one token needs no prefill pass."""
    rows = n - 1
    if rows <= 0:
        return 0
    return weight_bytes(m) + rows * m.d_model * m.dtype_bytes \
        + rows * kv_bytes_per_token(m)


def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """Least time the chip could take for work of ``flops`` operations that
    moves ``nbytes``: the larger of the two at the chip's peaks."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def roofline_seconds(m: ModelSpec, contexts: Sequence[int],
                     peaks: dict) -> float:
    """Least time the chip could take for one call."""
    return least_seconds(step_flops(m, contexts), step_bytes(m, contexts),
                         peaks)


def prefill_seconds(m: ModelSpec, n: int, peaks: dict) -> float:
    """Least time the chip could take to prefill a prompt of ``n``
    tokens."""
    return least_seconds(prefill_flops(m, n), prefill_bytes(m, n), peaks)
