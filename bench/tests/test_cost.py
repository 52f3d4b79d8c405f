"""The cost functions against the program's own shapes, at published widths
(shapes only: nothing is allocated)."""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench import cost
from bench.harness import program_config
from bench.model_spec import ModelSpec
from bench.spec import BENCH_DIR

from .conftest import file_config, tiny_spec

CONFIGS = {"starcoder2_3b": 30_720, "h2o_danube_1_8b": 61_440}
# parameters of the published checkpoints, in billions to two places (the
# model cards' safetensors counts); StarCoder2-3B ties its LM head to the
# embedding
PUBLISHED_B = {"starcoder2_3b": 3.03, "h2o_danube_1_8b": 1.83}


def _load(name):
    """The benchmark's configuration file of ``name``, or where it has none
    the program's own config at its published widths."""
    path = BENCH_DIR / "configs" / f"{name}.json"
    conf = json.loads(path.read_text()) if path.exists() \
        else file_config(name)
    return conf, program_config(conf), ModelSpec.from_config(conf)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_bytes_match_the_program(name):
    from repro.models.registry import model_for
    _, cfg, m = _load(name)
    tree = jax.eval_shape(lambda k: model_for(cfg).init_params(cfg, k),
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert cost.param_bytes(m) == sum(x.size * x.dtype.itemsize
                                      for x in leaves)
    # ModelConfig.param_count() counts every matrix and the norm scales,
    # but not the biases nor LayerNorm's shift
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    shifts = sum(x.size for path, x in flat
                 if str(path[-1].key) in ("bias", "bq", "bk", "bv", "bi",
                                          "bo"))
    assert sum(x.size for x in leaves) - shifts == cfg.param_count()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_parameter_count_is_published(name):
    _, cfg, m = _load(name)
    assert round(cfg.param_count() / 1e9, 2) == PUBLISHED_B[name]
    table = 0 if m.tie_embeddings else m.vocab * m.d_model
    assert round((cost.matmul_params(m) + table) / 1e9, 2) \
        == PUBLISHED_B[name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_kv_bytes_per_token(name):
    from repro.models.registry import model_for
    _, cfg, m = _load(name)
    assert cost.kv_bytes_per_token(m) == CONFIGS[name]
    cache = jax.eval_shape(
        lambda: model_for(cfg).init_decode_cache(cfg, 2, 512))
    kv = [x for k, x in cache.items() if k.startswith(("k_", "v_"))]
    slots = 2 * (cfg.sliding_window or 512)   # ring: window slots a sequence
    assert sum(x.size * x.dtype.itemsize for x in kv) // slots == CONFIGS[name]


def test_step_bytes_and_flops():
    _, _, m = _load("starcoder2_3b")
    # batch 1 at context 1 reads every weight once (the tied embedding table
    # whole, as the LM head) and its token's row of the table, and writes
    # one token's K/V and logits
    assert m.tie_embeddings
    b = cost.step_bytes(m, [1])
    assert b == cost.param_bytes(m) + m.d_model * 2 \
        + 2 * 30_720 + m.vocab * 2
    # a decode batch adds each row's visible context
    assert cost.step_bytes(m, [10, 20]) - cost.step_bytes(m, [1, 1]) \
        == 28 * 30_720
    assert cost.token_flops(m, 1) == 2 * cost.matmul_params(m) \
        + 4 * 30 * 24 * 128
    # one token of this model is weight-read bound on the v5e
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert cost.roofline_seconds(m, [512], peaks) == pytest.approx(
        cost.step_bytes(m, [512]) / 819e9)


def test_window_bounds_attention():
    _, _, m = _load("h2o_danube_1_8b")
    assert m.window == 4096
    assert cost.visible(m, 5000) == 4096
    assert cost.token_flops(m, 5000) == cost.token_flops(m, 4096)
    assert np.isclose(cost.token_flops(m, 1000) - cost.token_flops(m, 999),
                      4 * 24 * 32 * 80)


def test_prefill_bytes_and_flops():
    m = tiny_spec()
    assert cost.matmul_params(m) == 2 * (192 + 256) + 8 * 32
    # a token at context c: 2 × 1,152 weights, and 4 × 2 layers × 2 heads
    # × 4 of attention per visible token
    assert cost.token_flops(m, 3) == 2304 + 64 * 3
    # a prompt of 4 tokens prefills its first 3, at contexts 1, 2 and 3
    assert cost.prefill_flops(m, 4) == 3 * 2304 + 64 * (1 + 2 + 3)
    assert cost.prefill_flops(tiny_spec(window=2), 4) \
        == 3 * 2304 + 64 * (1 + 2 + 2)
    # weights: 896 matrices, the 256 of the table read whole as the head,
    # 5 LayerNorms of 16 float32; then 3 embedding rows of 8 and 3 tokens'
    # K/V of 2 layers × 1 head × 4 × 2, written once
    assert cost.param_bytes(m) == (896 + 256) * 2 + 5 * 16 * 4
    assert cost.weight_bytes(m) == cost.param_bytes(m) == 2624
    assert cost.kv_bytes_per_token(m) == 32
    assert cost.prefill_bytes(m, 4) == 2624 + 3 * 8 * 2 + 3 * 32
    # the weights count once per prompt, not once per token
    assert cost.prefill_bytes(m, 9) - cost.prefill_bytes(m, 5) == 4 * 48
    # a prompt of one token enters through the decode step alone
    assert cost.prefill_flops(m, 1) == cost.prefill_bytes(m, 1) == 0
    assert cost.prefill_seconds(m, 1, {"bf16_flops_per_s": 1.0,
                                       "hbm_bytes_per_s": 1.0}) == 0
    # the least time is the larger of the two
    assert cost.prefill_seconds(m, 4, {"bf16_flops_per_s": 1.0,
                                       "hbm_bytes_per_s": 1e9}) == 7296.0
    assert cost.prefill_seconds(m, 4, {"bf16_flops_per_s": 1e9,
                                       "hbm_bytes_per_s": 1.0}) == 2768.0
