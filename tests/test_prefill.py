"""One-pass prefill on the paged K/V layout: the pass writes the cache the
token-by-token loop writes, the served tokens equal a teacher-forced
``forward``, the engine takes the pass only where the cache holds paged
K/V pools, and each page bucket compiles once."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.config import reduced
from repro.models.registry import model_for
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingEngine

MAX_LEN, PAGE = 64, 16
# prompt length -> two more lengths in the same page bucket of n - 1 tokens
CASES = {
    30: (19, 24),   # 29 tokens cross the first page boundary: bucket 32
    17: (2, 9),     # 16 tokens, an exact page: bucket 16, no padding
    1: (1, 1),      # no prefill pass at all
}


def _model(arch, name, **overrides):
    cfg = reduced(get_config(arch), kv_page_tokens=PAGE, **overrides)
    # a name of its own: the process has compiled nothing for this config
    cfg = dataclasses.replace(cfg, name=name)
    return cfg, model_for(cfg).init_params(cfg, jax.random.PRNGKey(0))


def _prompt(cfg, n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, cfg.vocab_size,
                                                     size=n)


def _loop_cache(cfg, params, head):
    """The cache that feeding ``head`` token by token to the decode step
    writes."""
    model = model_for(cfg)
    step = jax.jit(lambda c, t: model.decode_step(params, cfg, c, t))
    cache = model.init_decode_cache(cfg, 1, MAX_LEN)
    for t in head:
        _, cache = step(cache, jnp.asarray([[t]], jnp.int32))
    return cache


def _positions(pool, n):
    """The first ``n`` token positions of a batch-1 pool, whose identity
    page table lays its pages out in order: (L, n, KVH, hd)."""
    pool = np.asarray(pool)
    return pool.reshape(pool.shape[0], -1, *pool.shape[3:])[:, :n]


@pytest.mark.parametrize("n", sorted(CASES))
def test_one_pass_prefill(n):
    cfg, params = _model("starcoder2_3b", f"sc2-prefill-{n}")
    before = engine_mod._prefill._cache_size()
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN)
    # every page bucket of up to max_len - 1 prompt tokens, compiled once,
    # at construction
    buckets = -(-(MAX_LEN - 1) // PAGE)
    assert engine_mod._prefill._cache_size() == before + buckets

    # the pass writes the token loop's cache on the prompt's positions
    prompt = _prompt(cfg, n)
    req = eng.submit(prompt, 6)
    eng._admit()
    got = eng._seq_caches[req.req_id]
    want = _loop_cache(cfg, params, prompt[:-1])
    assert np.asarray(got["lengths"]).tolist() == [n - 1]
    assert np.asarray(want["lengths"]).tolist() == [n - 1]
    for pool in ("k_pool", "v_pool"):
        np.testing.assert_allclose(_positions(got[pool], n - 1),
                                   _positions(want[pool], n - 1),
                                   rtol=1e-5, atol=1e-5)

    # served greedy tokens equal a teacher-forced forward over the same
    # sequence, three prompts of one bucket compiling nothing more
    others = [eng.submit(_prompt(cfg, m, seed=1), 6) for m in CASES[n]]
    eng.run_until_done()
    assert engine_mod._prefill._cache_size() == before + buckets
    model = model_for(cfg)
    for r in [req] + others:
        seq = np.concatenate([r.prompt, r.generated[:-1]]).astype(np.int32)
        logits, _ = model.forward(params, cfg, jnp.asarray(seq)[None])
        ref = np.argmax(np.asarray(logits[0, len(r.prompt) - 1:]), axis=-1)
        assert ref.tolist() == r.generated
    assert eng.stats.prefill_step_calls == 0
    assert eng.stats.prefill_tokens == n - 1 + sum(m - 1 for m in CASES[n])

    # the SWA ring has no paged pool: its prefill stays token by token
    ring_cfg, ring_params = _model("h2o_danube_1_8b", "danube-prefill")
    ring = ServingEngine(ring_cfg, ring_params, max_batch=2, max_len=MAX_LEN)
    ring.submit(_prompt(ring_cfg, n), 2)
    ring.run_until_done()
    assert ring.stats.prefill_step_calls == ring.stats.prefill_tokens == n - 1


def test_paged_moe_prefill_routes_dropless():
    """A paged MoE layer at a capacity that drops tokens in training: the
    pass routes every token, as the decode step does, so it writes the
    token loop's cache."""
    cfg, params = _model("mixtral_8x7b", "moe-prefill", sliding_window=0,
                         capacity_factor=0.25)
    eng = ServingEngine(cfg, params, max_batch=1, max_len=MAX_LEN)
    prompt = _prompt(cfg, 40)
    req = eng.submit(prompt, 1)
    eng._admit()
    got = eng._seq_caches[req.req_id]
    want = _loop_cache(cfg, params, prompt[:-1])
    for pool in ("k_pool", "v_pool"):
        np.testing.assert_allclose(_positions(got[pool], 39),
                                   _positions(want[pool], 39),
                                   rtol=1e-5, atol=1e-5)
