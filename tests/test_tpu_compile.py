"""Compile the serving path's kernels, decode step and prefill for a TPU v5e.

Nothing runs: each test lowers and compiles at StarCoder2-3B widths for a
described (not attached) v5e chip, so the TPU compiler's refusals (block
tiling, VMEM use, a program that does not fit HBM) show up without a chip.
The topology is described inside a fixture, never at import, so that only
the test worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.page_pack.ops import gather_pages, scatter_pages
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.registry import model_for

ARCH = "starcoder2_3b"
BATCH, MAX_LEN = 4, 1024
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    return compiled, compiled.as_text()


def _pool_specs(cfg, one_chip):
    ps = cfg.kv_page_tokens
    pages = BATCH * (-(-MAX_LEN // ps))
    pool = _spec((pages, ps, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16,
                 one_chip)
    table = _spec((BATCH, pages // BATCH), jnp.int32, one_chip)
    return pool, table


def test_paged_attention_kernel(cfg, one_chip):
    pool, table = _pool_specs(cfg, one_chip)
    q = _spec((BATCH, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    lengths = _spec((BATCH,), jnp.int32, one_chip)
    _, hlo = _compile(paged_attention, q, pool, pool, table, lengths)
    assert "tpu_custom_call" in hlo


def test_flash_attention_kernel(cfg, one_chip):
    q = _spec((1, MAX_LEN, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, MAX_LEN, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16,
               one_chip)
    _, hlo = _compile(flash_attention, q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_page_gather(cfg, one_chip):
    pool, _ = _pool_specs(cfg, one_chip)
    idx = _spec((BATCH,), jnp.int32, one_chip)
    _, hlo = _compile(gather_pages, pool, idx)
    assert "tpu_custom_call" in hlo


def test_page_scatter(cfg, one_chip):
    pool, _ = _pool_specs(cfg, one_chip)
    idx = _spec((BATCH,), jnp.int32, one_chip)
    block = _spec((BATCH,) + pool.shape[1:], jnp.bfloat16, one_chip)
    _, hlo = _compile(scatter_pages, pool, idx, block)
    assert "tpu_custom_call" in hlo


def _placed(fn, one_chip):
    """The shapes ``fn`` would make, each placed on the described chip."""
    return jax.tree_util.tree_map(lambda s: _spec(s.shape, s.dtype, one_chip),
                                  jax.eval_shape(fn))


def _fits(compiled) -> bool:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return used < HBM_BYTES


def test_decode_step_full_width(cfg, one_chip):
    model = model_for(cfg)
    params = _placed(lambda: model.init_params(cfg, jax.random.PRNGKey(0)),
                     one_chip)
    cache = _placed(lambda: model.init_decode_cache(cfg, BATCH, MAX_LEN),
                    one_chip)
    tokens = _spec((BATCH, 1), jnp.int32, one_chip)
    step = jax.jit(lambda p, c, t: model.decode_step(p, cfg, c, t))
    compiled, _ = _compile(step, params, cache, tokens)
    assert _fits(compiled)


def test_prefill_full_width(cfg, one_chip):
    """The one-pass prefill of the longest page bucket, a whole 1,024-token
    prompt, into a batch-1 paged cache."""
    model = model_for(cfg)
    params = _placed(lambda: model.init_params(cfg, jax.random.PRNGKey(0)),
                     one_chip)
    cache = _placed(lambda: model.init_decode_cache(cfg, 1, MAX_LEN),
                    one_chip)
    tokens = _spec((1, MAX_LEN), jnp.int32, one_chip)
    length = _spec((), jnp.int32, one_chip)
    fill = jax.jit(lambda p, c, t, n: model.prefill(p, cfg, c, t, n))
    compiled, _ = _compile(fill, params, cache, tokens, length)
    assert _fits(compiled)
