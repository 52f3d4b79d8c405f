"""Launcher set-up: where the compile cache goes, and the random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import ARCH_IDS, get_config
from repro.launch.common import (CACHE_ENV, REPO_ROOT, random_params,
                                 use_compile_cache)
from repro.models.config import reduced
from repro.models.registry import model_for


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings after a test changes them."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_compile_cache_goes_where_env_says(tmp_path, monkeypatch,
                                           cache_config):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(17.0)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch, cache_config):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = use_compile_cache()
    assert path == str(REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (REPO_ROOT / "chip_smoke.py").is_file()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_random_params_match_eager_init(arch):
    """Same weights as the eager init, up to f32 rounding of the fused
    normal-times-scale under ``jit``."""
    cfg = reduced(get_config(arch))
    eager = model_for(cfg).init_params(cfg, jax.random.PRNGKey(3))
    jitted = random_params(cfg, 3)
    assert (jax.tree_util.tree_structure(eager)
            == jax.tree_util.tree_structure(jitted))
    for a, b in zip(jax.tree_util.tree_leaves(eager),
                    jax.tree_util.tree_leaves(jitted)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
