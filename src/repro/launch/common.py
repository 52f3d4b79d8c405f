"""Set-up shared by the launchers and ``chip_smoke.py``: the persistent
compilation cache and random weights."""

from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.models.config import ModelConfig
from repro.models.registry import model_for

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, it alone decides the
    directory.  Otherwise the cache is ``<repo>/.jax_cache``: a fixed path,
    so that the next run of this checkout finds what this one compiled.
    Call it before the first compile, since JAX decides once per process
    whether the cache is in use.
    """
    path = os.environ.get(CACHE_ENV) or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def random_params(cfg: ModelConfig, seed: int):
    """Random weights from ``seed``, made by one compiled program.

    Under ``jit`` XLA builds each stacked per-layer array in place; eagerly,
    every layer's arrays would exist twice (once alone, once stacked),
    which at published widths approaches twice the parameter bytes.
    """
    init = jax.jit(model_for(cfg).init_params, static_argnums=0)
    return init(cfg, jax.random.PRNGKey(seed))
