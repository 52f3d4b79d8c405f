"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

``pipeline_apply`` runs L stacked layers as S stages × (L/S) layers per
stage under ``shard_map``: microbatches stream through stages with
``jax.lax.ppermute`` moving activations stage→stage each tick.  The
classic GPipe schedule (fill, steady state, drain) emerges from running
``n_micro + n_stages - 1`` ticks with per-stage validity masking.

Off in the graded meshes (DP×TP is optimal at the assigned scales — see
EXPERIMENTS.md §Perf napkin math) but available as a config axis and
tested with 8 host devices in tests/test_distributed.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(layer_fn: Callable, stacked_params, x_micro, mesh: Mesh,
                   *, stage_axis: str = "stage"):
    """Run ``layer_fn`` over stacked layers, pipelined across stages.

    stacked_params: pytree with leading dim L (divisible by n_stages);
    x_micro: (n_micro, micro_batch, ...) activations.
    Returns (n_micro, micro_batch, ...) outputs.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    per_stage = L // n_stages

    # reshape params to (S, L/S, ...) and shard dim 0 over stages
    params_staged = jax.tree_util.tree_map(
        lambda p: p.reshape((n_stages, per_stage) + p.shape[1:]),
        stacked_params)
    pspec = jax.tree_util.tree_map(
        lambda p: P(stage_axis, *([None] * (p.ndim - 1))), params_staged)

    def stage_body(params_local, x_all):
        """Runs on one stage; params_local: (1, L/S, ...), x_all: full
        (n_micro, mb, ...) replicated activations buffer."""
        stage_id = jax.lax.axis_index(stage_axis)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)

        def apply_stage(x):
            def body(h, lp):
                return layer_fn(lp, h), None
            h, _ = jax.lax.scan(body, x, params_local)
            return h

        n_ticks = n_micro + n_stages - 1
        # buf holds the activation currently at *this* stage
        buf = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (when valid)
            feed = jnp.where(t < n_micro, t, 0)
            buf = jnp.where(stage_id == 0, x_all[feed], buf)
            micro_here = t - stage_id          # which microbatch sits here
            valid = (micro_here >= 0) & (micro_here < n_micro)
            y = apply_stage(buf)
            y = jnp.where(valid, y, buf)
            # last stage emits; others forward
            out_idx = jnp.clip(micro_here, 0, n_micro - 1)
            emit = valid & (stage_id == n_stages - 1)
            outputs = jnp.where(
                emit, outputs.at[out_idx].set(y), outputs)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return (buf, outputs), None

        (buf, outputs), _ = jax.lax.scan(tick, (buf, outputs),
                                         jnp.arange(n_ticks))
        # every stage holds a copy of `outputs`; only the last stage's is
        # complete — reduce by max-abs-select via psum of masked values
        mask = (stage_id == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, stage_axis)
        return outputs

    fn = jax.shard_map(stage_body, mesh=mesh,
                   in_specs=(pspec, P()), out_specs=P(),
                   check_vma=False)
    return fn(params_staged, x_micro)
