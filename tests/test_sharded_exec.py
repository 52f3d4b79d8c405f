"""Numerical equivalence of the distributed paths on 8 host devices.

The §Perf iterations changed *how* things compute (shard_map paged decode,
logical activation rules, 2-D EP); these tests run the same model under a
(2 data × 4 model) mesh and on one device and assert identical outputs.
Runs in a subprocess so the main pytest process keeps one device.
"""

import os
import subprocess
import sys

import pytest

# full model/kernel/device sweeps: minutes of work, deselected in the
# CI fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_configs
from repro.distributed.logical import logical_rules
from repro.distributed.sharding import cache_shardings, param_shardings
from repro.models.config import reduced
from repro.models.registry import model_for

# 8 kv heads / 8 q heads so heads divide model=4; pages divide data=2
cfg = reduced(all_configs()["codeqwen15_7b"], n_layers=2, n_heads=8,
              n_kv_heads=8, head_dim=16, d_model=64, kv_page_tokens=8)
model = model_for(cfg)
params = model.init_params(cfg, jax.random.PRNGKey(0))
B, CTX = 4, 32
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0,
                            cfg.vocab_size)

# ---- single-device reference --------------------------------------------
cache0 = model.init_decode_cache(cfg, B, CTX)
cache0["lengths"] = jnp.full((B,), 9, jnp.int32)   # mid-context decode
ref_logits, ref_cache = model.decode_step(params, cfg, cache0, tokens)

# ---- distributed: mesh (2 data x 4 model), shard_map paged decode --------
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = {"batch": "data", "heads": "model", "kv_heads": "model",
         "ff": "model"}
p_sh = param_shardings(params, mesh)
c_sh = cache_shardings(cache0, mesh, B)
with mesh, logical_rules(mesh, rules):
    fn = jax.jit(lambda p, c, t: model.decode_step(p, cfg, c, t),
                 in_shardings=(p_sh, c_sh, NamedSharding(mesh, P("data"))),
                 donate_argnums=(1,))
    dist_logits, dist_cache = fn(params, cache0, tokens)

np.testing.assert_allclose(np.asarray(dist_logits), np.asarray(ref_logits),
                           atol=2e-4, rtol=2e-3)
np.testing.assert_allclose(np.asarray(dist_cache["k_pool"]),
                           np.asarray(ref_cache["k_pool"]), atol=1e-5)
print("DECODE_DIST_OK")

# ---- distributed train step: logical rules + remat ----------------------
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.training.trainer import TrainConfig, make_train_step

tcfg = TrainConfig(microbatches=2, remat=True,
                   optimizer=AdamWConfig(lr=1e-3))
step = make_train_step(cfg, tcfg)
opt = adamw.init(tcfg.optimizer, params)
tk = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size)
lb = jnp.roll(tk, -1, axis=1)

p_ref, o_ref, m_ref = jax.jit(step)(params, opt, tk, lb)

opt_sh = adamw.AdamWState(
    step=NamedSharding(mesh, P()),
    mu=jax.tree_util.tree_map(lambda s, sh: sh, opt.mu, p_sh),
    nu=jax.tree_util.tree_map(lambda s, sh: sh, opt.nu, p_sh))
with mesh, logical_rules(mesh, rules):
    fn = jax.jit(step, in_shardings=(p_sh, opt_sh,
                                     NamedSharding(mesh, P("data", None)),
                                     NamedSharding(mesh, P("data", None))),
                 out_shardings=(p_sh, opt_sh, None))
    p_dist, o_dist, m_dist = fn(params, opt, tk, lb)

np.testing.assert_allclose(float(m_dist["loss"]), float(m_ref["loss"]),
                           atol=1e-4, rtol=1e-4)
for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                jax.tree_util.tree_leaves(p_dist)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-3)
print("TRAIN_DIST_OK")
"""


def test_distributed_paths_match_single_device():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=560)
    assert "DECODE_DIST_OK" in r.stdout, r.stdout[-800:] + r.stderr[-3000:]
    assert "TRAIN_DIST_OK" in r.stdout, r.stdout[-800:] + r.stderr[-3000:]
