"""Distributed substrate: checkpoints (+elastic reshard), FT control plane,
gradient compression, sharding rules, pipeline parallelism (8 host devices
in a subprocess so the main test process keeps 1 device)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.checkpoint import Checkpointer
from repro.distributed.compression import (compressed_bytes, int8_compress,
                                           int8_decompress, topk_compress,
                                           topk_decompress)
from repro.distributed.fault_tolerance import (HeartbeatMonitor,
                                               StragglerDetector,
                                               plan_rescale)
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig

# full model/kernel/device sweeps: minutes of work, deselected in the
# CI fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow


def small_params(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (8, 16)),
            "b": {"w": jax.random.normal(k, (16, 4)),
                  "s": jnp.ones((4,))}}


class TestCheckpoint:
    def test_roundtrip_with_opt_state(self, tmp_path):
        params = small_params()
        opt = adamw.init(AdamWConfig(), params)
        ck = Checkpointer()
        ck.save(str(tmp_path), params, opt, step=7)
        p2, o2, step = ck.restore(str(tmp_path), 7, params, opt)
        assert step == 7
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), params, p2)

    def test_atomic_latest_and_gc(self, tmp_path):
        params = small_params()
        ck = Checkpointer()
        for s in (1, 2, 3, 4, 5):
            ck.save(str(tmp_path), params, None, step=s)
        assert ck.latest_step(str(tmp_path)) == 5
        dirs = sorted(os.listdir(tmp_path))
        assert len(dirs) == 3            # keep=3 garbage collection
        assert not any(d.endswith(".tmp") for d in dirs)

    def test_elastic_reshard_2_hosts_to_1(self, tmp_path):
        """Save from 2 hosts, restore on 1 (a host died) — DESIGN.md FT."""
        params = small_params()
        ck0 = Checkpointer(host_id=0, n_hosts=2)
        ck1 = Checkpointer(host_id=1, n_hosts=2)
        ck0.save(str(tmp_path), params, None, step=3)
        ck1.save(str(tmp_path), params, None, step=3)
        survivor = Checkpointer(host_id=0, n_hosts=1)
        p2, _, step = survivor.restore(str(tmp_path), 3, params,
                                       n_saved_hosts=2)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), params, p2)


class TestFaultTolerance:
    def test_heartbeat_detects_silent_death(self):
        hb = HeartbeatMonitor(4, timeout=10.0)
        for t in (0.0, 5.0):
            for n in range(4):
                hb.beat(n, t)
        hb.beat(0, 12.0)
        hb.beat(1, 12.0)
        hb.beat(2, 12.0)          # node 3 silent since t=5
        dead = hb.check(16.0)
        assert dead == [3]
        assert hb.alive_nodes == [0, 1, 2]

    def test_straggler_detection(self):
        sd = StragglerDetector(4, threshold=1.5)
        for _ in range(5):
            for n in range(4):
                sd.record(n, 1.0 if n != 2 else 2.5)
        assert sd.stragglers() == [2]

    def test_rescale_plan_drops_dead_data_slice(self):
        plan = plan_rescale({"data": 16, "model": 16}, dead_nodes=[37])
        assert plan.viable
        assert plan.new_shape == (15, 16)     # one data slice lost
        assert plan.reshard_data_factor == pytest.approx(16 / 15)

    def test_rescale_multi_pod_keeps_pods_when_balanced(self):
        # one dead node per pod at the same slice offset
        plan = plan_rescale({"pod": 2, "data": 16, "model": 16},
                            dead_nodes=[0, 256])
        assert plan.new_shape == (2, 15, 16)


class TestCompression:
    def test_int8_error_feedback_converges(self):
        """Compressed-sum with error feedback tracks the true sum."""
        key = jax.random.PRNGKey(0)
        g = {"w": jax.random.normal(key, (64, 64))}
        residual = None
        acc_true = jnp.zeros((64, 64))
        acc_comp = jnp.zeros((64, 64))
        for i in range(20):
            gi = {"w": g["w"] * (1 + 0.01 * i)}
            comp, residual = int8_compress(gi, residual)
            acc_comp += int8_decompress(comp)["w"]
            acc_true += gi["w"]
        err = jnp.abs(acc_comp - acc_true).max() / jnp.abs(acc_true).max()
        assert float(err) < 0.02

    def test_int8_wire_bytes_4x_smaller(self):
        g = {"w": jnp.ones((128, 128), jnp.float32)}
        comp, _ = int8_compress(g)
        assert compressed_bytes(comp.values) * 4 <= compressed_bytes(g)

    def test_topk_keeps_largest(self):
        g = {"w": jnp.asarray(np.linspace(-1, 1, 100).reshape(10, 10),
                              jnp.float32)}
        comp, res = topk_compress(g, k_fraction=0.1)
        dec = topk_decompress(comp, g)
        nz = np.nonzero(np.asarray(dec["w"]).ravel())[0]
        assert len(nz) == 10
        mags = np.abs(np.linspace(-1, 1, 100))
        assert set(nz) == set(np.argsort(-mags)[:10])


PIPELINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
L, D = 8, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, D, D)) * 0.3

def layer_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (6, 4, D))   # 6 microbatches
out = pipeline_apply(layer_fn, ws, x, mesh)

# reference: plain sequential layers
ref = x
for i in range(L):
    ref = jnp.tanh(ref @ ws[i])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("PIPELINE_OK")
"""


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
        r = subprocess.run([sys.executable, "-c", PIPELINE_SCRIPT],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


DRYRUN_SMOKE = r"""
import sys
from repro.launch.dryrun import run_cell
rec = run_cell("xlstm_125m", "train_4k", multi_pod=True, save=False)
assert rec["status"] == "ok", rec.get("error")
assert rec["n_devices"] == 512
print("DRYRUN_OK", rec["per_device_bytes"])
"""


class TestDryRunMachinery:
    def test_multipod_cell_compiles_on_512_devices(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
        r = subprocess.run([sys.executable, "-c", DRYRUN_SMOKE],
                           capture_output=True, text=True, env=env,
                           timeout=560)
        assert "DRYRUN_OK" in r.stdout, r.stdout[-500:] + r.stderr[-2000:]
