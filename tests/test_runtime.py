"""Runtime layers: paged store, KV manager, offloaded optimizer, serving
engine, trainer+checkpoint restart, data pipeline determinism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs
from repro.core.resolver import Strategy
from repro.data.pipeline import PackedFileDataset, ShardInfo, SyntheticLM, \
    write_packed_file
from repro.distributed.checkpoint import Checkpointer
from repro.memory.kv_cache import PagedKVManager
from repro.memory.offload import PagedAdamW
from repro.memory.paged_store import PagedTensorStore
from repro.models.config import reduced
from repro.models.registry import model_for
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.serving.engine import ServingEngine
from repro.training.trainer import TrainConfig, Trainer

# full model/kernel/device sweeps: minutes of work, deselected in the
# CI fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow


class TestPagedTensorStore:
    def test_fault_and_touch_ahead(self):
        st = PagedTensorStore(page_elems=8, n_device_frames=4, n_host_pages=16,
                              strategy=Strategy.TOUCH_AHEAD, lookahead=4)
        for v in range(16):
            st.write_host(v, np.full(8, v, np.float32))
        out = st.access([0])
        assert st.stats.faults == 1
        assert st.resident_pages() == 4          # touched ahead
        np.testing.assert_array_equal(np.asarray(out[0]), np.zeros(8))
        st.access([1, 2, 3])
        assert st.stats.faults == 1              # prefetched, no new faults
        assert st.stats.prefetch_hits == 3

    def test_touch_a_page_faults_per_page(self):
        st = PagedTensorStore(8, 8, 16, strategy=Strategy.TOUCH_A_PAGE)
        for v in range(16):
            st.write_host(v, np.full(8, v, np.float32))
        st.access([0, 1, 2, 3])
        assert st.stats.faults == 4

    def test_eviction_writeback_roundtrip(self):
        st = PagedTensorStore(4, 2, 8, strategy=Strategy.TOUCH_A_PAGE)
        st.write_host(0, np.zeros(4, np.float32))
        st.access([0])
        # mutate the device copy, then force eviction by touching others
        f = int(st.page_table[0])
        st.frames = st.frames.at[f].set(jnp.full(4, 7.0))
        st.access([1])
        st.access([2])                            # evicts page 0 (LRU)
        assert not st.is_resident(0)
        out = st.access([0])                      # faults back in
        np.testing.assert_array_equal(np.asarray(out[0]), np.full(4, 7.0))

    def test_pinned_never_evicted(self):
        st = PagedTensorStore(4, 2, 8)
        st.pin([0])
        st.access([1])
        with pytest.raises(MemoryError):
            st.pin([1]) or st.access([2]) if False else (
                st.pin([1]), st.access([2]))


class TestPagedKVManager:
    def test_spill_and_touch_ahead_fault(self):
        kv = PagedKVManager(n_frames=8, page_tokens=4, max_pages_per_seq=8,
                            strategy=Strategy.TOUCH_AHEAD)
        kv.add_sequence(1)
        kv.add_sequence(2)
        kv.append_tokens(1, 32)                   # all 8 frames to seq 1
        assert kv.frames_used == 8
        kv.append_tokens(2, 8, spill_candidates=[1])   # forces spills
        assert kv.stats.spills == 2
        assert len(kv.spilled[1]) == 2
        n = kv.ensure_resident(1, spill_candidates=[2])
        assert n == 2
        assert not kv.spilled[1]
        assert kv.stats.fault_events == 1         # one block fault (T-A)

    def test_touch_a_page_pays_per_page(self):
        kv = PagedKVManager(8, 4, 8, strategy=Strategy.TOUCH_A_PAGE)
        kv.add_sequence(1)
        kv.add_sequence(2)
        kv.append_tokens(1, 32)
        kv.append_tokens(2, 12, spill_candidates=[1])
        n = kv.ensure_resident(1, spill_candidates=[2])
        assert n == 3
        assert kv.stats.fault_events == 3         # one per page

    def test_device_table_masks_spilled(self):
        kv = PagedKVManager(4, 4, 4)
        kv.add_sequence(1)
        kv.append_tokens(1, 16)
        tbl = kv.device_table([1])
        assert (tbl >= 0).all()
        kv.add_sequence(2)
        kv.append_tokens(2, 4, spill_candidates=[1])
        tbl = kv.device_table([1])
        assert (tbl == -1).sum() == 1             # spilled slot unmapped


class TestOffloadedOptimizer:
    def test_matches_reference_adamw(self):
        cfg = AdamWConfig(lr=1e-2, grad_clip=0.0, weight_decay=0.01)
        key = jax.random.PRNGKey(0)
        params = {"a": jax.random.normal(key, (33, 7)),
                  "b": jnp.ones((11,))}
        grads = {"a": jax.random.normal(jax.random.PRNGKey(1), (33, 7)),
                 "b": jnp.full((11,), 0.5)}
        ref_state = adamw.init(cfg, params)
        ref_p = params
        po = PagedAdamW(cfg, params, block_elems=64)
        pg_p = params
        for _ in range(3):
            ref_p, ref_state, _ = adamw.update(cfg, ref_state, ref_p, grads)
            pg_p = po.update(pg_p, grads)
        for k in params:
            np.testing.assert_allclose(np.asarray(pg_p[k]),
                                       np.asarray(ref_p[k]), atol=1e-5)
        assert po.stats.prefetch_overlapped > 0

    def test_device_residency_bounded(self):
        cfg = AdamWConfig()
        params = {"w": jnp.zeros((1 << 16,))}
        po = PagedAdamW(cfg, params, block_elems=1 << 10)
        assert po.device_bytes_resident() == 2 * (1 << 10) * 8
        # full f32 moments would be 2 * 4 bytes * 65536 = 512 KiB; the
        # paged working set is 16 KiB
        assert po.device_bytes_resident() < 2 * 4 * (1 << 16) // 8


# one reduced config per decode-cache layout: paged pool, SWA ring, MLA latent
DECODE_LAYOUTS = ["qwen3_14b", "h2o_danube_1_8b", "deepseek_v3_671b"]


def _serve_greedy(cfg, params, prompts, max_batch, max_new=6):
    eng = ServingEngine(cfg, params, max_batch=max_batch, max_len=64)
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return [r.generated for r in reqs]


def _layout_model(arch):
    cfg = reduced(all_configs()[arch])
    params = model_for(cfg).init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    # different lengths: rows of one batch sit at different offsets, and
    # the longer prompt crosses page and SWA-window boundaries
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 30)]
    return cfg, params, prompts


class TestServingEngine:
    @pytest.mark.parametrize("arch", DECODE_LAYOUTS)
    def test_batched_rows_decode_independently(self, arch):
        cfg, params, prompts = _layout_model(arch)
        assert (_serve_greedy(cfg, params, prompts, max_batch=2)
                == _serve_greedy(cfg, params, prompts, max_batch=1))

    @pytest.mark.parametrize("arch", DECODE_LAYOUTS)
    def test_greedy_matches_teacher_forced_forward(self, arch):
        cfg, params, prompts = _layout_model(arch)
        served = _serve_greedy(cfg, params, prompts, max_batch=2)
        model = model_for(cfg)
        for prompt, gen in zip(prompts, served):
            seq = np.concatenate([prompt, gen[:-1]]).astype(np.int32)
            logits, _ = model.forward(params, cfg, jnp.asarray(seq)[None])
            ref = np.argmax(np.asarray(logits[0, len(prompt) - 1:]), axis=-1)
            assert ref.tolist() == gen

    def _engine(self, **kw):
        cfg = reduced(all_configs()["h2o_danube_1_8b"], n_layers=2)
        model = model_for(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, ServingEngine(cfg, params, max_batch=2, max_len=64, **kw)

    def test_continuous_batching_completes(self):
        _, eng = self._engine()
        rng = np.random.default_rng(0)
        reqs = [eng.submit(rng.integers(0, 100, size=4), max_new_tokens=5)
                for _ in range(4)]
        eng.run_until_done()
        assert all(r.done for r in reqs)
        assert all(len(r.generated) == 5 for r in reqs)
        assert eng.stats.decode_steps > 0

    def test_greedy_deterministic(self):
        _, e1 = self._engine()
        _, e2 = self._engine()
        prompt = np.array([5, 6, 7], np.int32)
        r1 = e1.submit(prompt, 6)
        r2 = e2.submit(prompt, 6)
        e1.run_until_done()
        e2.run_until_done()
        assert r1.generated == r2.generated


class TestTrainerCheckpointRestart:
    def test_restart_resumes_identically(self, tmp_path):
        cfg = reduced(all_configs()["starcoder2_3b"], n_layers=2)
        model = model_for(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        ds = SyntheticLM(cfg.vocab_size, 16, 4)
        tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3))
        ck = Checkpointer()

        tr = Trainer(cfg, tcfg, params, ds, checkpoint_dir=str(tmp_path),
                     checkpoint_every=5, checkpointer=ck)
        tr.run(10, log_every=0)
        loss_10 = tr.history[-1]["loss"]

        # "crash" and restore from step 10, run 5 more
        tr2 = Trainer(cfg, tcfg, model.init_params(cfg, jax.random.PRNGKey(9)),
                      ds, checkpoint_dir=str(tmp_path), checkpointer=ck)
        restored = ck.restore_latest(str(tmp_path), tr2.params, tr2.opt_state)
        assert restored is not None
        tr2.params, tr2.opt_state, tr2.step = restored
        assert tr2.step == 10
        tr2.run(5, log_every=0)

        # uninterrupted reference
        tr3 = Trainer(cfg, tcfg, model.init_params(cfg, jax.random.PRNGKey(0)),
                      ds)
        tr3.run(15, log_every=0)
        assert tr2.history[-1]["loss"] == pytest.approx(
            tr3.history[-1]["loss"], rel=1e-4)


class TestDataPipeline:
    def test_synthetic_deterministic_and_learnable(self):
        ds1 = SyntheticLM(100, 32, 4, seed=7)
        ds2 = SyntheticLM(100, 32, 4, seed=7)
        t1, l1 = ds1.batch_at(3)
        t2, l2 = ds2.batch_at(3)
        np.testing.assert_array_equal(t1, t2)
        assert (l1[:, -1] == -1).all()

    def test_shards_disjoint(self):
        a = SyntheticLM(100, 16, 4, ShardInfo(0, 2)).batch_at(0)[0]
        b = SyntheticLM(100, 16, 4, ShardInfo(1, 2)).batch_at(0)[0]
        assert not np.array_equal(a, b)

    def test_packed_file_resume_arithmetic(self, tmp_path):
        path = str(tmp_path / "tokens.bin")
        write_packed_file(path, np.arange(10_000) % 500)
        ds = PackedFileDataset(path, 500, 32, 2, ShardInfo(1, 4))
        t1, _ = ds.batch_at(5)
        ds2 = PackedFileDataset(path, 500, 32, 2, ShardInfo(1, 4))
        t2, _ = ds2.batch_at(5)          # resume is pure arithmetic
        np.testing.assert_array_equal(t1, t2)
        labels = ds.batch_at(0)
        np.testing.assert_array_equal(labels[0][0, 1:], labels[1][0, :-1])
