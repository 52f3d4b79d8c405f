"""The serving engine's host spans and counters, as the benchmark reads them:
the ``serve.*`` spans in a real profiler trace, the count of bytes the
batch cache crosses between device and host, and the pager's fault-ins in
a pool short of the batch's pages."""

import dataclasses
import types

import jax
import numpy as np
import pytest

from bench import spans, trace
from bench.spec import metric_reader
from repro.configs import all_configs, get_config
from repro.launch.serve import make_engine
from repro.models.config import reduced
from repro.models.registry import model_for
from repro.serving.engine import ServingEngine

# one reduced config per decode-cache layout: paged pool, SWA ring, MLA latent
DECODE_LAYOUTS = ["qwen3_14b", "h2o_danube_1_8b", "deepseek_v3_671b"]
SPANS = {"serve.step", "serve.admit", "serve.prefill", "serve.pager",
         "serve.gather", "serve.dispatch", "serve.scatter", "serve.retire"}


def _engine(arch="starcoder2_3b"):
    cfg = reduced(all_configs()[arch])
    params = model_for(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(cfg, params, max_batch=2, max_len=64)


def step_bytes(template, seq, rows: int) -> int:
    """Bytes one decode step over ``rows`` sequences converts between
    device and host, counted from the leaves of the batch cache
    ``template`` and of one sequence's cache ``seq``: the gather copies
    each batch leaf to the host, each row's leaf to the host and the merged
    leaf back; the scatter copies each batch leaf to the host, then for
    each row every leaf but ``lengths`` and the page table to the host and
    back."""
    full = sum(map(_nbytes, jax.tree_util.tree_leaves(template)))
    row = sum(map(_nbytes, jax.tree_util.tree_leaves(seq)))
    moved = sum(_nbytes(x) for k, x in seq.items()
                if k != "lengths" and "table" not in k)
    return 3 * full + rows * (row + 2 * moved)


def _nbytes(x) -> int:
    return x.size * x.dtype.itemsize


@pytest.mark.parametrize("arch", DECODE_LAYOUTS)
def test_cache_host_bytes_is_the_leaf_size_count(arch):
    eng = _engine(arch)
    seq = model_for(eng.cfg).init_decode_cache(eng.cfg, 1, eng.max_len)
    want = step_bytes(eng.cache, seq, eng.max_batch)
    rng = np.random.default_rng(0)
    for n in (5, 30):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=n), 4)
    for step in (1, 2, 3):
        eng.step_decode()
        assert eng.stats.cache_host_bytes == step * want
    assert eng.stats.prefill_tokens == 4 + 29


def test_published_widths_move_six_pools_a_step():
    """At StarCoder2-3B's published widths, batch 4 of 1,024 tokens, a
    decode step converts six times the K and V pools (125.8 MB) and a few
    bytes of lengths and page tables."""
    cfg = get_config("starcoder2_3b")
    model = model_for(cfg)
    template = jax.eval_shape(lambda: model.init_decode_cache(cfg, 4, 1024))
    seq = jax.eval_shape(lambda: model.init_decode_cache(cfg, 1, 1024))
    pools = _nbytes(template["k_pool"]) + _nbytes(template["v_pool"])
    assert pools == 125_829_120
    got = step_bytes(template, seq, 4)
    assert 0 < got - 6 * pools < 1024


def test_profiler_trace_holds_every_engine_span(tmp_path):
    eng = _engine()
    rng = np.random.default_rng(1)
    for n in (6, 9):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=n), 2)
    eng.step_decode()                # compile outside the trace
    eng.submit(rng.integers(0, eng.cfg.vocab_size, size=7), 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):           # admits, prefills, decodes, retires
            eng.step_decode()
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    kept = {name for name, _, _ in spans.load(path)["spans"]
            if name.startswith(spans.PREFIX)}
    assert kept == SPANS
    # the arguments an operator follows a request by
    from jax.profiler import ProfileData
    args = [dict(e.stats) for p in ProfileData.from_file(path).planes
            for line in p.lines for e in line.events
            if e.name in ("serve.prefill", "serve.pager")]
    assert {"req_id": 3, "prompt_tokens": 7} in args
    assert {"op": "ensure_resident", "req_id": 3, "pages": 0} in args
    assert {a.get("op") for a in args} >= {
        "add_sequence", "append_tokens", "ensure_resident", "free_sequence"}


def test_short_pool_serves_the_same_tokens():
    """A pool of 0.375 of an exact fit (6 of 16 frames) against an exact
    one, on the same requests: the rows evict each other's pages on every
    step, the pager faults them back in, and the tokens are the same."""
    cfg = dataclasses.replace(reduced(get_config("starcoder2_3b")),
                              kv_page_tokens=16)
    params = model_for(cfg).init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, cfg.vocab_size, size=n), m)
            for n, m in ((40, 12), (28, 20), (45, 8), (33, 16), (20, 10))]
    served, counters = [], []
    for frames in (None, 6):
        eng = make_engine(cfg, params, max_batch=4, max_len=64,
                          pool_frames=frames)
        out = [eng.submit(p, m) for p, m in reqs]
        eng.run_until_done()
        served.append([r.generated for r in out])
        counters.append(dataclasses.asdict(eng.stats))
    assert served[0] == served[1]
    assert counters[0]["fault_page_ins"] == 0
    read = metric_reader("fault_ins_per_ktok")
    ktok = [read(types.SimpleNamespace(counters=c)) for c in counters]
    assert ktok[0] == 0 < ktok[1]
    assert counters[1]["spill_events"] > 0


def test_engine_fixes_the_host_heap_for_its_round_trip(monkeypatch):
    """The engine fixes glibc's mmap and trim thresholds once, the trim at
    the power of two above what a step converts, and only ever raises it."""
    from repro.serving import engine as engine_mod
    calls = []
    monkeypatch.setattr(engine_mod.ctypes, "CDLL", lambda _name: types.
                        SimpleNamespace(mallopt=lambda *a: calls.append(a)))
    monkeypatch.setattr(engine_mod, "_trim_threshold", 0)
    mmap, trim = engine_mod._M_MMAP_THRESHOLD, engine_mod._M_TRIM_THRESHOLD
    engine_mod._keep_host_heap(754_975_040)      # published widths, a step
    assert calls == [(mmap, 32 << 20), (trim, 1 << 30)]
    engine_mod._keep_host_heap(1 << 20)
    assert len(calls) == 2
    calls.clear()
    _engine()                 # a smaller round trip lowers nothing
    assert calls == [] and engine_mod._trim_threshold == 1 << 30
    monkeypatch.setattr(engine_mod, "_trim_threshold", 0)
    eng = _engine()
    assert 6 * sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.cache)) \
        < 64 << 20
    assert calls == [(mmap, 32 << 20), (trim, 64 << 20)]
