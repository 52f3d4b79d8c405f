"""CPU rehearsal of the harness at small widths: the cell's pieces are found
by name, and the closed loop's bookkeeping counts every token."""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from bench import loop
from bench.harness import RunRecord, run_cell
from bench.model_spec import ModelSpec
from bench.spec import load_cell, metric_reader
from bench.traffic import RequestStream, levels

from .conftest import SMALL_MIX, make_root, small_config

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 12345          # above what 32 signed bits hold


def test_new_mix_and_cell_are_found_by_name(tmp_path):
    """A new mix file and a new workloads entry run with no code edit, and a
    new per-layer metric is one more file under bench/metrics."""
    mix = dict(SMALL_MIX, sizes=4,
               prompt_tokens={"dist": "uniform", "min": 8, "max": 24})
    root = make_root(tmp_path, {"tiny": small_config("starcoder2_3b")},
                     {"fresh-mix": mix}, [("fresh-cell", "tiny", "fresh-mix")],
                     limit=0.25)
    cell = load_cell("fresh-cell", root)
    assert cell.traffic["sizes"] == 4
    out = run_cell(cell, SEED, 1.0, False, peaks=PEAKS, log=lambda s: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert list(out)[-1] == "checks"

    (root / "bench" / "metrics" / "rows_per_step.py").write_text(
        "def read(run):\n"
        "    return sum(len(s.contexts) for s in run.window.steps)"
        " / len(run.window.steps)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "rows_per_step", "unit": "rows", "better": "higher",
        "source": "host_clock", "layer": "decode step",
        "moves": "out_tok_per_s", "workloads": ["fresh-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell("fresh-cell", root)
    assert "rows_per_step" in {m.name for m in cell.per_layer}
    w = loop.Window(0.0, 1.0, [loop.Step("decode", 0, 1, [], [5, 6])],
                    [], [], 2, [], [])
    record = RunRecord(ModelSpec.from_config(cell.config), PEAKS, w, {}, None)
    assert metric_reader("rows_per_step", root)(record) == 2


def test_same_sizes_for_every_seed():
    a = RequestStream(SMALL_MIX, 128, 1)
    b = RequestStream(SMALL_MIX, 128, SEED)
    n = SMALL_MIX["sizes"]
    ra = [a.next() for _ in range(3 * n)]
    rb = [b.next() for _ in range(3 * n)]
    # the same sizes in the same order, each block of n holding each once
    sa = [(len(p), o) for p, o in ra]
    assert sa == [(len(p), o) for p, o in rb]
    for i in range(0, 3 * n, n):
        assert sorted(s for s, _ in sa[i:i + n]) == sorted(
            levels(SMALL_MIX["prompt_tokens"], n))
    # the token ids are the seed's
    assert any((p != q).any() for (p, _), (q, _) in zip(ra, rb))


@dataclasses.dataclass
class _Req:
    req_id: int
    prompt: np.ndarray
    max_new_tokens: int
    generated: list
    done: bool = False


class _FakeEngine:
    """The engine's interface on a clock the test moves: prefill costs
    ``PREFILL`` seconds a prompt token, a decode step ``DECODE``."""
    PREFILL, DECODE = 0.01, 0.1

    def __init__(self, clock, max_batch=2):
        self.clock, self.max_batch = clock, max_batch
        self.queue, self.active, self.n = [], [], 0

    def submit(self, prompt, n):
        self.n += 1
        r = _Req(self.n, prompt, n, [])
        self.queue.append(r)
        return r

    def step_decode(self):
        while self.queue and len(self.active) < self.max_batch:
            r = self.queue.pop(0)
            self.clock.t += self.PREFILL * len(r.prompt)
            self.active.append(r)
        self.clock.t += self.DECODE
        for r in self.active:
            r.generated.append(1)
            r.done = len(r.generated) >= r.max_new_tokens
        self.active = [r for r in self.active if not r.done]
        return 1


class _Clock:
    t = 0.0

    def __call__(self):
        return self.t


def test_closed_loop_counts_every_token_and_stall():
    clock = _Clock()
    eng = _FakeEngine(clock)
    closed = loop.ClosedLoop(eng, RequestStream(SMALL_MIX, 128, 7), 2,
                             clock=clock)
    t_open = closed.fill()
    w = closed.run(t_open, 20.0)
    # the batch never runs short: a finished request is replaced at once
    assert all(len(s.contexts) == eng.max_batch for s in w.steps)
    assert any(s.kind == "admit" for s in w.steps)
    # every token of the window is a first token or ends a gap
    assert w.tokens == len(w.ttft_s) + len(w.gaps_s)
    # each gap is the whole step that held the token, prefill stall included
    prev, want_gaps, want_ttft = t_open, [], []
    for s in w.steps:
        n_first = len(s.admitted)
        want_ttft += [s.t1 - prev] * n_first
        want_gaps += [s.t1 - prev] * (len(s.contexts) - n_first)
        if s.kind == "admit":
            assert s.t1 - s.t0 == pytest.approx(
                _FakeEngine.PREFILL * sum(s.admitted) + _FakeEngine.DECODE)
        prev = s.t1
    assert sorted(w.gaps_s) == pytest.approx(sorted(want_gaps))
    assert sorted(w.ttft_s) == pytest.approx(sorted(want_ttft))
    e2e = loop.end_to_end(w)
    assert e2e["out_tok_per_s"] == pytest.approx(w.tokens / w.seconds)
    assert e2e["itl_worst5pct_ms"] > e2e["itl_p50_ms"] == pytest.approx(
        1e3 * _FakeEngine.DECODE)


@pytest.mark.parametrize("n, want", [
    (20, 10.0),                        # the one slowest gap
    (40, 10.0),                        # the two slowest
    (50, (10 + 10 + 5 * 0.5) / 2.5),   # the third weighted by half
])
def test_tail_mean_weights_the_edge(n, want):
    xs = [1.0] * n
    xs[:3] = [10.0, 10.0, 5.0]
    assert loop.tail_mean(xs, 0.05) == pytest.approx(want)


def test_trace_holds_the_windows_last_steps():
    """The trace starts once, between two steps, when ``trace_s`` of the
    window are left, and the traced steps run from there to the close."""
    clock = _Clock()
    eng = _FakeEngine(clock)
    closed = loop.ClosedLoop(eng, RequestStream(SMALL_MIX, 128, 7), 2,
                             clock=clock)
    t_open = closed.fill()
    starts = []
    w = closed.run(t_open, 20.0, 5.0, lambda: starts.append(clock.t))
    assert len(starts) == 1
    i = w.traced_from
    assert w.traced_steps == w.steps[i:] and 0 < i < len(w.steps)
    assert starts[0] == w.steps[i - 1].t1 <= w.steps[i].t0
    assert w.steps[i - 1].t1 - t_open >= 15.0 > w.steps[i - 2].t1 - t_open
    # a window no longer than the trace is traced whole
    w = closed.run(clock.t, 2.0, 5.0, lambda: None)
    assert w.traced_steps == w.steps


def test_closed_loop_on_the_engine():
    """The real engine at small widths: the window keeps max_batch requests
    in flight and compiles nothing."""
    from bench.harness import CompileClock, program_config
    from repro.launch.common import random_params
    from repro.launch.serve import make_engine
    conf = small_config("h2o_danube_1_8b")
    cfg = program_config(conf)
    eng = make_engine(cfg, random_params(cfg, 3), max_batch=2, max_len=64,
                      temperature=0.0)
    clock = CompileClock()
    closed = loop.ClosedLoop(eng, RequestStream(SMALL_MIX, 128, 3), 2)
    t_open = closed.fill()
    n0 = clock.compiles
    w = closed.run(t_open, 1.0)
    assert clock.compiles == n0
    assert all(len(s.contexts) == 2 for s in w.steps)
    assert w.tokens == len(w.ttft_s) + len(w.gaps_s) > 0
    assert eng.stats.tokens_generated >= w.tokens


def test_short_pool_faults_pages_in(tmp_path):
    """A mix whose pool holds half of an exact fit runs with no code edit:
    the pager spills and faults pages back in, and the answers hold."""
    mix = dict(SMALL_MIX, pool_share=0.5)
    root = make_root(tmp_path, {"tiny": small_config("starcoder2_3b")},
                     {"short": mix}, [("short-pool", "tiny", "short")],
                     limit=0.25)
    out = run_cell(load_cell("short-pool", root), SEED, 1.0, False,
                   peaks=PEAKS, log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["checks"]["fault_page_ins"]["value"] >= 1
