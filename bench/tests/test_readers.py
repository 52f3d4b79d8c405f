"""Per-layer metric readers on hand-made records whose answers are known."""

from __future__ import annotations

import types

import pytest

from bench import cost, loop, trace
from bench.harness import RunRecord
from bench.spec import metric_reader

from .conftest import tiny_spec

MS = 1_000_000     # ns
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PROMPT = 5         # the admitted prompt: 4 tokens of prefill


def _programs(kind):
    """Device events of one traced admit step and one decode step.  The
    prefill of the prompt runs as one ``jit__step`` program per token, or
    as a single program of another name; either way the device is busy
    from 10 to 18 ms and from 20 to 30 ms."""
    if kind == "per_token":
        ops = [[f"fusion.{i}", (10 + 2 * i) * MS, 2 * MS] for i in range(4)]
        modules = [["jit__step(1)", (10 + 2 * i) * MS, 2 * MS]
                   for i in range(4)]
    else:
        ops = [["while.7", 10 * MS, 8 * MS]]
        modules = [["jit_prefill_prompt(2)", 10 * MS, 8 * MS]]
    ops.append(["fusion.9", 20 * MS, 10 * MS])
    modules.append(["jit__step(1)", 20 * MS, 10 * MS])
    return {"spans": [["bench.traced", 0, 40 * MS],
                      ["bench.step.admit", 5 * MS, 16 * MS],
                      ["bench.step.decode", 21 * MS, 10 * MS]],
            "devices": [{"name": "/device:TPU:0", "lines": {
                "XLA Ops": ops, "XLA Modules": modules}}]}


@pytest.mark.parametrize("kind", ["per_token", "per_prompt"])
def test_step_roofline_reads_the_work_whatever_the_programs(kind):
    m = tiny_spec()
    steps = [loop.Step("admit", 0.0, 0.02, [PROMPT], [PROMPT, 9]),
             loop.Step("decode", 0.02, 0.03, [], [6, 10])]
    w = loop.Window(0.0, 0.04, steps, [], [], 2, [], [], traced_from=0)
    reduction = trace.reduce(_programs(kind))
    assert reduction.busy_s == pytest.approx(0.018)
    got = metric_reader("step_roofline")(
        RunRecord(m, PEAKS, w, {}, reduction))
    need = cost.prefill_seconds(m, PROMPT, PEAKS) \
        + cost.roofline_seconds(m, [PROMPT, 9], PEAKS) \
        + cost.roofline_seconds(m, [6, 10], PEAKS)
    assert got == pytest.approx(100 * need / 0.018)


def test_step_roofline_needs_a_trace():
    w = loop.Window(0.0, 1.0, [loop.Step("decode", 0, 1, [], [5])],
                    [], [], 1, [], [], traced_from=0)
    assert metric_reader("step_roofline")(
        RunRecord(tiny_spec(), PEAKS, w, {}, None)) is None


def test_cache_host_mb_per_step():
    read = metric_reader("cache_host_mb_per_step")
    run = types.SimpleNamespace(counters={"decode_steps": 4,
                                          "cache_host_bytes": 3_019_900_160})
    assert read(run) == pytest.approx(754.97504)
    # a program that does not count the bytes leaves the metric out
    assert read(types.SimpleNamespace(counters={"decode_steps": 4})) is None
    assert read(types.SimpleNamespace(counters={"decode_steps": 0,
                                                "cache_host_bytes": 0})) \
        is None
