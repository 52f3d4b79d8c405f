"""The trace reduction, on a hand-made trace whose answers are known."""

from __future__ import annotations

import pytest

from bench import trace

MS = 1_000_000     # ns


def _trace():
    return {
        "spans": [["bench.traced", 0, 100 * MS],
                  ["bench.step.admit", 10 * MS, 40 * MS],
                  ["bench.step.decode", 60 * MS, 30 * MS],
                  ["bench.other", 0, 5 * MS]],
        "devices": [{"name": "/device:TPU:0", "lines": {
            "XLA Ops": [["fusion.1", 5 * MS, 15 * MS],
                        ["fusion.2", 15 * MS, 15 * MS],
                        ["copy.3", 40 * MS, 5 * MS],
                        ["fusion.1", 70 * MS, 10 * MS],
                        ["fusion.1", 120 * MS, 10 * MS]]}}],  # after the window
    }


def test_reduce():
    r = trace.reduce(_trace())
    assert r.window_s == pytest.approx(0.1)
    # union of [5, 30], [40, 45], [70, 80]
    assert r.busy_s == pytest.approx(0.040)
    assert r.device_ops[0] == ("fusion.1", pytest.approx(0.025))
    assert [n for n, _ in r.device_ops] == ["fusion.1", "fusion.2", "copy.3"]
    assert r.idle_gaps == [("harness", pytest.approx(0.025)),
                           ("decode step", pytest.approx(0.020)),
                           ("admit step", pytest.approx(0.010)),
                           ("harness", pytest.approx(0.005))]


def test_no_device_work_is_an_error():
    t = _trace()
    t["devices"][0]["lines"]["XLA Ops"] = []
    with pytest.raises(RuntimeError):
        trace.reduce(t)


def test_op_names_lose_their_hlo_text():
    assert trace._short("%fusion.70 = (bf16[256]{0}) fusion(%p), "
                        "kind=kLoop") == "%fusion.70"
    assert trace._short("copy.3") == "copy.3"
