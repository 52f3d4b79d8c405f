"""The engine-span reduction, on hand-made traces whose answers are known."""

from __future__ import annotations

import pytest

from bench import spans, trace

from .test_trace import MS, _trace


def _served_trace():
    """``test_trace._trace`` with the engine's spans inside both steps, and
    one more device operation inside the decode step's scatter."""
    t = _trace()
    t["spans"] += [
        ["serve.step", 11 * MS, 38 * MS],
        ["serve.admit", 12 * MS, 36 * MS],
        ["serve.prefill", 13 * MS, 34 * MS],
        ["serve.step", 61 * MS, 28 * MS],
        ["serve.pager", 62 * MS, 2 * MS],
        ["serve.gather", 64 * MS, 5 * MS],
        ["serve.dispatch", 69 * MS, 2 * MS],
        ["serve.scatter", 71 * MS, 15 * MS],
        ["serve.pager", 84 * MS, 1 * MS],
        ["serve.retire", 86 * MS, 2 * MS]]
    t["devices"][0]["lines"]["XLA Ops"].append(["fusion.4", 86 * MS, 6 * MS])
    return t


def test_idle_by_innermost_span():
    t = _served_trace()
    r = spans.reduce(t)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(trace.reduce(t).busy_s) \
        == pytest.approx(0.046)
    # idle: [0, 5], [30, 40], [45, 70], [80, 86], [92, 100]
    want = {"harness": 23, "serve.prefill": 12, "serve.gather": 5,
            "serve.scatter": 5, "serve.pager": 3, "serve.step": 2,
            "admit step": 1, "decode step": 1, "serve.admit": 1,
            "serve.dispatch": 1}
    assert r.idle_by_span == pytest.approx({k: v / 1e3
                                            for k, v in want.items()})
    assert list(r.idle_by_span)[:2] == ["harness", "serve.prefill"]
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert r.span_seconds == {
        "serve.step": (pytest.approx(0.066), 2),
        "serve.admit": (pytest.approx(0.036), 1),
        "serve.prefill": (pytest.approx(0.034), 1),
        "serve.pager": (pytest.approx(0.003), 2),
        "serve.gather": (pytest.approx(0.005), 1),
        "serve.dispatch": (pytest.approx(0.002), 1),
        "serve.scatter": (pytest.approx(0.015), 1),
        "serve.retire": (pytest.approx(0.002), 1)}
    assert r.idle_gaps == [("harness", pytest.approx(0.025)),
                           ("admit step / serve.prefill", pytest.approx(0.010)),
                           ("harness", pytest.approx(0.008)),
                           ("decode step / serve.scatter",
                            pytest.approx(0.006)),
                           ("harness", pytest.approx(0.005))]


def test_without_engine_spans_the_labels_are_the_harness_ones():
    """A trace of a program without the engine's spans: the idle time goes
    to the harness's step spans, and the gaps read as ``bench.trace``
    names them."""
    t = _trace()
    r = spans.reduce(t)
    assert r.idle_gaps == trace.reduce(t).idle_gaps
    assert r.span_seconds == {}
    assert r.idle_by_span == pytest.approx(
        {"harness": 0.025, "admit step": 0.015, "decode step": 0.020})


def test_window_of_a_trace_without_the_harness():
    """An operator's trace around a serving loop has no ``bench.traced``
    span: the window runs from the first ``serve.step`` to the end of the
    last."""
    t = _served_trace()
    t["spans"] = [s for s in t["spans"] if s[0].startswith("serve.")]
    r = spans.reduce(t)
    assert r.window_s == pytest.approx(0.078)      # [11, 89]
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert "harness" in r.idle_by_span             # [49, 61], between steps
    t["spans"] = []
    with pytest.raises(RuntimeError):
        spans.reduce(t)
