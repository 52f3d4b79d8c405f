#!/usr/bin/env python3
"""Where the device's idle time goes, by the serving engine's host spans.

    python3 bench/spans.py <trace directory or .xplane.pb>

The engine marks its host phases with ``serve.*`` spans
(``src/repro/serving/engine.py``), which the profiler writes into the same
``.xplane.pb`` as the device's operations, on their clock.  ``load`` reads
what ``bench.trace.load`` reads, with the ``serve.*`` spans beside the
harness's, so that ``bench.trace.reduce`` can read its result too;
``reduce`` gives, over the traced window (the ``bench.traced`` span where
the trace holds one, else from the first ``serve.step`` span's start to
the last one's end):

* ``idle_by_span``: the device's idle seconds, each put down to the
  innermost span that holds it: a ``serve.*`` span's name, else the
  harness step's (``admit step``, ``decode step``), else ``harness``; they
  sum to the window less the busy time;
* ``span_seconds``: the summed seconds and the number of each ``serve.*``
  span inside the window;
* ``idle_gaps``: the longest idle gaps, each named as ``bench.trace``
  names it, followed by the innermost ``serve.*`` span at its midpoint
  where one holds it (``decode step / serve.scatter``).

The command prints them as one JSON object.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import os
import sys
from typing import Any

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PREFIX = "serve."
STEP = "serve.step"
OUTSIDE = "harness"


def load(path: str) -> dict[str, Any]:
    """What ``bench.trace.load`` returns for ``path``, with the ``serve.*``
    host spans among its spans.  One pass over the file: a trace of 20 s of
    token-by-token prefill is large enough that reading it more than once
    ran out of the 40 GiB of a one-chip v5e host."""
    from jax.profiler import ProfileData
    devices, found = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            devices.append({"name": plane.name, "lines": {
                line.name: [[trace._short(e.name), e.start_ns, e.duration_ns]
                            for e in line.events]
                for line in plane.lines
                if line.name == trace.OPS_LINE}})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                found += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith((trace.SPAN_PREFIX, PREFIX))]
    return {"devices": devices, "spans": found}


@dataclasses.dataclass
class SpanReduction:
    window_s: float
    busy_s: float
    idle_by_span: dict[str, float]
    span_seconds: dict[str, tuple[float, int]]   # seconds, spans
    idle_gaps: list[tuple[str, float]]


def _window(spans: list) -> tuple[float, float]:
    traced = [(a, a + d) for name, a, d in spans if name == trace.WINDOW_SPAN]
    if len(traced) > 1:
        raise RuntimeError(f"expected at most one {trace.WINDOW_SPAN} span, "
                           f"found {len(traced)}")
    if traced:
        return traced[0]
    steps = [(a, a + d) for name, a, d in spans if name == STEP]
    if not steps:
        raise RuntimeError(f"the trace holds neither a {trace.WINDOW_SPAN} "
                           f"nor a {STEP} span")
    return min(a for a, _ in steps), max(b for _, b in steps)


def reduce(raw: dict[str, Any]) -> SpanReduction:
    w0, w1 = _window(raw["spans"])
    steps = sorted((a, a + d, trace.GAP_NAMES[name])
                   for name, a, d in raw["spans"] if name in trace.GAP_NAMES)
    engine = [(max(a, w0), min(a + d, w1), name)
              for name, a, d in raw["spans"]
              if name.startswith(PREFIX) and a < w1 and a + d > w0]
    pieces = _pieces(steps + engine, w0, w1)
    starts = [a for a, _, _ in pieces]

    busy: list[float] = []
    gaps: list[tuple[float, float]] = []
    idle_by: dict[str, float] = {}
    for dev in raw["devices"]:
        ops = [(max(a, w0), min(a + d, w1))
               for _, a, d in dev["lines"].get(trace.OPS_LINE, [])
               if a < w1 and a + d > w0]
        if not ops:
            continue
        merged = trace._union(ops)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps += idle
        _attribute(idle, pieces, idle_by)
    if not busy:
        raise RuntimeError("no device operation ran inside the traced window")

    span_s: dict[str, tuple[float, int]] = {}
    for a, b, name in engine:
        total, n = span_s.get(name, (0.0, 0))
        span_s[name] = (total + (b - a) * 1e-9, n + 1)

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        inner = pieces[bisect.bisect_right(starts, mid) - 1][2]
        text = trace._span_at(mid, steps)
        return f"{text} / {inner}" if inner.startswith(PREFIX) else text

    return SpanReduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / len(busy),
        idle_by_span={k: v / len(busy) for k, v in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])},
        span_seconds=span_s,
        idle_gaps=[(label(a, b), (b - a) * 1e-9) for a, b in
                   sorted(gaps, key=lambda g: g[0] - g[1])[:trace.TOP]])


def _pieces(spans: list[tuple[float, float, str]], w0: float,
            w1: float) -> list[tuple[float, float, str]]:
    """``[w0, w1]`` cut into consecutive pieces, each named by the innermost
    of the nested ``spans`` (start, end, name) that holds it, or
    ``harness``."""
    out: list[tuple[float, float, str]] = []
    open_: list[tuple[float, str]] = []     # (end, name), innermost last
    t = w0

    def upto(x: float) -> None:
        nonlocal t
        x = min(max(x, t), w1)
        if x > t:
            out.append((t, x, open_[-1][1] if open_ else OUTSIDE))
            t = x

    def close() -> None:
        upto(open_[-1][0])
        open_.pop()

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while open_ and open_[-1][0] <= a:
            close()
        upto(a)
        open_.append((b, name))
    while open_:
        close()
    upto(w1)
    return out


def _attribute(idle: list[tuple[float, float]],
               pieces: list[tuple[float, float, str]],
               into: dict[str, float]) -> None:
    """Add the seconds of the sorted, disjoint ``idle`` intervals to
    ``into`` under the name of each piece they overlap."""
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            name = pieces[k][2]
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            into[name] = into.get(name, 0.0) + (hi - lo) * 1e-9
            k += 1


def main() -> int:
    if len(sys.argv) != 2:
        raise SystemExit(__doc__.split("\n\n")[1])
    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    print(json.dumps(dataclasses.asdict(reduce(load(path)))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
