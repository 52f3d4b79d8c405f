"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a plain structure that
keeps the device planes (``/device:TPU:<n>``) and the harness's own host
spans (``bench.*``); ``reduce`` computes from that structure:

* the traced window: the ``bench.traced`` span, which the harness opens
  between two steps once the trace has started and closes after the
  window's last step, so that it holds whole steps and nothing else;
* device busy time: the union of the intervals of the ``XLA Ops`` events
  of each device plane inside the window, averaged over the planes that ran
  anything;
* the device operations that took most time, by name;
* the longest idle gaps of the device, each named by the harness span it
  fell in: ``admit step`` (a ``step_decode`` call that admitted requests,
  so ran their prefill), ``decode step`` (one that only decoded) or
  ``harness`` (between steps: the loop's own bookkeeping and clients).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Any

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
GAP_NAMES = {"bench.step.admit": "admit step",
             "bench.step.decode": "decode step"}
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> dict[str, Any]:
    """The device planes and harness spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines[line.name] = [[_short(e.name), e.start_ns,
                                         e.duration_ns] for e in line.events]
            devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.70 = (...)
    fusion(...)`` reads ``%fusion.70``."""
    return name.split(" = ", 1)[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]


def reduce(trace: dict[str, Any]) -> Reduction:
    windows = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    w0 = windows[0][1]
    w1 = w0 + windows[0][2]
    steps = sorted((s[1], s[1] + s[2], GAP_NAMES[s[0]])
                   for s in trace["spans"] if s[0] in GAP_NAMES)

    busy: list[float] = []
    by_op: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for dev in trace["devices"]:
        ops = [(max(a, w0), min(a + d, w1), name)
               for name, a, d in dev["lines"].get(OPS_LINE, [])
               if a < w1 and a + d > w0]
        if not ops:
            continue
        for a, b, name in ops:
            by_op[name] = by_op.get(name, 0.0) + (b - a) * 1e-9
        merged = _union([(a, b) for a, b, _ in ops])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    if not busy:
        raise RuntimeError("no device operation ran inside the traced window")
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / len(busy),
        device_ops=sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP],
        idle_gaps=[(_span_at((a + b) / 2, steps), (b - a) * 1e-9)
                   for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]])


def _span_at(t: float, steps: list[tuple[float, float, str]]) -> str:
    for a, b, name in steps:
        if a <= t <= b:
            return name
    return "harness"
