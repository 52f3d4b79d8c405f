"""Find a cell's pieces by name: the benchmark file, the configuration, the
traffic mix, the cell's limits, the per-layer metric readers and the
table of peaks."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A name that the data files do not resolve."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple[str, ...] | None   # None: every cell reporting `moves`
    moves: str | None = None            # per-layer metrics only


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    traffic_name: str
    traffic: dict[str, Any]
    limits: dict[str, Any]
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    root: Path = ROOT


def _read_json(path: Path) -> dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _metrics(entries: list[dict], per_layer: bool) -> list[Metric]:
    return [Metric(e["name"], e["unit"], e["better"], e["source"],
                   tuple(e["workloads"]) if "workloads" in e else None,
                   e.get("moves") if per_layer else None)
            for e in entries]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files, which
    lie under ``<root>/bench``, and the metrics it reports."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names no known config "
                        f"{w['config']!r}")
    here = root / "bench"
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(here / "mixes" / f"{w['traffic']}.json")
    limits = _read_json(here / "limits" / f"{name}.json")

    e2e = [m for m in _metrics(bench["end_to_end"], False)
           if m.workloads is None or name in m.workloads]
    reported = {m.name for m in e2e}
    layer = [m for m in _metrics(bench["per_layer"], True)
             if (name in m.workloads if m.workloads is not None
                 else m.moves in reported)]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, limits, tuple(e2e), tuple(layer), root)


def metric_reader(name: str, root: Path = ROOT) -> Callable[[Any], Any]:
    """``read(run)`` of ``bench/metrics/<name>.py``: the metric's value, or
    None where the run holds nothing for it to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{len(name)}_{abs(hash(name))}", path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(device_kind: str, root: Path = ROOT) -> dict[str, Any]:
    """The published peaks of ``device_kind``; a device missing from
    ``bench/peaks.json`` is an error, never a default."""
    table = _read_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
