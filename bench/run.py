#!/usr/bin/env python3
"""Run one cell of the serving benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's model with random weights from ``--seed`` and the
serving engine, then fills the batch through the closed loop.  The window
drives the engine for ``--seconds`` seconds.  Once it closes, a sample of
the requests it served is compared with the plain reference.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiler trace of the window),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared beside its limit, which also end standard error.

It exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``bench/peaks.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.spec import SpecError, load_cell, peaks_for  # noqa: E402


def fail(msg: str) -> None:
    raise SystemExit(f"bench: {msg}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights and the traffic")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window, report per-layer metrics")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: put the fp8 control in the program's place in "
                         "the comparison, so that the run must come out not "
                         "correct (calibration of the limit)")
    args = ap.parse_args()
    if args.seconds <= 0:
        fail("--seconds must be positive")
    try:
        cell = load_cell(args.workload)
    except SpecError as e:
        fail(str(e))
    try:
        from bench.harness import run_cell
    except ImportError as e:
        fail(f"the system under test cannot be imported: {e}")

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX reports platform {dev.platform!r}; this "
             "benchmark measures only on a TPU")
    if len(devices) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} chips, JAX finds "
             f"{len(devices)}")
    try:
        peaks = peaks_for(dev.device_kind)
    except SpecError as e:
        fail(str(e))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}", flush=True)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   peaks=peaks, device=dev, t_start=T_START,
                   control=bool(args.control),
                   log=lambda s: print(s, flush=True))
    out["device"] = {**device, **out["device"]}
    out.pop("readings")
    checks = out.pop("checks")
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (at {c['at']} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
