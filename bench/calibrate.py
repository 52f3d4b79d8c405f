#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the worst and the mean logit
gap of the program and of the fp8 control, on many seeds, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...] --seconds <s>

Each seed is one run of the cell (set-up, a window of ``--seconds``, the
comparison) with the control beside the program at the same positions.
One JSON line per seed goes to standard output; the last line gives, for
each number, the largest program reading and the smallest control
reading.  Like
``run.py`` it measures only on a TPU.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.spec import load_cell, peaks_for  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    from bench.harness import run_cell
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU found (platform {dev.platform!r})")
    peaks = peaks_for(dev.device_kind)
    program, control = {}, {}
    for seed in args.seeds:
        out = run_cell(cell, seed, args.seconds, False, peaks=peaks,
                       device=dev, control=True,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
        r = out["readings"]
        for name, v in r["program"].items():
            program.setdefault(name, []).append(v)
            control.setdefault(name, []).append(r["control"][name])
        print(json.dumps({"seed": seed, **r, "attempted": out["attempted"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}), flush=True)
    print(json.dumps({name: {"program_max": max(program[name]),
                             "control_min": min(control[name])}
                      for name in program} | {"seeds": len(args.seeds)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
