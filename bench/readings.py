#!/usr/bin/env python3
"""Readings that the limits of ``check.LIMITS`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 12 --control 3 --seconds 10

In one process (the chip belongs to one process), runs the cell on
``--seeds`` seeds with the configuration's own policy and on ``--control``
of them with its lower-precision control policy, each with a short window at
the cell's own load, and prints every run's compared numbers, then the
largest reading of the program and the smallest of the control per number.
Not part of the benchmark's runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    low, high = {}, {}
    plan = [(s, 0) for s in range(args.seeds)] + \
        [(s, 1) for s in range(args.control)]
    for i, ctl in plan:
        seed = args.first_seed + 7919 * i
        out = run.run(types.SimpleNamespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=0, control=ctl), cell=cell)
        rec = {"workload": args.workload, "seed": seed, "control": ctl,
               "correct": out["correct"], "attempted": out["attempted"],
               "failed": out["failed"],
               "checks": {k: c["value"] for k, c in out["checks"].items()}}
        print(json.dumps(rec), flush=True)
        for k, v in rec["checks"].items():
            if ctl:
                high[k] = min(high.get(k, float("inf")), v)
            else:
                low[k] = max(low.get(k, 0.0), v)
    print(json.dumps({"workload": args.workload, "program_max": low,
                      "control_min": high}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
