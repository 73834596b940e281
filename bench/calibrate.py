#!/usr/bin/env python3
"""Readings for a cell's correctness limits: runs of the cell on several
seeds in one process, each printing the program's numbers and the
control's, one JSON line per seed.

    python bench/calibrate.py --workload glm3l-m23-closed \
        --seeds 11,12,13 --seconds 10

The control is the configuration's plain reference put in the program's
place, one precision below each request's format (``bf16``, ``high``); it
reads the gap of the token that the lower precision puts first at each
served position.  A limit lies between the
largest program reading and the smallest control reading (PERF.md gives
both).  This is a tool for setting limits, not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

import bench.run as R  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    R.configure_jax_cache()
    R.require_chips(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = R.run_cell(cell, seed, args.seconds, False,
                         t_start=time.perf_counter(),
                         controls=("bf16", "high"))
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: v["value"] for k, v in res["checks"].items()},
               "controls": res["controls"], "window": res["window"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
