#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same engine serves the cell's
traffic at each of several rates in one process, and each rate's window
prints its tokens/s, tails and backlog.

    python bench/sweep.py --workload <open-loop cell> --rates 6,8,10,12 \
        --seconds 15 --seed 7

The knee is the highest rate whose queue stays flat through the window;
the cell's traffic file then takes 0.8 of it as a fixed number.  This is a
tool for defining a cell, not part of a benchmark run.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    R.configure_jax_cache()
    R.require_chips(cell.chips)

    import repro.mp as mp
    from repro.launch.serve import make_scheduler

    from bench import traffic as traffic_lib, warmup, weights
    from bench.driver import Driver

    mp.configure(backend="pallas")
    spec = cell.traffic
    model = cell.config["model"]
    rates = [float(r) for r in args.rates.split(",")]
    params = weights.make_params(cell.config, args.seed, cell.root)
    sched = make_scheduler(R.model_config(cell.config), params,
                           slots=spec["slots"], max_seq=spec["max_seq"],
                           policy=R.engine_policy(spec),
                           block_size=spec["block_size"],
                           n_blocks=spec["n_blocks"])
    streams = {}
    for r in rates:
        s = dict(spec, rate_per_s=r)
        streams[r] = (s, traffic_lib.make_stream(
            s, model["vocab"], args.seed, spec["preroll_s"] + args.seconds))
    t0 = time.perf_counter()
    for s, st in streams.values():
        warmup.warm(sched, s, st)
    R.log(f"sweep: warm-up {time.perf_counter() - t0:.1f} s")
    for r, (s, st) in streams.items():
        tr0 = sched.engine.trace_events
        drv = Driver(sched, s, st)
        drv.run(spec["preroll_s"], args.seconds)
        e2e = R.end_to_end(drv, args.seconds, 0.0)
        due = drv.due_in_window()
        late = [t.sent - t.due for t in due]
        row = {"rate_per_s": r, **{k: e2e[k] for k in
                                   ("out_tok_per_s", "ttft_p95_ms",
                                    "itl_p95_ms")},
               "ttft_p50_ms": R.percentile(
                   [(t.first - t.due) * 1e3 for t in due], 50),
               "queued_at_close": sched.n_queued,
               "active_at_close": sched.n_active,
               "lateness_p95_ms": R.percentile(late, 95) * 1e3,
               "traces_in_window": sched.engine.trace_events - tr0,
               "tick_ms_p50": R.percentile(
                   [(x.t1 - x.t0) * 1e3 for x in drv.window_steps()], 50)}
        print(json.dumps(row), flush=True)
        # empty the scheduler before the next rate
        for t in list(drv.live.values()):
            sched.cancel(t.req.idx)
        while sched.n_active or sched.n_queued:
            sched.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
