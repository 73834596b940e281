#!/usr/bin/env python3
"""How far the program's router logits lie from the reference's: the
reading that sets a routed configuration's tie margin
(``TIE_MULTIPLE`` in its reference).

    python bench/router_probe.py --workload dsv2l-m23-closed \
        --seeds 11,12,13 --tokens 1024

For each seed it makes the cell's weights and one sequence of the cell's
prompt tokens, runs the program's forward pass over it (with the cell's
policy and the Pallas kernels) and reads each MoE layer's router logits, then the reference's at ``highest``.  One JSON line per seed: the
widest difference, alone and in units of the reference's M23 bound of the
router's dot products at its position, and how many positions route
differently.  A tool for setting the margin, not part of a benchmark run.
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


def program_routers(params, tokens, cfg, policy):
    """The program's router logits of every MoE layer over one sequence.

    It runs the program's own ``transformer.forward`` (the layers unrolled,
    so no value is traced inside a scan) and records what the MoE module's
    ``mp_matmul`` returns: on the dense expert path that module calls it for
    the router's logits alone, so the records are the logits the program
    routes by, in layer order.  The raw router is split into limbs as the
    serving engine's prelimbed copy is (tests/test_prelimb_layout.py)."""
    import dataclasses

    from repro.models import moe as moe_lib
    from repro.models import transformer as T

    seen = []
    real = moe_lib.mp_matmul

    def recording(*args, **kw):
        out = real(*args, **kw)
        seen.append(out)
        return out

    moe_lib.mp_matmul = recording
    try:
        T.forward(params, {"tokens": tokens[None]},
                  dataclasses.replace(cfg, scan_layers=False), policy)
    finally:
        moe_lib.mp_matmul = real
    return seen


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", type=int, default=1024)
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    R.configure_jax_cache()
    R.require_chips(cell.chips)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.mp as mp

    from bench import traffic as traffic_lib, weights

    mp.configure(backend="pallas")
    model = cell.config["model"]
    cfg = R.model_config(cell.config)
    policy = R.engine_policy(cell.traffic)
    ref = cell.reference
    k = model["moe"]["top_k"]
    prog_fn = jax.jit(lambda p, t: program_routers(p, t, cfg, policy))
    ref_fn = jax.jit(lambda p, t: ref.forward(p, t, model)[1])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params = weights.make_params(cell.config, seed, cell.root)
        stream = traffic_lib.make_stream(cell.traffic, model["vocab"], seed,
                                         10.0)
        toks = np.concatenate([r.prompt for r in stream])[: args.tokens]
        toks = jnp.asarray(toks, jnp.int32)
        prog = [np.asarray(x, np.float64) for x in prog_fn(params, toks)]
        refs = [(np.asarray(lg, np.float64), np.asarray(b, np.float64))
                for lg, b in ref_fn(params, toks)]
        diff = [np.abs(p - r) for p, (r, _) in zip(prog, refs)]
        ratio = [np.max(d, axis=-1) / b for d, (_, b) in zip(diff, refs)]
        flips = [int(np.sum(np.any(np.sort(np.argsort(-p)[:, :k], -1)
                                   != np.sort(np.argsort(-r)[:, :k], -1), -1)))
                 for p, (r, _) in zip(prog, refs)]
        gap = [float(np.min(np.sort(r, -1)[:, -k] - np.sort(r, -1)[:, -k - 1]))
               for r, _ in refs]
        row = {"seed": seed, "tokens": int(toks.shape[0]),
               "widest_diff": max(float(np.max(d)) for d in diff),
               "widest_ratio": max(float(np.max(r)) for r in ratio),
               "median_bound": float(np.median(np.concatenate(
                   [b for _, b in refs]))),
               "route_flips": flips, "least_tie_gap": gap,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
