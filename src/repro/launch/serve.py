"""Serving launcher: batched generation with the precision dial.

Static path (one batch, one policy):

    PYTHONPATH=src python -m repro.launch.serve --arch paper-mpfp-100m \
        --smoke --policy serve_default --requests 4 --max-new 16

Continuous-batching path (paged KV pool, Poisson request stream, per-request
precision modes — the paper's mode table as per-request QoS):

    PYTHONPATH=src python -m repro.launch.serve --arch paper-mpfp-100m \
        --smoke --scheduler --requests 12 --mixed-modes

Fleet path (N engine replicas behind the mode-aware router, disaggregated
prefill/decode with paged-KV handoff — serve/fleet/):

    PYTHONPATH=src python -m repro.launch.serve --arch paper-mpfp-100m \
        --smoke --engines 4 --disaggregate --router-policy mode_affinity \
        --requests 16 --mixed-modes

Chaos path (deterministic fault injection against the fleet — kill cells,
poison decode steps, fail handoffs — per a JSON plan; see serve/faults.py):

    python - <<'EOF'  # write a seeded plan
    from repro.serve.faults import FaultPlan
    open("plan.json", "w").write(FaultPlan.chaos(seed=0, n_cells=4).to_json())
    EOF
    PYTHONPATH=src python -m repro.launch.serve --arch paper-mpfp-100m \
        --smoke --engines 4 --requests 16 --fault-plan plan.json
"""
import argparse

import numpy as np
import jax

from repro.configs.registry import ARCH_IDS, get_config
from repro.core.policy import get_policy
from repro.launch.compile_cache import configure_compile_cache
from repro.models import transformer as T
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-mpfp-100m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="serve_default")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--backend", default="",
                    help="mp_matmul dispatch backend (ref/pallas/"
                         "pallas_interpret/sharded); '' = context default")
    ap.add_argument("--scheduler", action="store_true",
                    help="continuous-batching scheduler (paged KV pool, "
                         "join-on-arrival/evict-on-EOS) instead of the "
                         "static generate() batch")
    ap.add_argument("--mixed-modes", action="store_true",
                    help="scheduler only: give requests rotating per-request "
                         "precision modes (M8/M16/M23)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="scheduler only: Poisson mean arrivals per decode "
                         "step for the simulated request stream")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="scheduler only: paged pool size in blocks "
                         "(0 = sized from --requests)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="scheduler only: tokens per KV block")
    ap.add_argument("--engines", type=int, default=0,
                    help="fleet mode: number of engine cells behind the "
                         "router (0 = no fleet; implies the request-stream "
                         "driver)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="fleet only: pace prefill (1/cell/tick) so decode "
                         "ticks never starve behind a prefill burst; "
                         "default is interleaved (greedy prefill)")
    ap.add_argument("--router-policy", default="round_robin",
                    choices=("round_robin", "least_kv", "mode_affinity"),
                    help="fleet only: cell placement policy")
    ap.add_argument("--fault-plan", default="",
                    help="fleet only: JSON fault plan (serve/faults.py "
                         "FaultPlan) injected deterministically — cell "
                         "crashes, poisoned decode steps, failed handoffs")
    args = ap.parse_args()
    configure_compile_cache()

    if args.backend:
        # one-shot process configuration (replaces REPRO_MP_BACKEND env)
        import repro.mp as mp
        mp.configure(backend=args.backend)

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    if not args.smoke and cfg.param_count() > 1e9 \
            and jax.default_backend() == "cpu":
        raise SystemExit("full config on CPU: use --smoke")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if args.engines:
        _run_fleet(cfg, params, args, rng)
        return
    if args.scheduler:
        _run_scheduler(cfg, params, args, rng)
        return

    eng = ServeEngine(cfg, params, max_batch=args.requests,
                      max_seq=args.max_seq, policy=get_policy(args.policy))
    prompts = [rng.integers(0, cfg.vocab, size=rng.integers(2, 9)
                            ).astype(np.int32)
               for _ in range(args.requests)]
    outs = eng.generate(prompts, max_new=args.max_new)
    for i, o in enumerate(outs):
        print(f"req{i} ({len(prompts[i])} prompt toks): {o}")
    print(eng.decode_throughput_probe())


def _build_stream(cfg, args, rng):
    """Poisson arrival trace shared by the scheduler and fleet drivers."""
    from repro.serve.primitives import ScheduledRequest

    modes = ("M8", "M16", "M23") if args.mixed_modes else (None,)
    t = 0
    reqs = []
    for i in range(args.requests):
        t += int(rng.poisson(1.0 / max(args.arrival_rate, 1e-6)))
        reqs.append(ScheduledRequest(
            rid=i,
            prompt=rng.integers(0, cfg.vocab,
                                size=int(rng.integers(2, 17))
                                ).astype(np.int32),
            max_new=int(rng.integers(2, args.max_new + 1)),
            mode=modes[i % len(modes)],
            arrival=t))
    return reqs


def make_scheduler(cfg, params, *, slots: int, max_seq: int, policy,
                   block_size: int = 16, n_blocks: int = 0):
    """The engine and continuous scheduler that ``--scheduler`` serves
    through (``chip_smoke.py`` serves through the same pair).  ``n_blocks`` 0 sizes
    the paged pool at two full-length sequences per slot."""
    from repro.serve.scheduler import ContinuousScheduler

    eng = ServeEngine(cfg, params, max_batch=slots, max_seq=max_seq,
                      policy=policy)
    n_blocks = n_blocks or (
        1 + slots * 2 * max(1, -(-max_seq // block_size)))
    return ContinuousScheduler(eng, n_blocks=n_blocks, block_size=block_size)


def _run_scheduler(cfg, params, args, rng):
    """Request-stream driver: Poisson arrivals through the continuous
    scheduler, each request optionally carrying its own precision mode."""
    sched = make_scheduler(cfg, params, slots=min(args.requests, 8),
                           max_seq=args.max_seq,
                           policy=get_policy(args.policy),
                           block_size=args.kv_block_size,
                           n_blocks=args.kv_blocks)
    done = sched.run(_build_stream(cfg, args, rng))
    for r in sorted(done, key=lambda r: r.rid):
        qos = r.mode or "engine-default"
        print(f"req{r.rid} [{qos}] arrive@{r.arrival} "
              f"admit@{r.admitted_step} done@{r.done_step}: {r.out}")
    print(sched.stats())


def _run_fleet(cfg, params, args, rng):
    """Fleet driver: the same Poisson stream routed over --engines cells
    (one shared ServeEngine, per-cell pools, paged-KV prefill->decode
    handoff) through the --router-policy placement policy."""
    from repro.serve.fleet import FleetRouter, make_fleet

    eng = ServeEngine(cfg, params, max_batch=4, max_seq=args.max_seq,
                      policy=get_policy(args.policy))
    block_size = args.kv_block_size
    n_blocks = args.kv_blocks or (
        1 + 8 * max(1, -(-(args.max_seq) // block_size)))
    cells = make_fleet(eng, args.engines, n_blocks=n_blocks,
                       block_size=block_size,
                       disaggregate=args.disaggregate)
    plan = None
    if args.fault_plan:
        from repro.serve.faults import FaultPlan
        with open(args.fault_plan) as f:
            plan = FaultPlan.from_json(f.read())
    router = FleetRouter(cells, policy=args.router_policy, fault_plan=plan)
    done = router.run(_build_stream(cfg, args, rng))
    for r in sorted(done, key=lambda r: r.rid):
        qos = r.mode or "engine-default"
        extra = f" (downgraded from {r.downgraded_from})" \
            if r.downgraded_from else ""
        if r.escalated_from:
            extra += f" (escalated from {r.escalated_from})"
        if r.recoveries:
            extra += f" (recovered x{r.recoveries})"
        print(f"req{r.rid} [{qos}]{extra} arrive@{r.arrival} "
              f"cell{r.engine_id} done@{r.done_step}: {r.out}")
    if plan is not None:
        print("fault trace:", router.injector.trace)
    print(router.stats())


if __name__ == "__main__":
    main()
