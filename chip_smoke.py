#!/usr/bin/env python3
"""Chip smoke run: the paper model's serving and training path, once, on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the two multi-device paths, four chips

One chip drives ``paper-mpfp-100m`` (768 wide, 12 layers, d_ff 3072, vocab
32000, random weights from a seed) through the normal entry points with
``backend="pallas"`` (compiled Mosaic kernels):

  kernels   one ``mp_matmul`` per built-in format, 256x768 @ 768x3072,
            against a float64 numpy product, within the format's
            ``rel_err_bound`` — on ``pallas`` and on the default ``ref``;
  serving   the launcher's engine + continuous scheduler
            (``launch/serve.make_scheduler``) serving 8 requests (16-64
            prompt tokens, 16 new tokens, formats rotating M8/M16/M23)
            through the paged prefill and the mixed decode step: every
            request completes, no guard trips, one decode launch per tick,
            one request matches its solo run token for token, and the
            compiled decode step holds Mosaic kernels (``tpu_custom_call``);
  training  3 steps of the launcher's ``Trainer`` (batch 8, seq 128,
            ``train_default``) with finite losses and no NaN rollback.

``--chips 4`` runs only the sharded matmul backend and sequence-parallel
decode attention (``dist/attention.sp_decode_attention``) on a 4-device mesh
at paper widths, each against the same call on one device.

Earlier lines are progress plus set-up and smoke timings (not metrics).  The
last line is one JSON object, ``{"ok": true, "device": {"platform", "kind",
"count"}}``.  No TPU, a phase that raises, or a check that fails ends the
run with a non-zero exit and without that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "paper-mpfp-100m"
SERVE_MODES = ("M8", "M16", "M23")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def rel_err(out, gold) -> float:
    import numpy as np

    gold = np.asarray(gold, np.float64)
    diff = np.asarray(out, np.float64) - gold
    return float(np.linalg.norm(diff) / np.linalg.norm(gold))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def kernel_phase(backends=("pallas", "ref"), m: int = 256, k: int = 768,
                 n: int = 3072, seed: int = 0) -> None:
    """One mp_matmul per built-in format and backend against a float64
    product: the Pallas kernels, and the XLA limb cascade the default
    ``ref`` backend (and the backward's weight gradients) run."""
    import jax
    import numpy as np

    from repro.core import mp_matmul
    from repro.core.formats import builtin_formats, resolve

    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    gold = a.astype(np.float64) @ b.astype(np.float64)
    for backend in backends:
        for fmt in sorted(map(resolve, builtin_formats()),
                          key=lambda f: f.n_limbs):
            t0 = time.perf_counter()
            out = np.asarray(jax.jit(
                lambda x, y: mp_matmul(x, y, fmt, backend=backend))(a, b))
            dt = time.perf_counter() - t0
            rel = rel_err(out, gold)
            log(f"kernels {backend} {fmt.name}: rel_err {rel!r} bound "
                f"{fmt.rel_err_bound!r} (set-up incl. compile {dt:.2f}s)")
            check(rel < fmt.rel_err_bound,
                  f"{backend} {fmt.name} matmul error {rel} over its bound")


def _record_mixed_steps(engine, calls: list) -> None:
    """Keep the engine's jit'd mixed decode step and the arguments of its
    latest call, so the run can read back the program it executed."""
    step_for = engine.mixed_decode_step_for

    def recording(envelope):
        fn = step_for(envelope)

        def call(*args):
            calls[:] = [(fn, args)]
            return fn(*args)

        return call

    engine.mixed_decode_step_for = recording


def serve_phase(cfg, *, n_requests: int = 8, prompt_lens=(16, 64),
                max_new: int = 16, max_seq: int = 128,
                seed: int = 0) -> None:
    import jax
    import numpy as np

    from repro.core.policy import get_policy
    from repro.launch.serve import make_scheduler
    from repro.models import transformer as T
    from repro.serve.primitives import ScheduledRequest
    from repro.serve.scheduler import ContinuousScheduler

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(
        prompt_lens[0], prompt_lens[1] + 1))).astype(np.int32)
        for _ in range(n_requests)]

    def request(i: int) -> ScheduledRequest:
        return ScheduledRequest(rid=i, prompt=prompts[i], max_new=max_new,
                                mode=SERVE_MODES[i % len(SERVE_MODES)])

    t0 = time.perf_counter()
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    sched = make_scheduler(cfg, params, slots=n_requests, max_seq=max_seq,
                           policy=get_policy("serve_default"))
    jax.block_until_ready(sched.engine._decode_params)
    log(f"serving set-up (params + engine + weight prelimb): "
        f"{time.perf_counter() - t0:.2f}s")
    calls: list = []
    _record_mixed_steps(sched.engine, calls)

    t0 = time.perf_counter()
    done = sched.run([request(i) for i in range(n_requests)])
    log(f"serving smoke timing (incl. compiles): "
        f"{time.perf_counter() - t0:.2f}s for {n_requests} requests")
    stats = sched.stats()
    log(f"serving stats: completed {stats['completed']}/{n_requests} "
        f"guard_trips {stats['guard_trips']} launches_per_tick "
        f"{stats['launches_per_tick']} decode_ticks {sched.decode_ticks}")
    check(len(done) == n_requests
          and all(len(r.out) == max_new for r in done),
          "not every request completed with all its tokens")
    check(stats["guard_trips"] == 0, "numerical guardrail tripped")
    check(stats["launches_per_tick"] == 1.0,
          "mixed decode took more than one launch per tick")

    by_rid = {r.rid: r.out for r in done}
    solo = ContinuousScheduler(sched.engine, n_blocks=sched.pool.n_blocks,
                               block_size=sched.pool.block_size)
    solo_out = solo.run([request(0)])[0].out
    log(f"serving solo parity (rid 0, {SERVE_MODES[0]}): batched "
        f"{by_rid[0]} solo {solo_out}")
    check(solo_out == by_rid[0], "request 0 differs from its solo run")

    check(bool(calls), "the mixed decode step never ran")
    fn, args = calls[0]
    hlo = fn.lower(*args).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"serving decode step: {n_kernels} tpu_custom_call in compiled HLO")
    check(n_kernels > 0, "compiled decode step holds no Mosaic kernel")


def train_phase(cfg, *, steps: int = 3, batch: int = 8,
                seq: int = 128) -> None:
    import math

    from repro.core.policy import get_policy
    from repro.launch.train import make_trainer

    trainer, pipe = make_trainer(cfg, batch=batch, seq=seq, steps=steps,
                                 policy=get_policy("train_default"))
    state, losses = None, []
    for step in range(steps):
        t0 = time.perf_counter()
        state, hist = trainer.run(pipe, start_step=step, num_steps=step + 1,
                                  state=state, log_every=0)
        dt = time.perf_counter() - t0
        losses += hist
        kind = "set-up incl. compile" if step == 0 else "smoke timing"
        log(f"train step {step}: loss {hist[-1]!r} ({kind} {dt:.2f}s)")
    check(trainer.rollbacks == 0, "a training step went non-finite")
    check(len(losses) == steps and all(map(math.isfinite, losses)),
          f"training losses not finite: {losses}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def _spanning_mesh(n: int):
    from repro.launch.mesh import make_matmul_mesh

    mesh = make_matmul_mesh(n)
    ids = {d.id for d in mesh.devices.flat}
    check(len(ids) == n, f"mesh spans {len(ids)} distinct devices, not {n}")
    return mesh


def _all_reduces(fn, *args) -> int:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text().count("all-reduce")


def multichip_phase(n_devices: int, *, m: int = 256, k: int = 768,
                    n: int = 3072, batch: int = 8, t: int = 2048,
                    heads: int = 12, head_dim: int = 64,
                    seed: int = 0) -> None:
    """Sharded matmul and sequence-parallel decode attention on an
    ``n_devices`` mesh, each against the same call on one device."""
    import jax
    import numpy as np

    import repro.mp as mp
    from repro.core import mp_matmul
    from repro.core.formats import resolve
    from repro.dist.attention import sp_decode_attention

    mesh, one = _spanning_mesh(n_devices), _spanning_mesh(1)
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    gold = a.astype(np.float64) @ b.astype(np.float64)
    for fmt in map(resolve, SERVE_MODES):
        def matmul(x, y):
            return mp_matmul(x, y, fmt, backend="sharded")

        with mp.context(mesh=mesh):
            out = jax.jit(matmul)(a, b)
            n_ar = _all_reduces(matmul, a, b)
        with mp.context(mesh=one):
            ref = np.asarray(jax.jit(matmul)(a, b))
        spans = len(out.sharding.device_set)
        diff, err = rel_err(out, ref), rel_err(out, gold)
        log(f"sharded matmul {fmt.name}: {n_ar} all-reduce, output on "
            f"{spans} devices, rel diff vs one device {diff!r}, rel_err "
            f"{err!r} (bound {fmt.rel_err_bound!r})")
        check(n_ar > 0 and spans == n_devices,
              "sharded matmul did not span the mesh")
        check(diff < fmt.rel_err_bound and err < fmt.rel_err_bound,
              f"sharded matmul {fmt.name} disagrees with one device")

    q = rng.standard_normal((batch, 1, heads, head_dim)).astype(np.float32)
    kv = rng.standard_normal((2, batch, t, heads, head_dim)
                             ).astype(np.float32)
    lengths = rng.integers(1, t + 1, size=(batch,)).astype(np.int32)
    for fmt in map(resolve, ("M8", "M23")):
        def attend(q, k, v, ln, msh):
            return sp_decode_attention(q, k, v, ln, fmt, fmt, mesh=msh)

        sp = np.asarray(jax.jit(lambda *xs: attend(*xs, mesh))(
            q, kv[0], kv[1], lengths))
        ref = np.asarray(jax.jit(lambda *xs: attend(*xs, one))(
            q, kv[0], kv[1], lengths))
        n_ar = _all_reduces(lambda *xs: attend(*xs, mesh),
                            q, kv[0], kv[1], lengths)
        diff = rel_err(sp, ref)
        log(f"sp decode attention {fmt.name}: {n_ar} all-reduce, rel diff "
            f"vs one device {diff!r} (bound {fmt.rel_err_bound!r})")
        check(n_ar > 0, "sequence-parallel attention ran no collective")
        check(diff < fmt.rel_err_bound,
              f"sp decode attention {fmt.name} disagrees with one device")


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, serving, training on one chip; "
                         "4: the multi-device paths only")
    args = ap.parse_args()

    from repro.launch.compile_cache import configure_compile_cache

    log(f"compile cache: {configure_compile_cache()}")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    log(f"device: {devices[0].device_kind} x{len(devices)}")

    import repro.mp as mp
    from repro.configs.registry import get_config

    mp.configure(backend="pallas")
    t0 = time.perf_counter()
    if args.chips == 4:
        multichip_phase(4)
    else:
        cfg = get_config(ARCH)
        kernel_phase()
        serve_phase(cfg)
        train_phase(cfg)
    log(f"smoke wall time: {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
