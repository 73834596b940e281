"""Shared serving primitives: the request record, paged admission/step
building blocks, and latency accounting.

Both serving control loops — the single-engine
:class:`~repro.serve.scheduler.ContinuousScheduler` and the multi-engine
fleet (``serve/fleet/``) — are thin state machines over the same four
primitives:

  * :func:`try_reserve` / :func:`release` — graceful all-or-nothing block
    reservation against a :class:`~repro.serve.kv_cache.PagedKVPool`
    (exhaustion is a *scheduling event*, never an exception: the caller
    requeues and retries after eviction reclaim);
  * :func:`prefill_request` — one B=1 bucketed paged prefill producing the
    request's first output token;
  * :func:`bucket_by_policy` + :func:`decode_bucket_step` — one decode tick:
    active requests grouped by resolved per-request policy, each bucket
    routed through the engine's format-keyed jit'd step;
  * :func:`latency_stats` — per-request TTFT / TPOT / inter-token-latency /
    queue-wait percentiles over a completed set (the router-balancing and
    prefill-interference metrics the fleet benchmark gates on);
  * the **numerical guardrail** — every decode step returns one scalar per
    slot (max |logit|, computed inside the jit'd step so the check costs no
    extra launch); :func:`guard_check` turns it into a per-slot verdict
    (NaN/Inf, or past the registry ``rel_err_bound``-scaled sentinel) and
    :func:`escalate_mode` is the recovery dial — the inverse of the
    router's pressure downgrade: a poisoned M8 request re-admits at M16.

Recovery rides on the same prefill primitive: a request that already holds
generated tokens (``req.out``) re-prefills its *host-visible prefix*
(prompt + all emitted tokens but the last) instead of just the prompt, which
rebuilds the exact KV state the lost cell held — then decode resumes by
consuming ``out[-1]`` as if nothing happened.  See
:func:`prefill_request`.

Keeping these here (engine-agnostic, pool-explicit) is what lets a
disaggregated prefill engine and a decode engine on a *different* pool run
the exact jit'd steps the single-engine scheduler runs — the KV-handoff
bit-parity guarantee (tests/test_fleet.py) falls out of the sharing.

Host spans: the prefill and decode primitives open
``jax.profiler.TraceAnnotation`` spans (``serve.prefill`` with its
``.launch``/``.sync`` children; ``serve.decode`` with ``.prep``, ``.launch``,
``.sync``, ``.finish``), one per launch and never per row.  They record only
while a profiler collects, on the device trace's clock, so a trace can say
which host code sits in each device-idle gap; both control loops get them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import context as context_lib
from repro.core.formats import (
    available_formats, builtin_formats, get_format, is_auto)
from repro.core.policy import PrecisionPolicy
from repro.serve.kv_cache import PagedKVPool

# the guardrail's recovery dial: one mode UP on numerical divergence — the
# exact inverse of the router's pressure DOWNGRADE_CHAIN (M23 -> M16 -> M8).
# This is only the *fast path* for the built-in serving ladder:
# :func:`_next_rung` falls back to the format registry for run-time
# registered formats (next-higher mantissa_bits), so a custom-format request
# that trips the guardrail escalates instead of silently re-admitting at the
# mode that just diverged.
ESCALATE_CHAIN = {"M8": "M16", "M16": "M23"}


def _next_rung(cur: str) -> Optional[str]:
    """The next precision rung above ``cur``: the hardcoded builtin chain
    when it applies, else the registered format with the smallest
    ``mantissa_bits`` strictly above the current one (ties broken by fewer
    limbs, then name, for determinism).  None when ``cur`` is unknown,
    AUTO, or already at the top of the ladder."""
    nxt = ESCALATE_CHAIN.get(cur)
    if nxt is not None:
        return nxt
    if cur in builtin_formats():
        # builtin formats above the chain (M23/M36/M52) are the serving
        # ceiling by design — only *registered* custom formats fall through
        # to the registry ladder
        return None
    try:
        fmt = get_format(cur)
    except Exception:
        return None
    if is_auto(fmt):
        return None
    cands = []
    for name in available_formats():
        f = get_format(name)
        if not is_auto(f) and f.mantissa_bits > fmt.mantissa_bits:
            cands.append(f)
    if not cands:
        return None
    best = min(cands, key=lambda f: (f.mantissa_bits, f.n_limbs, f.name))
    return best.name


@dataclasses.dataclass
class ScheduledRequest:
    """One serving request with its own precision QoS.

    ``mode`` is a single format spelling (``"M8"``, a registered custom
    format, ...) applied as a whole-network overlay on the engine's policy;
    ``policy`` is a full per-request :class:`PrecisionPolicy` (object or
    JSON wire form) and wins over ``mode``.  Leave both None to inherit the
    engine policy.

    The fleet router adds routing metadata: ``submitter`` tags whose
    completion queue the finished request fans out to, ``engine_id`` records
    the decode engine that served it, ``requeues``/``downgraded_from`` record
    graceful-degradation events (admission backoff, mode downgrade under
    pressure).  Latency accounting (``t_submit``/``t_first``/``t_done``,
    per-token ``itl`` intervals) feeds :func:`latency_stats`.
    """

    rid: int
    prompt: np.ndarray                      # (S,) int32
    max_new: int = 16
    mode: Optional[object] = None           # FormatLike QoS overlay
    policy: Optional[object] = None         # PrecisionPolicy | JSON
    eos_token: Optional[int] = None
    arrival: int = 0                        # virtual arrival step
    submitter: str = "default"              # completion fan-out tag
    deadline_ticks: Optional[int] = None    # TTL in virtual ticks from submit

    # runtime state (scheduler/fleet-owned)
    out: List[int] = dataclasses.field(default_factory=list)
    state: str = "queued"           # queued | running | done |
    #                                 expired | canceled
    slot: Optional[int] = None
    blocks: List[int] = dataclasses.field(default_factory=list)
    length: int = 0                         # tokens in the paged cache
    next_token: int = -1                    # decode input for the next step
    admitted_step: int = -1
    done_step: int = -1
    engine_id: int = -1                     # decode engine that served it
    requeues: int = 0                       # admission-pressure requeues
    downgraded_from: Optional[str] = None   # original mode before downgrade
    resolved_policy: Optional[PrecisionPolicy] = None  # cached at submit

    # fault-tolerance state
    submitted_tick: int = -1                # deadline epoch (virtual)
    recoveries: int = 0                     # cell-loss recoveries survived
    guard_trips: int = 0                    # numerical guardrail evictions
    escalated_from: Optional[str] = None    # original mode before escalation
    lost_tick: int = -1                     # tick the serving cell was lost
    # len(out) at each re-admission — chaos parity re-runs the suffix solo
    recovery_prefixes: List[int] = dataclasses.field(default_factory=list)

    # wall-clock latency accounting (perf_counter seconds; -1 = unset)
    t_submit: float = -1.0
    t_first: float = -1.0
    t_done: float = -1.0
    itl: List[float] = dataclasses.field(default_factory=list)


def pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pow2_at_most(n: int) -> int:
    """Largest power of two <= n (n >= 1) — the decode micro-batch width
    cap.  Clamping to this instead of a raw non-pow2 ``max_slots`` keeps
    every decode launch on a pow2-bucketed batch shape: ``min(pow2_at_least
    (len), max_slots)`` with e.g. max_slots=12 would mint a stray width-12
    jit trace the moment 9+ requests were active, alongside the 1/2/4/8
    buckets."""
    if n < 1:
        raise ValueError(f"micro-batch cap must be >= 1, got {n}")
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# numerical guardrail
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GuardrailConfig:
    """Per-slot decode-logit policing.

    The finite check (NaN/Inf anywhere in a slot's logits) is always on.
    ``logit_bound`` adds the sentinel: a slot whose max |logit| exceeds
    ``logit_bound * (1 + fmt.rel_err_bound)`` — the registry's error bound
    for the request's lm_head format widens the envelope for low-precision
    formats, which legitimately wobble more — is treated as diverged and
    escalated exactly like a NaN.  ``max_trips_per_request`` bounds how
    often one request may trip before the loop fails loudly instead of
    cycling forever (a request that produces NaN even at the top mode is a
    model/params bug, not a serving condition)."""

    logit_bound: Optional[float] = None
    max_trips_per_request: int = 5

    def bound_for(self, policy: PrecisionPolicy) -> Optional[float]:
        if self.logit_bound is None:
            return None
        fmt = policy.mode("lm_head")
        if is_auto(fmt):
            return None
        return self.logit_bound * (1.0 + float(fmt.rel_err_bound))


def guard_check(stat: np.ndarray, policy: PrecisionPolicy,
                guard: Optional[GuardrailConfig]) -> np.ndarray:
    """Per-slot verdict over the step's max-|logit| scalars: True = healthy.
    NaN/Inf in the logits surfaces as a non-finite max (``jnp.max``
    propagates NaNs), so one scalar per slot carries both checks."""
    ok = np.isfinite(stat)
    bound = guard.bound_for(policy) if guard is not None else None
    if bound is not None:
        ok &= ~(stat > bound)  # NaN-safe: non-finite rows already False
    return ok


def escalate_mode(req: ScheduledRequest) -> bool:
    """One step UP the precision ladder after a guardrail trip (M8 -> M16 ->
    M23 on the builtin chain; registered custom formats climb to the
    registry's next-higher ``mantissa_bits`` rung via :func:`_next_rung`),
    recording the original mode; returns False when the request has no
    escalatable mode (full-policy or engine-default requests, unknown or
    top-of-ladder formats, re-admit unchanged — recovery still applies, the
    dial just has nowhere to go)."""
    if req.policy is not None or req.mode is None:
        return False
    cur = getattr(req.mode, "name", None) or str(req.mode)
    nxt = _next_rung(cur)
    if nxt is None:
        return False
    if req.escalated_from is None:
        req.escalated_from = cur
    req.mode = nxt
    req.resolved_policy = None  # re-resolve at the new mode
    return True


def deadline_expired(req: ScheduledRequest, tick: int) -> bool:
    """TTL check against the virtual clock; the epoch is the submit tick
    (set by the control loop when the request enters its clock domain)."""
    return (req.deadline_ticks is not None and req.submitted_tick >= 0
            and tick - req.submitted_tick >= req.deadline_ticks)


def resolve_request(req: ScheduledRequest, base: PrecisionPolicy
                    ) -> PrecisionPolicy:
    """Resolve + cache a request's effective policy (decode ticks hit this
    per slot per step; JSON wire policies must not re-parse in the hot
    loop).  Cleared to None by the router on mode downgrade."""
    if req.resolved_policy is None:
        req.resolved_policy = context_lib.resolve_request_policy(
            mode=req.mode, policy=req.policy, base=base)
    return req.resolved_policy


def blocks_needed(pool: PagedKVPool, req: ScheduledRequest) -> int:
    return pool.blocks_for_tokens(len(req.prompt) + req.max_new)


def validate_request(pool: PagedKVPool, req: ScheduledRequest) -> None:
    """Fail unschedulable requests NOW, not after the rest of the batch has
    run (an oversized request at the FIFO head would otherwise stall
    admissions and only raise at the very end of a run)."""
    from repro.serve.kv_cache import BlockPoolExhausted

    req.prompt = np.asarray(req.prompt, np.int32)
    if req.prompt.ndim != 1 or req.prompt.size == 0:
        raise ValueError("prompt must be a non-empty 1-D int32 array")
    if req.max_new < 1:
        raise ValueError("max_new must be >= 1")
    need = blocks_needed(pool, req)
    capacity = min(pool.max_blocks_per_seq, pool.n_blocks - 1)
    if need > capacity:
        raise BlockPoolExhausted(
            f"request {req.rid} needs {need} blocks "
            f"({len(req.prompt)} prompt + {req.max_new} new tokens) but "
            f"the pool can hold at most {capacity} per request")


def try_reserve(pool: PagedKVPool, req: ScheduledRequest) -> bool:
    """Graceful all-or-nothing reservation of a request's full block budget.

    Exhaustion mid-admission is an expected serving condition (the pool is
    shared — under the fleet, by concurrent engines), so it must never
    raise out of an admission loop or leak a partial reservation:
    ``PagedKVPool.try_alloc`` takes the free-list lock, hands out all ``n``
    blocks or none, and this returns False so the caller can requeue the
    request behind eviction reclaim."""
    blocks = pool.try_alloc(blocks_needed(pool, req))
    if blocks is None:
        return False
    req.blocks = blocks
    return True


def release(pool: PagedKVPool, req: ScheduledRequest) -> None:
    """Return a request's blocks to the free list (eviction / rollback)."""
    if req.blocks:
        pool.free(req.blocks)
        req.blocks = []


def table_width(pool: PagedKVPool, reqs: Sequence[ScheduledRequest]) -> int:
    """Bounded paged reads: the block table handed to a jit step is sliced
    to the bucket's maximum *used* block count (pow2-bucketed so the trace
    count stays O(log max_blocks_per_seq)) instead of all
    ``max_blocks_per_seq`` trash-padded columns — the fallback gather copies
    W·bs tokens per slot per step, and the paged kernel runs W grid columns,
    so trash padding is pure waste.  Positions past the sliced width still
    redirect to the trash block on write (models/attention._paged_write
    clamps against the table width)."""
    used = max(len(r.blocks) for r in reqs)
    return min(pow2_at_least(used), pool.max_blocks_per_seq)


def prefill_tokens(req: ScheduledRequest) -> np.ndarray:
    """The host-visible sequence a prefill must write: the prompt for a
    fresh request; for a recovery re-prefill (``req.out`` non-empty after a
    cell loss or guardrail eviction) the prompt plus every emitted token but
    the last — exactly the positions the lost KV cache covered, since the
    newest token's KV is only written by the decode step that consumes it."""
    if not req.out:
        return req.prompt
    return np.concatenate(
        [req.prompt, np.asarray(req.out[:-1], np.int32)])


def prefill_request(engine, pool: PagedKVPool, req: ScheduledRequest) -> int:
    """One B=1 bucketed paged prefill: writes the request's K/V blocks into
    ``pool`` and returns the first output token (argmax of the true-last-
    position logits).  The caller owns pushing the token / handing off.

    Recovery contract: when ``req.out`` is non-empty this is a re-prefill of
    the generated prefix (:func:`prefill_tokens`) — the caller must *discard*
    the returned token (the already-emitted ``out[-1]`` stays the decode
    input; under an unchanged mode the two are bit-identical anyway, under
    an escalated mode the emitted history is immutable)."""
    seq = prefill_tokens(req)
    n = len(seq)
    s_pad = pow2_at_least(n)
    with TraceAnnotation("serve.prefill"):
        policy = resolve_request(req, engine.policy)
        prefill_fn, _ = engine.paged_steps_for(policy)
        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, :n] = seq
        table = pool.table_row(req.blocks)[None, :table_width(pool, [req])]
        lengths = np.zeros((1,), np.int32)
        with TraceAnnotation("serve.prefill.launch"):
            logits, _stat = engine.launch_paged(
                prefill_fn, pool, engine.params, jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(tokens), np.int32(n - 1))
        req.length = n
        now = time.perf_counter()
        if req.t_first < 0:
            req.t_first = now
        with TraceAnnotation("serve.prefill.sync"):
            return int(jnp.argmax(logits[0, 0, :]))


def bucket_by_policy(reqs: Sequence[ScheduledRequest],
                     base: PrecisionPolicy
                     ) -> List[Tuple[PrecisionPolicy,
                                     List[ScheduledRequest]]]:
    """Group active requests by resolved policy: one micro-batch per bucket,
    each routed through the format-keyed jit'd step for its policy."""
    buckets: Dict[PrecisionPolicy, List[ScheduledRequest]] = {}
    for req in reqs:
        buckets.setdefault(resolve_request(req, base), []).append(req)
    return list(buckets.items())


def decode_tick_plan(reqs: Sequence[ScheduledRequest],
                     base: PrecisionPolicy
                     ) -> List[Tuple[str, List[ScheduledRequest]]]:
    """Partition one tick's active requests into decode launches — shape
    bucketing, not format bucketing.

    Every lane-eligible request (all decode op classes resolved to static
    formats) joins ONE group regardless of its format: a homogeneous group
    keeps the legacy per-policy step (``("bucket", reqs)`` — no lane tables
    to carry), a heterogeneous group becomes one partitioned-lane launch
    (``("mixed", reqs)`` via :func:`decode_mixed_step`).  Only AUTO-policy
    requests still bucket per policy (their formats are chosen per operand
    *inside* the step, so there is no static lane to mask).  Under any
    non-AUTO traffic mix the plan is exactly one launch per tick.
    """
    from repro.core import lanes as lanes_lib

    eligible: List[ScheduledRequest] = []
    rest: List[ScheduledRequest] = []
    for r in reqs:
        pol = resolve_request(r, base)
        (eligible if lanes_lib.lanes_eligible(pol) else rest).append(r)
    plan: List[Tuple[str, List[ScheduledRequest]]] = []
    if eligible:
        pols = {resolve_request(r, base) for r in eligible}
        plan.append(("bucket" if len(pols) == 1 else "mixed", eligible))
    for _, group in bucket_by_policy(rest, base):
        plan.append(("bucket", group))
    return plan


def decode_bucket_step(engine, pool: PagedKVPool,
                       reqs: Sequence[ScheduledRequest], *,
                       max_slots: int, guard=None, injector=None,
                       cell_id: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """One jit'd decode step for one policy bucket: builds the pow2-padded
    (table, lengths, tokens) micro-batch, runs the step, advances each
    request's cache length, and returns ``(tokens, ok)`` — one new token and
    one guardrail verdict per request.

    The guardrail scalar (max |logit| per slot) comes back from the jit'd
    step itself — the ``isfinite``/sentinel reduction is folded into the
    step function, so policing costs no extra launch.  A False verdict means
    the slot's logits are poisoned (NaN/Inf, a sentinel trip, or an injected
    ``step_nan`` fault): the caller must discard that token and evict only
    that slot.  Rows that trip do not advance ``length`` or ITL accounting —
    the victim is re-prefilled from its host-visible prefix anyway.

    Inter-token latency accounting: the wall-clock gap since the request's
    previous token lands in ``req.itl`` — the per-token latency distribution
    whose p95 the fleet benchmark compares across scheduling disciplines
    (prefill interference shows up here as a heavy tail)."""
    cap = pow2_at_most(max_slots)
    if len(reqs) > cap:
        # pathological non-pow2 max_slots admitting more actives than the
        # pow2 cap: run pow2-width chunks rather than mint a stray trace
        return _chunked_steps(
            lambda part: decode_bucket_step(
                engine, pool, part, max_slots=cap, guard=guard,
                injector=injector, cell_id=cell_id), reqs, cap)
    mb = min(pow2_at_least(len(reqs)), cap)
    with TraceAnnotation("serve.decode"):
        with TraceAnnotation("serve.decode.prep"):
            table, lengths, tokens, w = _micro_batch(pool, reqs, mb)
            policy = resolve_request(reqs[0], engine.policy)
            _, decode_fn = engine.paged_steps_for(policy)
            params = engine._decode_params_for(policy)
        with TraceAnnotation("serve.decode.launch"):
            logits, stat = engine.launch_paged(
                decode_fn, pool, params, jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(tokens))
        with TraceAnnotation("serve.decode.sync"):
            toks = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
            stat_np = np.asarray(stat)[: len(reqs)]
        with TraceAnnotation("serve.decode.finish"):
            ok = guard_check(stat_np, policy, guard)
            _finish_decode_rows(reqs, ok, injector, cell_id)
    return toks[: len(reqs)], ok


def _micro_batch(pool: PagedKVPool, reqs: Sequence[ScheduledRequest],
                 mb: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """The pow2-padded (table, lengths, tokens) arrays one decode launch
    consumes; padded rows are (trash row, length 0, token 0) so they read
    nothing and write to trash."""
    w = table_width(pool, reqs)
    table = np.stack(
        [pool.table_row(r.blocks) for r in reqs]
        + [pool.trash_row()] * (mb - len(reqs)))[:, :w]
    lengths = np.asarray([r.length for r in reqs]
                         + [0] * (mb - len(reqs)), np.int32)
    tokens = np.asarray([[r.next_token] for r in reqs]
                        + [[0]] * (mb - len(reqs)), np.int32)
    return table, lengths, tokens, w


def _finish_decode_rows(reqs: Sequence[ScheduledRequest], ok: np.ndarray,
                        injector, cell_id: int) -> None:
    """Post-step request bookkeeping shared by the bucket and mixed decode
    steps: injected-fault verdicts, cache-length advance, and per-token ITL
    accounting (rows that tripped advance nothing — the victim re-prefills
    from its host-visible prefix)."""
    if injector is not None:
        for i, r in enumerate(reqs):
            if ok[i] and injector.step_nan(cell_id, r.slot, r.rid):
                ok[i] = False
    now = time.perf_counter()
    for r, good in zip(reqs, ok):
        if not good:
            continue
        r.length += 1
        prev = r.t_first if not r.itl else r.t_first + sum(r.itl)
        r.itl.append(now - prev)


def _chunked_steps(step_fn, reqs: Sequence[ScheduledRequest], cap: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    parts = [step_fn(list(reqs[i:i + cap]))
             for i in range(0, len(reqs), cap)]
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def decode_mixed_step(engine, pool: PagedKVPool,
                      reqs: Sequence[ScheduledRequest], *,
                      max_slots: int, guard=None, injector=None,
                      cell_id: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """ONE partitioned-lane decode launch over a *heterogeneous* group:
    every request runs at its own resolved (non-AUTO) format inside a
    single jit'd step — the run-time reconfigurable datapath of the paper,
    lane-partitioned over the micro-batch instead of bucketed into one
    launch per format.

    The group's static lane *envelope* (per-op-class max limbs/order) keys
    the trace; the per-slot formats travel as (C, B) int32 lane tables —
    data, not trace constants — so any format mix under the envelope reuses
    one compiled step.  Weights come from the prelimbed cache at the
    envelope's batch-max limb depth: limb decomposition is depth-stable, so
    a shallow lane masking into the deep stack sees bit-identical limbs to
    its homogeneous bucket.  Guardrail verdicts are per-request (each
    request's own lm_head ``rel_err_bound`` scales its sentinel), matching
    what the per-bucket path would have ruled.

    Same return contract, padding discipline, and ITL accounting as
    :func:`decode_bucket_step`."""
    from repro.core import lanes as lanes_lib

    cap = pow2_at_most(max_slots)
    if len(reqs) > cap:
        return _chunked_steps(
            lambda part: decode_mixed_step(
                engine, pool, part, max_slots=cap, guard=guard,
                injector=injector, cell_id=cell_id), reqs, cap)
    mb = min(pow2_at_least(len(reqs)), cap)
    with TraceAnnotation("serve.decode"):
        with TraceAnnotation("serve.decode.prep"):
            table, lengths, tokens, w = _micro_batch(pool, reqs, mb)
            policies = [resolve_request(r, engine.policy) for r in reqs]
            env = lanes_lib.envelope_of(policies)
            lane_n, lane_ord = lanes_lib.lane_tables(policies, mb)
            decode_fn = engine.mixed_decode_step_for(env)
            params = engine._decode_params_for_limbs(env.max_limbs)
        with TraceAnnotation("serve.decode.launch"):
            logits, stat = engine.launch_paged(
                decode_fn, pool, params, jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(tokens),
                jnp.asarray(lane_n), jnp.asarray(lane_ord))
        with TraceAnnotation("serve.decode.sync"):
            toks = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
            stat_np = np.asarray(stat)[: len(reqs)]
        with TraceAnnotation("serve.decode.finish"):
            ok = np.ones(len(reqs), bool)
            for i, (r, pol) in enumerate(zip(reqs, policies)):
                ok[i] = bool(guard_check(stat_np[i:i + 1], pol, guard)[0])
            _finish_decode_rows(reqs, ok, injector, cell_id)
    return toks[: len(reqs)], ok


# ---------------------------------------------------------------------------
# latency accounting
# ---------------------------------------------------------------------------
def _pcts(values: List[float], unit: float = 1.0) -> Tuple[float, float]:
    if not values:
        return 0.0, 0.0
    arr = np.asarray(values, np.float64) * unit
    return (round(float(np.percentile(arr, 50)), 3),
            round(float(np.percentile(arr, 95)), 3))


def latency_stats(completed: Sequence[ScheduledRequest]) -> Dict[str, float]:
    """Per-request latency percentiles over a completed set.

    TTFT (submit -> first token) and TPOT (mean decode time per output
    token after the first) are wall-clock milliseconds; ITL is the pooled
    per-token interval distribution (its p95 is where prefill interference
    shows up); queue-wait is virtual steps (admitted - arrival), the
    router-balancing signal that stays deterministic across machines."""
    ttft = [r.t_first - r.t_submit for r in completed
            if r.t_first >= 0 and r.t_submit >= 0]
    tpot = [(r.t_done - r.t_first) / (len(r.out) - 1) for r in completed
            if r.t_done >= 0 and r.t_first >= 0 and len(r.out) > 1]
    itl = [dt for r in completed for dt in r.itl]
    qwait = [float(r.admitted_step - r.arrival) for r in completed
             if r.admitted_step >= 0]
    out: Dict[str, float] = {}
    for name, vals, unit in (("ttft_ms", ttft, 1e3), ("tpot_ms", tpot, 1e3),
                             ("itl_ms", itl, 1e3),
                             ("queue_wait_steps", qwait, 1.0)):
        metric, suffix = name.rsplit("_", 1)
        out[f"{metric}_p50_{suffix}"], out[f"{metric}_p95_{suffix}"] = \
            _pcts(vals, unit)
    return out
