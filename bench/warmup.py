"""Warm-up: compile, before the window, every program the cell's traffic
can reach, by driving synthetic requests through ``submit()`` and
``step()`` and cancelling them.

The shapes follow the serving program's bucketing (``serve/primitives``):
a prefill is one request padded to a power of two, with a block table as
wide as the power of two above its reserved blocks (at most the
per-sequence maximum); a decode launch pads its batch to a power of two and
its table to the widest member's, and its program is keyed by the batch's
formats (one format: the per-policy step; several: the lane step, keyed by
the deepest).  Each prefill key is reached by one request of the stream
that has it; each decode key by a batch admitted in one tick.  The
pre-roll then runs the cell's own traffic, which reaches the steady state.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from repro.serve.scheduler import ScheduledRequest

from bench.traffic import Req

WARM_RID0 = 1 << 40   # request ids of warm-up requests, clear of the stream's


def pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def table_width(tokens: int, block_size: int, max_blocks: int) -> int:
    return min(pow2_at_least(-(-tokens // block_size)), max_blocks)


def prefill_key(req: Req, block_size: int, max_blocks: int) -> Tuple:
    return (req.mode, pow2_at_least(len(req.prompt)),
            table_width(len(req.prompt) + req.max_new, block_size,
                        max_blocks))


def prefill_reps(reqs: List[Req], block_size: int, max_blocks: int
                 ) -> Dict[Tuple, Req]:
    """One request of the stream for each distinct prefill key."""
    reps: Dict[Tuple, Req] = {}
    for r in reqs:
        reps.setdefault(prefill_key(r, block_size, max_blocks), r)
    return reps


def _depth(mode: str, order: List[str]) -> int:
    return order.index(mode)


def pow2_at_most(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def decode_widths(spec: dict, slots: int) -> List[int]:
    """The padded batch widths a decode launch of the cell can have.

    A closed loop holds ``clients`` requests in flight: a client sends its
    next request as soon as the step that finished its last one returns,
    and the next step admits it before it decodes, so every decode launch
    carries ``min(clients, slots)`` requests.  An open loop can reach any
    occupancy."""
    cap = pow2_at_most(slots)
    if spec["loop"] == "closed":
        return [min(pow2_at_least(min(spec["clients"], slots)), cap)]
    widths, w = [], 1
    while w <= cap:
        widths.append(w)
        w *= 2
    return widths


def decode_batches(reps: Dict[Tuple, Req], spec: dict, slots: int
                   ) -> List[List[Req]]:
    """Batches that reach each decode key (format set, batch width, table
    width) the traffic can reach: every batch width of
    ``decode_widths``, every table width a request of the stream has, and
    every deepest format (alone when it is the only format of the mix,
    else beside the shallowest)."""
    modes = sorted(spec["modes"], key=lambda m: int(m[1:]))
    widths = decode_widths(spec, slots)
    tables = sorted({k[2] for k in reps})
    by_table: Dict[int, List[Req]] = {}
    for k, r in sorted(reps.items(), key=lambda kv: (kv[0][2], kv[0][1])):
        by_table.setdefault(k[2], []).append(r)
    smallest: Dict[str, Req] = {}
    for r in sorted(reps.values(), key=lambda r: len(r.prompt) + r.max_new,
                    reverse=True):
        smallest[r.mode] = r
    batches = []
    for deepest in modes:
        mates = [deepest] if len(modes) == 1 or deepest == modes[0] \
            else [modes[0]]
        for mb in widths:
            k = mb // 2 + 1 if mb > 1 else 1
            for w in tables:
                wide = [r for r in by_table[w]
                        if _depth(r.mode, modes) <= _depth(deepest, modes)]
                if not wide:
                    continue
                head = max(wide, key=lambda r: _depth(r.mode, modes))
                batch = [head]
                if head.mode != deepest:
                    batch.append(smallest[deepest])
                while len(batch) < k:
                    batch.append(smallest[mates[0]])
                batches.append(batch)
    return batches


def prefill_batches(reps: Dict[Tuple, Req], slots: int) -> List[List[Req]]:
    """The prefill representatives in batches of more than half the slots
    (padded with repeats), so each admitting tick decodes at the full
    batch width the steady state uses."""
    items = list(reps.values())
    if not items:
        return []
    size = max(1, min(slots, len(items)))
    low = slots // 2 + 1
    out = []
    for i in range(0, len(items), size):
        b = items[i:i + size]
        while len(b) < min(low, slots):
            b.append(b[len(b) % len(b)])
        out.append(b)
    return out


def run_batch(sched, batch: List[Req], rid0: int) -> int:
    """Admit a batch in one tick (prefills plus one decode launch), then
    cancel it.  Returns the next free request id."""
    rids = []
    for r in batch:
        sreq = ScheduledRequest(rid=rid0, prompt=r.prompt,
                                max_new=r.max_new, mode=r.mode)
        sched.submit(sreq)
        rids.append(rid0)
        rid0 += 1
    sched.step()
    for rid in rids:
        sched.cancel(rid)
    return rid0


def warm(sched, spec: dict, reqs: List[Req], log=None) -> Dict[str, float]:
    """Run every warm-up batch; returns counts and the seconds it took."""
    t0 = time.perf_counter()
    pool = sched.pool
    reps = prefill_reps(reqs, pool.block_size, pool.max_blocks_per_seq)
    slots = sched.max_slots
    before = sched.engine.trace_events
    rid = WARM_RID0
    pre = prefill_batches(reps, slots)
    dec = decode_batches(reps, spec, slots)
    for i, batch in enumerate(pre + dec):
        rid = run_batch(sched, batch, rid)
        if log is not None and i % 10 == 9:
            log(f"bench: warm-up batch {i + 1}/{len(pre) + len(dec)} at "
                f"{time.perf_counter() - t0:.1f} s, "
                f"{sched.engine.trace_events - before} traces")
    return {"prefill_keys": len(reps), "batches": len(pre) + len(dec),
            "traces": sched.engine.trace_events - before,
            "seconds": time.perf_counter() - t0}
