"""The load driver: sends a stream of requests into the scheduler on the
wall clock, through ``submit()`` and ``step()`` only, and records what the
client sees.

An open loop submits each request at its due time with ``t_submit`` set to
that due time.  A closed loop keeps one request in flight per client.  Every
``step()`` is one host span; after it, each request's new tokens are stamped
with the span's end, the moment the host can hand them to the client.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

from repro.serve.scheduler import ScheduledRequest

from bench.traffic import Req

DRAIN_LIMIT_S = 60.0


@dataclasses.dataclass
class Track:
    """One request as the client sees it (host clock, seconds)."""
    req: Req
    sreq: ScheduledRequest
    due: float
    sent: float
    first: float = math.nan
    done: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRec:
    """One ``step()`` call: its span and the work it did, as counted from
    the tokens that appeared (prefills by prompt length, decoded tokens by
    the context they attended over)."""
    t0: float
    t1: float
    did: bool
    prefills: List[Tuple[int, str]] = dataclasses.field(default_factory=list)
    decodes: List[Tuple[int, str]] = dataclasses.field(default_factory=list)

    @property
    def phase(self) -> str:
        """``prefill`` for a tick that admitted a request (it decodes too),
        ``decode`` for one that only decoded, else ``idle``."""
        if self.prefills:
            return "prefill"
        return "decode" if self.decodes else "idle"


class Driver:
    def __init__(self, sched, spec: dict, stream: List[Req], *,
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Optional[Callable[[str], object]] = None):
        self.sched = sched
        self.spec = spec
        self.stream = stream
        self.clock = clock
        self.annotate = annotate
        self.tracks: List[Track] = []
        self.live: Dict[int, Track] = {}
        self.steps: List[StepRec] = []
        self.t_win0 = self.t_end = self.t_stop = math.nan
        self._pending: Deque[Req] = deque()
        self._clients: Dict[int, Deque[Req]] = {}
        self._t0 = math.nan
        self._sending = True

    # ---- sending ----------------------------------------------------------
    def _send(self, req: Req, due: float) -> None:
        sreq = ScheduledRequest(rid=req.idx, prompt=req.prompt,
                                max_new=req.max_new, mode=req.mode)
        sreq.t_submit = due
        sent = self.clock()
        self.sched.submit(sreq)
        tr = Track(req=req, sreq=sreq, due=due, sent=sent)
        self.tracks.append(tr)
        self.live[req.idx] = tr

    def _send_due(self, now: float) -> None:
        while self._sending and self._pending and \
                self._t0 + self._pending[0].due <= now:
            req = self._pending.popleft()
            self._send(req, self._t0 + req.due)

    def _next_due(self) -> float:
        if self._sending and self._pending:
            return self._t0 + self._pending[0].due
        return math.inf

    # ---- stepping ---------------------------------------------------------
    def _step(self) -> None:
        span = self.annotate("bench.step") if self.annotate else None
        t0 = self.clock()
        if span is not None:
            with span:
                did = self.sched.step()
        else:
            did = self.sched.step()
        t1 = self.clock()
        rec = StepRec(t0, t1, bool(did))
        finished = []
        for tr in self.live.values():
            n = len(tr.sreq.out)
            seen = len(tr.times)
            if n > seen:
                plen = len(tr.req.prompt)
                mode = tr.req.mode
                k0 = seen + 1
                if seen == 0:
                    rec.prefills.append((plen, mode))
                    tr.first = t1
                    k0 = 2
                for k in range(k0, n + 1):
                    rec.decodes.append((plen + k - 1, mode))
                tr.times.extend([t1] * (n - seen))
            if tr.sreq.state not in ("queued", "running"):
                tr.done = t1
                finished.append(tr)
        for tr in finished:
            del self.live[tr.req.idx]
            if self._sending and tr.req.client >= 0:
                self._send_next(tr.req.client, t1)
        self.steps.append(rec)

    def _send_next(self, client: int, now: float) -> None:
        q = self._clients.get(client)
        if q:
            self._send(q.popleft(), now)

    def _wait_until(self, t: float) -> None:
        span = self.annotate("bench.wait") if self.annotate else None
        dt = t - self.clock()
        if dt > 0:
            if span is not None:
                with span:
                    time.sleep(dt)
            else:
                time.sleep(dt)

    # ---- the run ----------------------------------------------------------
    def run(self, preroll_s: float, seconds: float,
            marks: Sequence[Tuple[float, Callable[[], None]]] = ()) -> None:
        """Pre-roll for ``preroll_s``, then the measured window of
        ``seconds``; then stop sending and step on until every request due
        in the window has its first token (at most ``DRAIN_LIMIT_S``).
        Each mark ``(offset, fn)`` calls ``fn()`` between steps once the
        clock has passed the window's start plus ``offset``."""
        self._t0 = self.clock()
        self.t_win0 = self._t0 + preroll_s
        self.t_end = self.t_win0 + seconds
        if self.spec["loop"] == "open":
            self._pending = deque(sorted(self.stream, key=lambda r: r.due))
        else:
            for r in self.stream:
                self._clients.setdefault(r.client, deque()).append(r)
            for c in sorted(self._clients):
                self._send_next(c, self._t0)
        todo = deque(sorted(((self.t_win0 + off, fn) for off, fn in marks),
                            key=lambda m: m[0]))
        while True:
            now = self.clock()
            while todo and todo[0][0] <= now:
                todo.popleft()[1]()
                now = self.clock()
            if now >= self.t_end:
                break
            self._send_due(now)
            if self.sched.n_active or self.sched.n_queued:
                self._step()
            else:
                nxt = todo[0][0] if todo else math.inf
                self._wait_until(min(self._next_due(), self.t_end, nxt))
        while todo:
            todo.popleft()[1]()
        self._sending = False
        limit = self.clock() + DRAIN_LIMIT_S
        while any(math.isnan(t.first) for t in self.due_in_window()) \
                and self.clock() < limit \
                and (self.sched.n_active or self.sched.n_queued):
            self._step()
        self.t_stop = self.clock()

    # ---- what the client saw ----------------------------------------------
    def due_in_window(self) -> List[Track]:
        return [t for t in self.tracks if self.t_win0 <= t.due < self.t_end]

    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps
                if s.t0 >= self.t_win0 and s.t1 <= self.t_end]
