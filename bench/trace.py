"""Reduction of a profiler trace to device busy time, kernel time and the
host activity behind each idle gap.

A trace is read into two plain lists: device operations ``(name, start,
end, text)`` from the ``XLA Ops`` line of each TPU device plane, where
``text`` is the name with the event's string statistics (the HLO text,
the JAX op path), which the kernel-family patterns search; and the
benchmark's own host spans (``bench.*`` annotations).  Times are
nanoseconds on the trace's clock.  The traced window is the
``bench.traced`` span.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Tuple

Event = Tuple  # (name, start_ns, end_ns[, text])

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"
STEP_SPAN = "bench.step"


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]   # plane name -> its operations
    host_spans: List[Event]

    def step_spans(self) -> List[Event]:
        """The ``bench.step`` spans inside the traced window, in order."""
        lo, hi = self.window()
        return sorted((s for s in self.host_spans
                       if s[0] == STEP_SPAN and lo <= s[1] and s[2] <= hi),
                      key=lambda s: s[1])

    def window(self) -> Tuple[float, float]:
        spans = [s for s in self.host_spans if s[0] == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                             f"{len(spans)}")
        return spans[0][1], spans[0][2]


def from_xplane(path: str) -> Trace:
    """Read an ``.xplane.pb`` file written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    dev: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = dev.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             _text(e)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    return Trace(dev, host)


def _text(event) -> str:
    """The event's name and its string statistics.  On a TPU the name is
    the op's HLO text (``%checkpoint.79 = f32[32,4096]{...} custom-call(
    ...), ..., operand_layout_constraints={...}``), without the backend
    config that holds a Pallas kernel's function name."""
    return " ".join([event.name] + [v for _, v in event.stats
                                    if isinstance(v, str)])


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for ev in events:
        s, e = max(ev[1], lo), min(ev[2], hi)
        if e > s:
            out.append((ev[0], s, e) + tuple(ev[3:]))
    return out


def merge(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for _, s, e, *_ in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(e - s for s, e in merge(events))


def family_ns(events: Iterable[Event], patterns: List[str]) -> float:
    """Device time of the operations whose text matches any pattern (each
    event counted once).  Each pattern is a regular expression of its own,
    so its groups and back-references are its own."""
    rxs = [re.compile(p) for p in patterns]
    hits: Dict[str, bool] = {}
    total = 0.0
    for ev in events:
        text = ev[3] if len(ev) > 3 else ev[0]
        if text not in hits:
            hits[text] = any(rx.search(text) for rx in rxs)
        if hits[text]:
            total += ev[2] - ev[1]
    return total


def idle_gaps(events: Iterable[Event], lo: float, hi: float,
              host_spans: List[Event]) -> List[Tuple[str, float]]:
    """Each interval of the window in which no device operation ran, named
    by the innermost ``bench.*`` host span around its midpoint, longest
    first: ``(name, ns)``."""
    gaps = []
    t = lo
    for s, e in merge(events) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        around = [h for h in host_spans
                  if h[0] != WINDOW_SPAN and h[1] <= mid <= h[2]]
        name = (min(around, key=lambda h: h[2] - h[1])[0] if around
                else "host:untraced")
        named.append((name, e - s))
    return sorted(named, key=lambda g: -g[1])


_OPERANDS = re.compile(r"operand_layout_constraints=(\{.*?\}\})")


def op_key(name: str) -> str:
    """A short name for a device operation: its HLO name with the numeric
    suffix folded (``fusion.12`` -> ``fusion``), and for a custom call the
    shapes of its operands.  On the chip the profiler names an operation by
    its whole HLO text (``%checkpoint.79 = f32[32,4096]{...} custom-call(
    ...), ..., operand_layout_constraints={f32[32,13824]{1,0}, ...}``), which
    gives ``checkpoint {f32[32,13824], bf16[3,13824,4096]}``."""
    head, _, rest = name.partition(" = ")
    key = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    m = _OPERANDS.search(rest)
    if m:
        key += " " + re.sub(r"\{[\d,]*\}", "", m.group(1))
    return key


def top_ops(events: Iterable[Event], n: int = 10) -> List[Tuple[str, float]]:
    """Device time per operation (``op_key``), largest first."""
    tot: Dict[str, float] = {}
    for name, s, e, *_ in events:
        key = op_key(name)
        tot[key] = tot.get(key, 0.0) + (e - s)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


@dataclasses.dataclass
class Summary:
    """A trace reduced over its window, averaged over the devices traced."""
    window_s: float
    busy_s: float
    family_s: Dict[str, float]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    idle_by_host: Dict[str, float]


def summarize(trace: Trace, families: Dict[str, List[str]]) -> Summary:
    lo, hi = trace.window()
    planes = {k: clip(v, lo, hi) for k, v in trace.device_ops.items()}
    if not any(planes.values()):
        raise ValueError("no device operation in the traced window")
    n = len(planes)
    busy = sum(busy_ns(v) for v in planes.values()) / n
    fam = {f: sum(family_ns(v, pats) for v in planes.values()) / n
           for f, pats in families.items()}
    first = planes[sorted(planes)[0]]
    gaps = idle_gaps(first, lo, hi, trace.host_spans)
    by_host: Dict[str, float] = {}
    for name, ns in gaps:
        by_host[name] = by_host.get(name, 0.0) + ns * 1e-9
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
        family_s={f: v * 1e-9 for f, v in fam.items()},
        device_ops=[(k, v * 1e-9) for k, v in top_ops(first)],
        idle_gaps=[(k, v * 1e-9) for k, v in gaps[:10]],
        idle_by_host=by_host)


def step_periods(trace: Trace, steps: List) -> List[Tuple[object, float,
                                                               float]]:
    """Pair each step record of the traced window with its period on the
    trace's clock: from its ``bench.step`` span's start to the next one's
    (the window's end after the last), so the host work between two steps
    belongs to the step before.  The records and spans pair in order; a
    count that differs is an error."""
    spans = trace.step_spans()
    if len(spans) != len(steps):
        raise ValueError(f"{len(steps)} steps in the traced window but "
                         f"{len(spans)} {STEP_SPAN!r} spans")
    ends = [s[1] for s in spans[1:]] + [trace.window()[1]]
    return [(st, sp[1], end) for st, sp, end in zip(steps, spans, ends)]


@dataclasses.dataclass
class PhaseTime:
    """Device time over a set of step periods, on one device plane."""
    period_s: float
    busy_s: float
    family_s: Dict[str, float]


def over_periods(trace: Trace, periods: List[Tuple[float, float]],
                 families: Dict[str, List[str]]) -> PhaseTime:
    """Busy and per-family device time inside the periods, averaged over
    the devices traced."""
    planes = [sorted(v, key=lambda ev: ev[1])
              for v in trace.device_ops.values()]
    busy = 0.0
    fam = {f: 0.0 for f in families}
    for ops in planes:
        starts = [ev[1] for ev in ops]
        longest = max((ev[2] - ev[1] for ev in ops), default=0.0)
        for lo, hi in periods:
            near = ops[bisect.bisect_left(starts, lo - longest):
                       bisect.bisect_left(starts, hi)]
            inside = clip(near, lo, hi)
            busy += busy_ns(inside)
            for f, pats in families.items():
                fam[f] += family_ns(inside, pats)
    n = max(1, len(planes))
    return PhaseTime(period_s=sum(hi - lo for lo, hi in periods) * 1e-9,
                     busy_s=busy / n * 1e-9,
                     family_s={f: v / n * 1e-9 for f, v in fam.items()})
