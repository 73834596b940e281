#!/usr/bin/env python3
"""The program's own host spans in a profiler trace of one benchmark run,
reduced per scheduler tick: where the device idles inside a tick, and what
the host was doing then.

    python3 bench/run.py --workload <cell> --seed <n> --seconds 30 \\
        --trace 1 --keep-trace <dir>
    python3 bench/spans.py <dir>/<host>.xplane.pb

The serving code opens ``jax.profiler.TraceAnnotation`` spans named
``serve.*`` (``serve.tick``, ``serve.admit``, ``serve.prefill``,
``serve.decode`` with ``.prep``/``.launch``/``.sync``/``.finish``,
``serve.emit``) and names each jitted step after its factory, so the
``XLA Modules`` line of a device plane shows ``jit_paged_decode(...)``.
The benchmark's per-layer readers do not read either yet
(``bench/trace.py`` keeps the ``bench.*`` spans and the ``XLA Ops`` line
only); this tool reads them from a kept trace and prints one JSON object:

- ``ticks``: the traced window's ticks by phase.  A tick runs from its
  ``bench.step`` span's start to the next one's, as in
  ``trace.step_periods``; it is ``prefill`` if a ``serve.prefill`` starts
  in it, else ``decode`` if a ``serve.decode`` does, else ``idle``.
- per phase, medians over its ticks in ms: the period, device busy and
  idle time, ``decode_device_ms`` (the runs of ``jit_paged_decode`` or
  ``jit_mixed_decode`` that start in the tick), ``prep_idle_ms`` and
  ``post_idle_ms`` (the tick's idle time before and after the end of its
  last ``serve.decode.launch``; the two add up to the tick's idle time),
  and each span's host time (``span_ms``).
- ``idle_by_span``: the window's idle time, each instant given to the
  innermost ``bench.*`` or ``serve.*`` span around it (``host:untraced``
  where none is), in s; ``idle_gaps``: the ten longest gaps, each named by
  the innermost span around its midpoint (``trace.idle_gaps``).
- ``prefills_per_admit``: how many ``serve.admit`` spans held how many
  ``serve.prefill`` spans.
"""
from __future__ import annotations

import bisect
import json
import re
import statistics
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as T  # noqa: E402

PREFIX = "serve."
LAUNCH = "serve.decode.launch"
DECODE_PROGRAMS = ("paged_decode", "mixed_decode")
_PROGRAM = re.compile(r"jit_(\w+?)(?:\(|$)")

Span = Tuple[str, float, float]


def read_program(path: str) -> Tuple[List[Span], Dict[str, List[Span]]]:
    """The ``serve.*`` host spans (name cut before any ``#`` that TraceMe
    metadata adds) and each device plane's ``XLA Modules`` events, from an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    modules: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if T.DEVICE_PLANE.match(plane.name):
            runs = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name.split("#", 1)[0]
                    if name.startswith(PREFIX):
                        spans.append((name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return sorted(spans, key=lambda s: s[1]), modules


def program_name(event_name: str) -> str:
    """The jitted function's name of an ``XLA Modules`` event:
    ``jit_paged_decode(1234)`` -> ``paged_decode``; other names unchanged."""
    m = _PROGRAM.match(event_name)
    return m.group(1) if m else event_name


def program_ns(modules: Dict[str, List[Span]], lo: float, hi: float,
               names: Iterable[str]) -> float:
    """Device time of the runs of the named programs that start in
    ``[lo, hi)``, averaged over the device planes."""
    names = set(names)
    total = sum(e - s for runs in modules.values() for n, s, e in runs
                if lo <= s < hi and program_name(n) in names)
    return total / max(1, len(modules))


def idle_split(trace: T.Trace, spans: Sequence[Span],
               periods: Sequence[Tuple[float, float]],
               cut_at: str = LAUNCH) -> List[Optional[Tuple[float, float]]]:
    """Each period's device-idle time cut at the end of the last ``cut_at``
    span that starts inside it: ``(idle before, idle after)`` in s, busy
    time as ``trace.over_periods`` counts it, so the two add up to the
    period's idle time.  None for a period without such a span."""
    cuts = [(s, e) for n, s, e in spans if n == cut_at]
    starts = [s for s, _ in cuts]
    out: List[Optional[Tuple[float, float]]] = []
    for lo, hi in periods:
        inside = cuts[bisect.bisect_left(starts, lo):
                      bisect.bisect_left(starts, hi)]
        if not inside:
            out.append(None)
            continue
        cut = min(inside[-1][1], hi)
        parts = [T.over_periods(trace, [p], {}) for p in ((lo, cut),
                                                          (cut, hi))]
        out.append(tuple(p.period_s - p.busy_s for p in parts))
    return out


def ticks(trace: T.Trace, spans: Sequence[Span]
          ) -> List[Tuple[str, float, float]]:
    """``(phase, lo, hi)`` of each tick of the traced window."""
    steps = trace.step_spans()
    ends = [s[1] for s in steps[1:]] + [trace.window()[1]]
    out = []
    for st, hi in zip(steps, ends):
        lo = st[1]
        names = {n for n, s, _ in spans if lo <= s < hi}
        phase = ("prefill" if "serve.prefill" in names else
                 "decode" if "serve.decode" in names else "idle")
        out.append((phase, lo, hi))
    return out


def idle_by_span(ops: Sequence, lo: float, hi: float,
                 spans: Sequence[Span]) -> Dict[str, float]:
    """The idle time of ``[lo, hi)`` on one device plane, each instant
    given to the innermost (shortest) span around it, in ns."""
    idle, t = [], lo
    for s, e in T.merge(T.clip(ops, lo, hi)) + [(hi, hi)]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    spans = sorted((sp for sp in spans if sp[0] != T.WINDOW_SPAN),
                   key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((sp[2] - sp[1] for sp in spans), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for a, b in idle:
        near = [sp for sp in spans[bisect.bisect_left(starts, a - longest):
                                   bisect.bisect_left(starts, b)]
                if sp[2] > a]
        edges = sorted({a, b} | {x for sp in near for x in sp[1:3]
                                 if a < x < b})
        for x, y in zip(edges, edges[1:]):
            around = [sp for sp in near if sp[1] <= x and y <= sp[2]]
            name = (min(around, key=lambda sp: sp[2] - sp[1])[0]
                    if around else "host:untraced")
            out[name] += y - x
    return dict(out)


def _phase_summary(trace, spans, modules, periods) -> dict:
    busy = [T.over_periods(trace, [p], {}).busy_s * 1e3 for p in periods]
    period = [(hi - lo) * 1e-6 for lo, hi in periods]
    split = [s for s in idle_split(trace, spans, periods) if s is not None]
    dev = [program_ns(modules, lo, hi, DECODE_PROGRAMS) * 1e-6
           for lo, hi in periods]
    host: Dict[str, List[float]] = defaultdict(list)
    for lo, hi in periods:
        per: Dict[str, float] = defaultdict(float)
        for n, s, e in spans:
            if lo <= s < hi:
                per[n] += (e - s) * 1e-6
        for n, v in per.items():
            host[n].append(v)
    med = statistics.median
    return {
        "n": len(periods),
        "period_ms": med(period),
        "busy_ms": med(busy),
        "idle_ms": med(p - b for p, b in zip(period, busy)),
        "idle_share": 1.0 - sum(busy) / sum(period),
        "decode_device_ms": med(v for v in dev if v > 0)
        if any(v > 0 for v in dev) else None,
        "prep_idle_ms": med(a for a, _ in split) * 1e3 if split else None,
        "post_idle_ms": med(b for _, b in split) * 1e3 if split else None,
        "span_ms": {n: med(v) for n, v in sorted(host.items())},
    }


def summarize(trace: T.Trace, spans: Sequence[Span],
              modules: Dict[str, List[Span]]) -> dict:
    tk = ticks(trace, spans)
    lo, hi = trace.window()
    first = trace.device_ops[sorted(trace.device_ops)[0]]
    named = list(trace.host_spans) + list(spans)
    gaps = T.idle_gaps(T.clip(first, lo, hi), lo, hi, named)
    admits = [(s, e) for n, s, e in spans if n == "serve.admit"]
    prefills = [s for n, s, _ in spans if n == "serve.prefill"]
    per_admit = Counter(sum(1 for p in prefills if a <= p < b)
                        for a, b in admits)
    out = {"ticks": dict(Counter(p for p, _, _ in tk))}
    for phase in ("decode", "prefill"):
        periods = [(a, b) for p, a, b in tk if p == phase]
        if periods:
            out[phase] = _phase_summary(trace, spans, modules, periods)
    out["idle_by_span"] = {
        k: v * 1e-9 for k, v in sorted(
            idle_by_span(first, lo, hi, named).items(), key=lambda kv: -kv[1])}
    out["idle_gaps"] = [[k, v * 1e-9] for k, v in gaps[:10]]
    out["prefills_per_admit"] = {str(k): v
                                 for k, v in sorted(per_admit.items())}
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    spans, modules = read_program(args[0])
    print(json.dumps(summarize(T.from_xplane(args[0]), spans, modules)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
