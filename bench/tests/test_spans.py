"""The reduction of the program's ``serve.*`` spans and program runs per
tick (``bench/spans.py``), on a hand-made trace whose numbers are worked
out in the comments, on a trace the profiler records here, and on two
ticks recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

from bench import spans as S
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"

# window 0..400 ns; bench.step spans start at 0, 100, 200, 300.  Tick 1
# admits a request (prefill phase) and decodes, ticks 2 and 3 only decode,
# tick 4 admits nothing and decodes nothing.  Device busy: tick 1 10..30
# and 50..90; tick 2 110..160; tick 3 215..275 (two ops overlapping).
OPS = [("fusion.1", 10, 30, "fusion.1"), ("k.2", 50, 90, "k.2"),
       ("k.3", 110, 160, "k.3"), ("k.4", 215, 240, "k.4"),
       ("copy.5", 235, 275, "copy.5")]
BENCH = [("bench.traced", 0, 400), ("bench.step", 0, 95),
         ("bench.step", 100, 190), ("bench.step", 200, 290),
         ("bench.step", 300, 305)]
PROGRAM = [
    ("serve.tick", 1, 94), ("serve.admit", 2, 35), ("serve.prefill", 4, 34),
    ("serve.prefill.launch", 5, 8), ("serve.prefill.sync", 8, 33),
    ("serve.decode", 36, 93), ("serve.decode.prep", 37, 44),
    ("serve.decode.launch", 44, 48), ("serve.decode.sync", 48, 91),
    ("serve.decode.finish", 91, 92), ("serve.emit", 93, 94),
    ("serve.tick", 101, 189), ("serve.admit", 101, 102),
    ("serve.decode", 102, 185), ("serve.decode.prep", 102, 106),
    ("serve.decode.launch", 106, 108), ("serve.decode.sync", 108, 162),
    ("serve.decode.finish", 162, 170), ("serve.emit", 186, 188),
    ("serve.tick", 201, 289), ("serve.admit", 201, 202),
    ("serve.decode", 202, 280), ("serve.decode.prep", 202, 210),
    ("serve.decode.launch", 210, 214), ("serve.decode.sync", 214, 276),
    ("serve.decode.finish", 276, 279), ("serve.emit", 281, 285),
    ("serve.tick", 300, 304), ("serve.admit", 301, 303)]
MODULES = {"/device:TPU:0": [
    ("jit_paged_prefill(11)", 10, 30), ("jit_paged_decode(12)", 50, 90),
    ("jit_paged_decode(12)", 110, 160), ("jit_paged_decode(12)", 215, 275),
    ("jit_wrapped(13)", 295, 299)]}
HAND = T.Trace({"/device:TPU:0": OPS}, BENCH)
DECODE_TICKS = [(100, 200), (200, 300)]


def test_program_name_is_the_jitted_function_name():
    assert S.program_name("jit_paged_decode(1234)") == "paged_decode"
    assert S.program_name("jit_mixed_decode") == "mixed_decode"
    assert S.program_name("jit_wrapped(5)") == "wrapped"
    assert S.program_name("fusion.1") == "fusion.1"


def test_ticks_take_their_phase_from_the_program_spans():
    assert S.ticks(HAND, PROGRAM) == [
        ("prefill", 0, 100), ("decode", 100, 200), ("decode", 200, 300),
        ("idle", 300, 400)]


def test_decode_device_time_counts_the_decode_runs_that_start_in_a_tick():
    # tick 2: 110..160; tick 3: 215..275; tick 1 holds a prefill run too
    assert [S.program_ns(MODULES, lo, hi, S.DECODE_PROGRAMS)
            for lo, hi in [(0, 100)] + DECODE_TICKS] == [40, 50, 60]
    # the steps of a program that names them all `wrapped` read nothing
    assert S.program_ns(MODULES, 290, 300, S.DECODE_PROGRAMS) == 0


def test_idle_is_split_at_the_end_of_the_decode_launch():
    # tick 1: cut at 48; idle 0..48 is 48 - 20 = 28, 48..100 is 52 - 40
    # tick 2: cut at 108; 8 before, 92 - 50 = 42 after
    # tick 3: cut at 214; 14 before, 86 - 60 = 26 after
    # tick 4 has no launch
    split = S.idle_split(HAND, PROGRAM, [(0, 100)] + DECODE_TICKS
                         + [(300, 400)])
    assert split[:3] == [pytest.approx((28e-9, 12e-9)),
                         pytest.approx((8e-9, 42e-9)),
                         pytest.approx((14e-9, 26e-9))]
    assert split[3] is None


def test_prep_plus_post_is_the_ticks_idle_time():
    periods = [(0, 100)] + DECODE_TICKS
    for (lo, hi), (prep, post) in zip(periods,
                                      S.idle_split(HAND, PROGRAM, periods)):
        ph = T.over_periods(HAND, [(lo, hi)], {})
        assert prep + post == pytest.approx(ph.period_s - ph.busy_s)


def test_idle_time_goes_to_the_innermost_span_at_each_instant():
    got = S.idle_by_span(OPS, 0, 400, BENCH + PROGRAM)
    # idle 0..10, 30..50, 90..110, 160..215, 275..400
    assert sum(got.values()) == pytest.approx(230)
    assert got["serve.decode.prep"] == pytest.approx(7 + 4 + 8)
    assert got["serve.decode.launch"] == pytest.approx(4 + 2 + 4)
    # 48..50, 90..91, 108..110, 160..162, 214..215, 275..276
    assert got["serve.decode.sync"] == pytest.approx(9)
    assert got["serve.prefill.sync"] == pytest.approx(2 + 3)
    # between the ticks' spans, and after the last one
    assert got["host:untraced"] == pytest.approx(5 + 10 + 10 + 95)


def test_summary_of_the_hand_trace():
    s = S.summarize(HAND, PROGRAM, MODULES)
    assert s["ticks"] == {"prefill": 1, "decode": 2, "idle": 1}
    d = s["decode"]
    assert d["n"] == 2
    assert d["period_ms"] == pytest.approx(100e-6)
    assert d["busy_ms"] == pytest.approx(55e-6)        # of 50 and 60
    assert d["idle_share"] == pytest.approx(90 / 200)
    assert d["decode_device_ms"] == pytest.approx(55e-6)
    assert d["prep_idle_ms"] == pytest.approx(11e-6)   # of 8 and 14
    assert d["post_idle_ms"] == pytest.approx(34e-6)   # of 42 and 26
    assert d["span_ms"]["serve.decode.launch"] == pytest.approx(3e-6)
    assert s["prefills_per_admit"] == {"0": 3, "1": 1}
    assert sum(s["idle_by_span"].values()) == pytest.approx(230e-9)


def test_program_spans_are_read_from_a_recorded_trace(tmp_path):
    """What the profiler writes here: span names without their
    arguments, and no device plane (so no program runs)."""
    import jax
    from jax.profiler import TraceAnnotation

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("serve.tick", tick=3, active=2, queued=0):
            with TraceAnnotation("serve.decode.launch"):
                pass
        with TraceAnnotation("bench.step"):
            pass
    spans, modules = S.read_program(
        str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    assert [n for n, _, _ in spans] == ["serve.tick", "serve.decode.launch"]
    assert spans[0][1] <= spans[1][1] and spans[1][2] <= spans[0][2]
    assert modules == {}


def _chip_trace():
    """Two consecutive decode-only ticks of ``glm3l-m23-closed`` as the
    profiler recorded them on a TPU v5 lite, with the program's spans and
    the ``XLA Modules`` line (times in ns from the first tick's start)."""
    with gzip.open(DATA / "chip_trace_2ticks_spans.json.gz", "rt") as f:
        d = json.load(f)
    lo, hi = d["window"]
    dev = {k: [(n, s, e, n) for n, s, e in v]
           for k, v in d["device_ops"].items()}
    tr = T.Trace(dev, [("bench.traced", lo, hi)]
                 + [tuple(s) for s in d["host_spans"]])
    spans = [tuple(s) for s in d["program_spans"]]
    modules = {k: [tuple(m) for m in v] for k, v in d["modules"].items()}
    return tr, spans, modules


def test_chip_trace_reduces_to_the_counted_numbers():
    """Expected numbers counted apart from ``bench/``, one boolean per ns
    of the 73,319,780 ns window: the device is busy 32,124,953 ns of the
    first tick (0 .. 36,955,340) and 32,091,154 of the second; the first
    tick's last ``serve.decode.launch`` ends at 2,792,600 ns, before which
    the device idles 1,804,034 ns and after which 3,026,353 (1,213,253 and
    3,060,033 in the second, cut at 39,190,470); the decode program runs
    32,056,453 and 32,022,136 ns; the longest idle gap, 4,218,223 ns from
    the first tick's last op, has its midpoint in ``serve.decode.sync``;
    each idle instant given to its innermost span, the read-back
    (``serve.decode.sync``) holds 5,597,237 of the 9,103,673 idle ns."""
    tr, spans, modules = _chip_trace()
    assert [p for p, _, _ in S.ticks(tr, spans)] == ["decode", "decode"]
    periods = [(lo, hi) for _, lo, hi in S.ticks(tr, spans)]
    assert periods == [(0, 36_955_340), (36_955_340, 73_319_780)]
    assert [T.over_periods(tr, [p], {}).busy_s for p in periods] == \
        pytest.approx([32_124_953e-9, 32_091_154e-9])
    assert S.idle_split(tr, spans, periods) == [
        pytest.approx((1_804_034e-9, 3_026_353e-9)),
        pytest.approx((1_213_253e-9, 3_060_033e-9))]
    assert [S.program_ns(modules, lo, hi, S.DECODE_PROGRAMS)
            for lo, hi in periods] == [32_056_453, 32_022_136]
    lo, hi = tr.window()
    ops = tr.device_ops["/device:TPU:0"]
    gaps = T.idle_gaps(ops, lo, hi, tr.host_spans + spans)
    assert gaps[0] == ("serve.decode.sync", pytest.approx(4_218_223))
    idle = S.idle_by_span(ops, lo, hi, tr.host_spans + spans)
    assert sum(idle.values()) == pytest.approx(9_103_673)
    assert idle["serve.decode.sync"] == pytest.approx(5_597_237)
    assert idle["serve.decode.launch"] == pytest.approx(1_022_857)


def test_chip_trace_names_the_steps_and_kernels():
    """On the chip the programs carry their factories' names, and the
    mp-matmul kernels their ``pallas_call`` name as the op's HLO name,
    which the benchmark's shape pattern still matches: 22 a tick (seven
    projections in each of three layers, and the lm_head)."""
    tr, _, modules = _chip_trace()
    names = {S.program_name(n) for runs in modules.values()
             for n, _, _ in runs}
    assert "paged_decode" in names and "wrapped" not in names
    pats = json.loads((DATA.parent.parent / "kernels"
                       / "mp_matmul.json").read_text())["patterns"]
    hits = [op for op in tr.device_ops["/device:TPU:0"]
            if T.family_ns([op], pats)]
    assert len(hits) == 44
    assert all(op[0].startswith("%mp_matmul_prelimbed.") for op in hits)
