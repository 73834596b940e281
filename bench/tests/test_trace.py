"""The trace reduction: busy time as the union of device-op intervals,
kernel time by name pattern, and idle gaps named by the host span around
them.  Expected numbers are worked out by hand in the comments."""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"

# window 0..100 ns; ops overlap on 10..30; idle gaps 40..50 and 90..100
HAND = T.Trace(
    device_ops={"/device:TPU:0": [
        ("fusion.1", 0, 20, "fusion.1"),
        ("k.2", 10, 30, "k.2 kernel_name=_prelimbed_kernel"),
        ("copy.3", 30, 40, "copy.3"),
        ("k.4", 50, 90, "k.4 kernel_name=_mixed_prelimbed_kernel"),
        ("fusion.5", 95, 120, "fusion.5"),        # clipped to 95..100
    ]},
    host_spans=[("bench.traced", 0, 100), ("bench.step", 0, 45),
                ("bench.wait", 45, 52), ("bench.step", 52, 92)])


def test_busy_is_the_union_of_intervals():
    s = T.summarize(HAND, {})
    # [0, 40] + [50, 90] + [95, 100] = 40 + 40 + 5 = 85 ns of 100
    assert s.busy_s == pytest.approx(85e-9)
    assert s.window_s == pytest.approx(100e-9)


def test_family_time_counts_each_matching_event_once():
    s = T.summarize(HAND, {"mp_matmul": ["_prelimbed_kernel",
                                         "_mixed_prelimbed_kernel"]})
    # k.2 (20 ns) + k.4 (40 ns); overlap with fusion.1 does not matter
    assert s.family_s["mp_matmul"] == pytest.approx(60e-9)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    s = T.summarize(HAND, {})
    # 40..50 (midpoint 45: bench.wait starts at 45, the innermost span)
    # and 90..95 (midpoint 92.5: past bench.step's end at 92 -> untraced)
    assert s.idle_gaps == [("bench.wait", pytest.approx(10e-9)),
                           ("host:untraced", pytest.approx(5e-9))]


def test_top_ops_fold_numeric_suffixes():
    s = T.summarize(HAND, {})
    # fusion: 20 + 5 (clipped) = 25; k: 20 + 40 = 60; copy: 10
    assert s.device_ops == [("k", pytest.approx(60e-9)),
                            ("fusion", pytest.approx(25e-9)),
                            ("copy", pytest.approx(10e-9))]


def test_a_window_without_device_ops_is_an_error():
    empty = T.Trace({"/device:TPU:0": [("x", 200, 300)]},
                    [("bench.traced", 0, 100)])
    with pytest.raises(ValueError, match="no device operation"):
        T.summarize(empty, {})


MP_MATMUL_KERNELS = {"_fused_kernel", "_fused_multi_kernel",
                     "_prelimbed_kernel", "_mixed_prelimbed_kernel",
                     "_both_prelimbed_kernel"}


def _kernel_ops():
    rows = [line.split("\t") for line in
            (DATA / "kernel_ops.txt").read_text().splitlines()
            if line and not line.startswith("#")]
    return [pytest.param(kernel, text, id=f"{src}-{kernel}-{i}")
            for i, (src, kernel, text) in enumerate(rows)]


@pytest.mark.parametrize("kernel,text", _kernel_ops())
def test_kernel_family_is_read_from_the_op_shapes(kernel, text):
    """Without the Mosaic body (the profiler on the chip names an op by its
    HLO text alone), the mp-matmul family is told by its shapes: the
    matmul kernels match, the flash, paged and limb-splitting kernels do
    not."""
    import json

    pats = json.loads((DATA.parent.parent / "kernels"
                       / "mp_matmul.json").read_text())["patterns"]
    assert T.family_ns([("op", 0, 7, text)], pats) == \
        (7 if kernel in MP_MATMUL_KERNELS else 0)


def test_op_key_names_a_custom_call_by_its_operands():
    chip = dict((k, t) for _, k, t in (
        line.split("\t") for line in
        (DATA / "kernel_ops.txt").read_text().splitlines()
        if line.startswith("chip\t")))
    assert T.op_key(chip["_prelimbed_kernel"]) == \
        "checkpoint {f32[32,13824], bf16[3,13824,4096]}"
    assert T.op_key("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), "
                    "kind=kLoop") == "fusion"


def _chip_trace():
    """Two consecutive ticks of ``glm3l-m23-closed`` as the profiler
    recorded them on a TPU v5 lite: 608 device ops named by their HLO
    text, and the two ``bench.step`` spans (times in ns from the first)."""
    import gzip
    import json

    with gzip.open(DATA / "chip_trace_2ticks.json.gz", "rt") as f:
        d = json.load(f)
    lo, hi = d["window"]
    dev = {k: [(n, s, e, n) for n, s, e in v]
           for k, v in d["device_ops"].items()}
    spans = [("bench.traced", lo, hi)] + [tuple(s) for s in d["host_spans"]]
    return T.Trace(dev, spans)


def test_chip_trace_reduces_to_the_counted_numbers():
    """Expected numbers counted apart from ``bench/trace.py``, by one sweep
    over the ops' start and end points: the device is busy 64,445,144 ns
    of the 72,893,458 ns window, 32,094,161 in the first tick and
    32,350,983 in the second; the mp-matmul kernels (22 a tick: seven
    projections in each of three layers, and the lm_head) take 7,408,425
    and 7,431,004 ns; the longest idle gap, 4,104,641 ns, lies between
    the two ticks' device work, inside the first tick's span."""
    import json

    pats = json.loads((DATA.parent.parent / "kernels"
                       / "mp_matmul.json").read_text())["patterns"]
    fam = {"mp_matmul": pats}
    tr = _chip_trace()
    s = T.summarize(tr, fam)
    assert s.window_s == pytest.approx(72_893_458e-9)
    assert s.busy_s == pytest.approx(64_445_144e-9)
    assert s.family_s["mp_matmul"] == pytest.approx(14_839_429e-9)
    assert s.idle_gaps[0] == ("bench.step", pytest.approx(4_104_641e-9))
    assert sum(s.idle_by_host.values()) == \
        pytest.approx((72_893_458 - 64_445_144) * 1e-9)
    ticks = [T.over_periods(tr, [(lo, hi)], fam)
             for _, lo, hi in T.step_periods(tr, ["tick 1", "tick 2"])]
    assert [t.busy_s for t in ticks] == \
        pytest.approx([32_094_161e-9, 32_350_983e-9])
    assert [t.family_s["mp_matmul"] for t in ticks] == \
        pytest.approx([7_408_425e-9, 7_431_004e-9])
    ops = tr.device_ops["/device:TPU:0"]
    assert sum(1 for op in ops if T.family_ns([op], pats)) == 44
