"""The per-layer readers on hand-made step records and a hand-made trace:
each reads the ticks of its phase only."""
import pytest

import bench.run as R
from bench import roofline
from bench import trace as T
from bench.driver import StepRec
from bench.reference import dense

GLM = {"n_layers": 3, "d_model": 4096, "n_heads": 32, "n_kv_heads": 2,
       "head_dim": 128, "d_ff": 13696, "vocab": 65024}
PEAK = roofline.peaks("TPU v5 lite")
# a tick that admits a request (prefill phase), two that only decode, and
# one that does nothing
STEPS = [StepRec(0.000, 0.020, True, prefills=[(100, "M23")],
                 decodes=[(101, "M23"), (50, "M23")]),
         StepRec(0.020, 0.030, True, decodes=[(102, "M23"), (51, "M23")]),
         StepRec(0.030, 0.042, True, decodes=[(103, "M23"), (52, "M23")]),
         StepRec(0.042, 0.043, False)]
# the same four ticks on the trace's clock (ns): step spans start at 0,
# 100, 200, 300; the window ends at 400.  Device ops: the prefill tick is
# busy 0..90 (kernel 10..60); the first decode tick 100..150 (kernel
# 110..140); the second 200..280 (kernel 200..260, plus a copy 250..270
# that overlaps it); nothing in the last.
TRACE = T.Trace(
    device_ops={"/device:TPU:0": [
        ("fusion.1", 0, 90, "fusion.1"),
        ("k.2", 10, 60, "k.2 _prelimbed_kernel"),
        ("k.3", 110, 140, "k.3 _prelimbed_kernel"),
        ("fusion.4", 100, 150, "fusion.4"),
        ("k.5", 200, 260, "k.5 _prelimbed_kernel"),
        ("copy.6", 250, 280, "copy.6")]},
    host_spans=[("bench.traced", 0, 400), ("bench.step", 0, 95),
                ("bench.step", 100, 190), ("bench.step", 200, 290),
                ("bench.step", 300, 305)])
KERNELS = {"mp_matmul": dict(R.kernel_families()["mp_matmul"],
                             patterns=["_prelimbed_kernel"])}


def ctx(traced=True):
    return R.ReadContext(
        model=GLM, peak=PEAK, window_steps=STEPS, reference=dense,
        trace=TRACE if traced else None,
        periods=T.step_periods(TRACE, STEPS) if traced else None,
        kernels=KERNELS)


def read(name, traced=True):
    return R._load_reader(name)(ctx(traced), name.split(".", 1)[1])


def test_steps_have_a_phase():
    assert [s.phase for s in STEPS] == ["prefill", "decode", "decode",
                                        "idle"]


def test_tick_ms_is_the_median_tick_of_the_phase():
    assert read("tick_ms.decode") == pytest.approx(11.0)   # of 10, 12 ms
    assert read("tick_ms.prefill") == pytest.approx(20.0)


def test_step_mfu_counts_the_phase_flops_over_its_ticks():
    flops = sum(dense.decode_flops(GLM, c) for c in (102, 51, 103, 52))
    assert read("step_mfu.decode") == pytest.approx(
        100 * flops / (0.022 * 197e12))
    flops = (dense.prefill_flops(GLM, 100)
             + dense.decode_flops(GLM, 101)
             + dense.decode_flops(GLM, 50))
    assert read("step_mfu.prefill") == pytest.approx(
        100 * flops / (0.020 * 197e12))


def test_step_mfu_reads_the_same_as_before_the_reference_counted():
    """The values the reader gave when the FLOPs lived in
    bench/roofline.py, on the same context, to the last digit."""
    assert read("step_mfu.decode") == 0.16245034646977385
    assert read("step_mfu.prefill") == 3.2149711918781727
    assert dense.decode_flops(GLM, 1000) == 1805516800.0
    assert dense.prefill_flops(GLM, 256) == 315413757952.0


def test_device_idle_reads_the_phase_ticks_periods():
    # decode ticks: periods 100..200 and 200..300; busy 50 + 80 = 130 ns
    assert read("device_idle.decode") == pytest.approx(35.0)
    # prefill tick: period 0..100, busy 90 ns
    assert read("device_idle.prefill") == pytest.approx(10.0)


def test_mm_roofline_reads_the_phase_launches_over_its_kernel_time():
    wb = KERNELS["mp_matmul"]["weight_bytes"]
    least = roofline.dense_matmuls(GLM, [
        {"kind": "decode", "rows": 2, "modes": ["M23"]},
        {"kind": "decode", "rows": 2, "modes": ["M23"]}], wb, PEAK)
    # kernel time inside the decode ticks: 30 + 60 = 90 ns
    assert read("mm_roofline.decode") == pytest.approx(100 * least / 90e-9)


def test_readers_without_a_trace_return_nothing():
    assert read("device_idle.decode", traced=False) is None
    assert read("mm_roofline.decode", traced=False) is None


def test_steps_and_spans_must_pair():
    with pytest.raises(ValueError, match="steps in the traced window"):
        T.step_periods(TRACE, STEPS[:3])


def test_first_token_p95_is_the_linear_percentile_of_the_window():
    c = R.ReadContext(model=GLM, peak=PEAK, window_steps=STEPS,
                      reference=dense, ttft=[float(v) for v in range(10, 210, 10)])
    # 20 values 10..200: position 0.95 * 19 = 18.05 -> 190 + 0.05 * 10
    assert R._load_reader("first_token_p95_ms")(c, "") == \
        pytest.approx(190.5)
    c.ttft = []
    assert R._load_reader("first_token_p95_ms")(c, "") is None
