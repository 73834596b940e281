"""The yardstick's arithmetic: peaks by device kind, byte widths by format,
useful rows only, and the deepest format of a mixed launch."""
import json
from pathlib import Path

import pytest

from bench import roofline
from bench.reference import dense

KERNELS = json.loads((Path(__file__).resolve().parents[1] / "kernels"
                      / "mp_matmul.json").read_text())
WB = KERNELS["weight_bytes"]
PEAK = roofline.peaks("TPU v5 lite")
PAPER = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
         "head_dim": 64, "d_ff": 3072, "vocab": 32000}


def test_peaks_are_keyed_by_device_kind():
    assert PEAK == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks("TPU v4")


def test_weight_bytes_by_format():
    # M8 streams one bf16 limb; M16 and deeper count the raw float32
    assert WB["M8"] == 2
    assert WB["M16"] == WB["M23"] == WB["M36"] == 4


def test_least_time_by_hand():
    # one decode row through a 768 x 3072 weight at M8: bound by bytes
    t = roofline.matmul_least_s(1, 768, 3072, 2, PEAK)
    assert t == pytest.approx((2 * 768 * 3072 + 2 * 768 + 4 * 3072) / 819e9)
    # 4096 rows: bound by FLOPs
    t = roofline.matmul_least_s(4096, 768, 3072, 4, PEAK)
    assert t == pytest.approx(2 * 4096 * 768 * 3072 / 197e12)


def test_padding_rows_count_no_work():
    assert roofline.matmul_least_s(0, 768, 3072, 4, PEAK) == 0.0
    # a decode launch of 17 useful rows costs the same whatever width the
    # program pads it to: only the useful rows are passed in
    one = roofline.dense_matmuls(
        PAPER, [{"kind": "decode", "rows": 17, "modes": ["M8"]}], WB, PEAK)
    assert one == pytest.approx(roofline.launch_least_s(PAPER, 17, 17, 2,
                                                        PEAK))


def test_mixed_launch_counts_the_deepest_format():
    mixed = roofline.dense_matmuls(
        PAPER, [{"kind": "decode", "rows": 20, "modes": ["M8", "M36"]}],
        WB, PEAK)
    m36 = roofline.launch_least_s(PAPER, 20, 20, 4, PEAK)
    m8 = roofline.launch_least_s(PAPER, 20, 20, 2, PEAK)
    assert mixed == pytest.approx(m36)
    assert m8 < m36


def test_prefill_counts_the_lm_head_once():
    t = roofline.dense_matmuls(
        PAPER, [{"kind": "prefill", "rows": 100, "modes": ["M23"]}], WB, PEAK)
    assert t == pytest.approx(roofline.launch_least_s(PAPER, 100, 1, 4, PEAK))


def test_model_flops():
    layer = 4 * 768 * 768 + 3 * 768 * 3072
    head = 768 * 32000
    assert dense.decode_flops(PAPER, 10) == pytest.approx(
        2 * (12 * layer + head) + 4 * 12 * 768 * 10)
    assert dense.prefill_flops(PAPER, 3) == pytest.approx(
        2 * 12 * layer * 3 + 4 * 12 * 768 * (1 + 2 + 3) + 2 * head)
