"""The yardstick's arithmetic: chip peaks and the least time of a matmul
(the model FLOPs of served tokens are the configuration's reference's:
``prefill_flops`` and ``decode_flops`` in ``bench/reference/<family>.py``).

Everything here is computed from shapes and the configuration file, never
from what the program reports, so a later change to the program cannot move
it.  The counts are of the work an operation needs, not of how a kernel
does it: one MXU pass per matmul, whatever limb cascade implements it.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

# Published peaks per chip, keyed by ``jax.Device.device_kind``.  A kind
# that is not here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}
PEAKS_SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                "16 GB HBM at 819 GB/s per chip")


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row for one device kind; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} ({PEAKS_SOURCE})") from None


def matmul_bytes(m: int, k: int, n: int, b: int) -> int:
    """Bytes one (m, k) @ (k, n) must move: weights and activations at
    ``b`` bytes an element, the f32 result at 4."""
    return b * k * n + b * m * k + 4 * m * n


def matmul_least_s(m: int, k: int, n: int, b: int,
                   peak: Dict[str, float]) -> float:
    """Least time of one matmul of ``m`` useful rows on a chip: the larger
    of its FLOPs over peak FLOP/s and its bytes over HBM bandwidth."""
    if m <= 0:
        return 0.0
    return max(2.0 * m * k * n / peak["bf16_flops_per_s"],
               matmul_bytes(m, k, n, b) / peak["hbm_bytes_per_s"])


def layer_matmuls(model: dict) -> List[Tuple[int, int]]:
    """(K, N) of each dense projection in one transformer layer: q, k, v,
    o, gate, up, down."""
    d, h, hk = model["d_model"], model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // h
    ff = model["d_ff"]
    return [(d, h * dh), (d, hk * dh), (d, hk * dh), (h * dh, d),
            (d, ff), (d, ff), (ff, d)]


def lm_head_matmul(model: dict) -> Tuple[int, int]:
    return (model["d_model"], model["vocab"])


def launch_least_s(model: dict, layer_rows: int, head_rows: int, b: int,
                   peak: Dict[str, float]) -> float:
    """Least time of the dense matmul work of one launch: every layer's
    projections over ``layer_rows`` useful rows and the lm_head over
    ``head_rows`` (one per prefill request, one per decoded token)."""
    per_layer = sum(matmul_least_s(layer_rows, k, n, b, peak)
                    for k, n in layer_matmuls(model))
    k, n = lm_head_matmul(model)
    return (model["n_layers"] * per_layer
            + matmul_least_s(head_rows, k, n, b, peak))


def dense_matmuls(model: dict, launches: Iterable[dict],
                  weight_bytes: Dict[str, int],
                  peak: Dict[str, float]) -> float:
    """Least time of the matmul work of a list of launches.

    A launch is ``{"kind": "prefill" | "decode", "rows": useful rows,
    "modes": formats of its rows}``.  A prefill launch does the layers over
    its prompt's rows and the lm_head over one row; a decode launch does
    both over one row per decoded token.  Weights count at the bytes of the
    deepest format in the launch."""
    total = 0.0
    for ln in launches:
        b = max(weight_bytes[m] for m in ln["modes"])
        head_rows = 1 if ln["kind"] == "prefill" else ln["rows"]
        total += launch_least_s(model, ln["rows"], head_rows, b, peak)
    return total


WORK = {"dense_matmuls": dense_matmuls}
