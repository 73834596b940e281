"""expert_roofline: the held routed experts' matmul kernels' share of their
roofline in the phase's traced ticks, in %.

Least time of the routed expert work that those ticks' decode launches did
over the device time, inside those ticks, of the kernel events that
bench/kernels/moe_expert.json names.  Per MoE layer and held expert, a
launch of R decoded tokens routes it R · top_k / n_experts rows, its
expected share; its three matmuls (gate and up d_model x expert_ff, down
expert_ff x d_model) each take the larger of their FLOPs (2·m·K·N) over the
chip's bf16 peak and their bytes over HBM bandwidth: the expert's weights
at the family's ``weight_bytes`` of the launch's deepest format, the rows
in at that width, the f32 result out (``work``)."""
from typing import List, Tuple

FAMILY = "moe_expert"


def work(model: dict, rows: int, b: int) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each held routed-expert matmul of a decode launch
    of ``rows`` tokens, weights and rows at ``b`` bytes an element."""
    d, moe = model["d_model"], model["moe"]
    ff = moe["expert_ff"]
    m = rows * moe["top_k"] / moe["n_experts"]
    shapes = [(d, ff), (d, ff), (ff, d)]
    per = [(2.0 * m * k * n, b * k * n + b * m * k + 4.0 * m * n)
           for k, n in shapes]
    held = moe["n_held"] or moe["n_experts"]
    return per * held * (model["n_layers"] - model["first_k_dense"])


def read(ctx, phase):
    dev = ctx.device(phase)
    if dev is None:
        return None
    kernel_s = dev.family_s.get(FAMILY, 0.0)
    if kernel_s <= 0:
        return None
    wbytes = ctx.kernels[FAMILY]["weight_bytes"]
    least = 0.0
    for st, _, _ in ctx.traced(phase):
        if not st.decodes:
            continue
        b = max(wbytes[m] for _, m in st.decodes)
        least += sum(max(f / ctx.peak["bf16_flops_per_s"],
                         n / ctx.peak["hbm_bytes_per_s"])
                     for f, n in work(ctx.model, len(st.decodes), b))
    if least <= 0:
        return None
    return 100.0 * least / kernel_s
