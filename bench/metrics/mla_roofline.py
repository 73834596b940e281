"""mla_roofline: the latent paged decode kernel's share of its roofline in
the phase's traced ticks, in %.

Least time of the latent attention that those ticks' decoded tokens did
over the device time, inside those ticks, of the kernel events that
bench/kernels/mla_attention.json names.  The least time of a tick is, per
layer, the larger of its FLOPs over the chip's bf16 peak and its bytes over
HBM bandwidth (``work``): every decoded token of context c reads c latent
entries of (kv_lora + rope) float32s and multiplies them with all H heads,
2·H·c·(kv_lora + rope) FLOPs for the logits and 2·H·c·kv_lora for the
context; its queries and its context move H·(2·kv_lora + rope) float32s
more.  Contexts come from ``StepRec.decodes``."""
from typing import Iterable, Tuple

FAMILY = "mla_attention"
F32 = 4


def work(model: dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's latent attention over a launch's
    decoded tokens of the given contexts."""
    mla = model["mla"]
    H, lora, dr = mla["n_heads"], mla["kv_lora"], mla["qk_rope_dim"]
    flops = nbytes = 0.0
    for c in contexts:
        flops += 2.0 * H * c * (2 * lora + dr)
        nbytes += F32 * (c * (lora + dr) + H * (2 * lora + dr))
    return flops, nbytes


def read(ctx, phase):
    dev = ctx.device(phase)
    if dev is None:
        return None
    kernel_s = dev.family_s.get(FAMILY, 0.0)
    if kernel_s <= 0:
        return None
    least = 0.0
    for st, _, _ in ctx.traced(phase):
        flops, nbytes = work(ctx.model, [c for c, _ in st.decodes])
        least += ctx.model["n_layers"] * max(
            flops / ctx.peak["bf16_flops_per_s"],
            nbytes / ctx.peak["hbm_bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / kernel_s
