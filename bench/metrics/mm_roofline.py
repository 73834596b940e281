"""mm_roofline: the mp-matmul kernels' share of their roofline in the
phase's traced ticks, in %.

Least time of the dense matmul work that those ticks did (useful rows
only, weights at the deepest format's bytes, bench/roofline.py) over the
device time, inside those ticks, of the kernel events that
bench/kernels/mp_matmul.json names."""
from bench import roofline

FAMILY = "mp_matmul"


def read(ctx, phase):
    dev = ctx.device(phase)
    if dev is None:
        return None
    kernel_s = dev.family_s.get(FAMILY, 0.0)
    if kernel_s <= 0:
        return None
    launches = []
    for st, _, _ in ctx.traced(phase):
        for n, mode in st.prefills:
            launches.append({"kind": "prefill", "rows": n, "modes": [mode]})
        if st.decodes:
            launches.append({"kind": "decode", "rows": len(st.decodes),
                             "modes": sorted({m for _, m in st.decodes})})
    if not launches:
        return None
    fam = ctx.kernels[FAMILY]
    least = roofline.WORK[fam["work"]](ctx.model, launches,
                                       fam["weight_bytes"], ctx.peak)
    return 100.0 * least / kernel_s
