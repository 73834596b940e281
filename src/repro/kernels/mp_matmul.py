"""Pallas TPU kernel: fused multi-precision limb matmul.

This is the performance-critical realization of the paper's reconfigurable
multiplier (DESIGN.md §4; limb algebra in §2).  One kernel invocation performs *all* selected limb
products for a (bm×bn) output tile while the A/B tiles sit in VMEM:

    HBM traffic  = read A once + read B once + write C once   (mode-independent)
    MXU passes   = n_products(mode)                            (mode-dependent)

versus the naive realization (n_products separate XLA matmuls over
pre-materialized limb arrays) which pays ``n_limbs×`` the HBM reads plus limb
materialization round-trips.  The fusion is the beyond-paper optimization that
makes low modes *memory*-cheap, not just FLOP-cheap (EXPERIMENTS.md §Perf).

Layout/tiling rationale (TPU v5e):
  * block sizes are multiples of (8, 128) fp32 tiles; MXU dims multiple of 128;
  * the K grid axis is innermost and sequential ("arbitrary"), M/N parallel;
  * per-order fp32 accumulators live in VMEM scratch across K steps — the
    carry-save-adder analogue (no per-pass HBM round trip, no per-pass
    re-rounding across orders);
  * on-the-fly limb extraction is VPU elementwise work fused ahead of the MXU
    passes — the paper's "truncate before multiply" costs zero extra HBM bytes.

VMEM budget per grid step (defaults bm=bn=256, bk=512, mode M23):
    A tile f32 512KB + B tile f32 512KB + limbs bf16 3*(256KB+256KB)
    + acc 3*256KB ≈ 3.3 MB  « 16 MB/core.

Variants (DESIGN.md §4 table): single-output fused (training matmuls),
multi-output fused (`_fused_multi_kernel`: ONE A tile + limb cascade shared
across n_out stacked B operands, epilogue lattice in the flush — QKV/SwiGLU
projection groups), pre-limbed B (serving decode), both pre-limbed (DD).
``vmem_bytes`` models each variant's true footprint for the autotuner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FormatLike, MPFormat, resolve
from repro.kernels import ref as ref_backend

# grid (Mi, Nj, Kk): output tiles are independent, K steps accumulate into
# the VMEM scratch and must run in order
MATMUL_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _compiler_params(semantics) -> pltpu.CompilerParams:
    """Mosaic parameters with one dimension semantic per grid axis:
    "arbitrary" for an axis a scratch accumulator carries across, else
    "parallel".  Mosaic refuses a tuple whose length is not the grid's."""
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def _extract_limbs(x: jax.Array, n_limbs: int) -> list[jax.Array]:
    """On-the-fly limb cascade (VPU): f32 tile -> n_limbs bf16 tiles.

    Mosaic keeps the f32->bf16->f32 convert pair, so kernels need none of
    ``limbs.round_to_bf16``'s bit work, which only XLA-side cascades pay
    for (XLA on TPU may drop the pair)."""
    limbs = []
    r = x
    for i in range(n_limbs):
        li = r.astype(jnp.bfloat16)
        limbs.append(li)
        if i + 1 < n_limbs:
            r = r - li.astype(jnp.float32)
    return limbs


def _combine_orders(acc_ref, n_orders: int, *, base=()) -> jax.Array:
    """Neumaier-compensated combine, smallest order-magnitude first.

    ``base`` prefixes the ref index — the multi-output kernel combines
    ``acc_ref[t, o]`` per output slot ``t`` with the same compensation."""
    if n_orders == 1:
        return acc_ref[base + (0,)]
    s = acc_ref[base + (n_orders - 1,)]
    c = jnp.zeros_like(s)
    for o in range(n_orders - 2, -1, -1):
        t = acc_ref[base + (o,)]
        tmp = s + t
        c = c + jnp.where(jnp.abs(s) >= jnp.abs(t), (s - tmp) + t, (t - tmp) + s)
        s = tmp
    return s + c


def _fused_kernel(a_ref, b_ref, o_ref, acc_ref, *, spec: MPFormat, out_dtype):
    """Grid (Mi, Nj, Kk); A block (bm,bk) f32; B block (bk,bn) f32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    al = _extract_limbs(a, spec.n_limbs)
    bl = _extract_limbs(b, spec.n_limbs)

    # group kept products by order so each order's partial sum stays separate
    for o in range(spec.max_order + 1):
        terms = [
            jnp.dot(al[i], bl[j], preferred_element_type=jnp.float32)
            for (i, j) in spec.products
            if i + j == o
        ]
        if not terms:
            continue
        tot = terms[0]
        for t in terms[1:]:
            tot = tot + t
        acc_ref[o] += tot

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _combine_orders(acc_ref, spec.max_order + 1).astype(out_dtype)


def _prelimbed_kernel(a_ref, bl_ref, o_ref, acc_ref, *, spec: MPFormat, out_dtype):
    """B pre-decomposed to (L, bk, bn) bf16 (static weights: serving path)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    al = _extract_limbs(a, spec.n_limbs)

    for o in range(spec.max_order + 1):
        terms = [
            jnp.dot(al[i], bl_ref[j], preferred_element_type=jnp.float32)
            for (i, j) in spec.products
            if i + j == o
        ]
        if not terms:
            continue
        tot = terms[0]
        for t in terms[1:]:
            tot = tot + t
        acc_ref[o] += tot

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _combine_orders(acc_ref, spec.max_order + 1).astype(out_dtype)


def _mixed_prelimbed_kernel(a_ref, bl_ref, ln_ref, lo_ref, o_ref, acc_ref, *,
                            env: MPFormat, out_dtype):
    """Partitioned-lane prelimbed matmul: the ``_prelimbed_kernel`` cascade
    run at the batch-max (envelope) depth with per-ROW lane masking.

    ``ln_ref``/``lo_ref`` carry each output row's limb count and order cut
    (lane-broadcast int32 blocks riding the M tiling); a row at ``k`` limbs
    masks the limb products outside its own format to exact +0.0 via the
    shared :func:`repro.kernels.ref.lane_keep` predicate — the masked rows
    skip nothing on the MXU, but the whole mixed micro-batch runs in ONE
    launch instead of one per format bucket.  The per-order accumulators
    and the compensated flush are unchanged, so a lane's result matches its
    homogeneous launch bit-for-bit modulo −0 → +0 flips (leading all-zero
    orders are exact no-ops in ``_combine_orders``)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    al = _extract_limbs(a, env.n_limbs)
    lane_n = ln_ref[:, :1]    # (bm, 1): broadcasts over the (bm, bn) tile
    lane_ord = lo_ref[:, :1]

    for o in range(env.max_order + 1):
        terms = []
        for (i, j) in env.products:
            if i + j != o:
                continue
            p = jnp.dot(al[i], bl_ref[j], preferred_element_type=jnp.float32)
            keep = ref_backend.lane_keep(i, j, lane_n, lane_ord)
            terms.append(jnp.where(keep, p, 0.0))
        if not terms:
            continue
        tot = terms[0]
        for t in terms[1:]:
            tot = tot + t
        acc_ref[o] += tot

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _combine_orders(acc_ref, env.max_order + 1).astype(out_dtype)


def _both_prelimbed_kernel(al_ref, bl_ref, o_ref, acc_ref, *, spec: MPFormat,
                           out_dtype):
    """Both operands pre-decomposed (DD / >fp32 inputs, modes 5-6)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for o in range(spec.max_order + 1):
        terms = [
            jnp.dot(al_ref[i], bl_ref[j], preferred_element_type=jnp.float32)
            for (i, j) in spec.products
            if i + j == o
        ]
        if not terms:
            continue
        tot = terms[0]
        for t in terms[1:]:
            tot = tot + t
        acc_ref[o] += tot

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _combine_orders(acc_ref, spec.max_order + 1).astype(out_dtype)


# ---------------------------------------------------------------------------
# Multi-output fused projection kernel: one A tile, n_out stacked B operands.
# ---------------------------------------------------------------------------
def epilogue_desc(gate: str = "none", has_bias: bool = False,
                  has_residual: bool = False) -> str:
    """Canonical descriptor of one point on the epilogue lattice — the string
    that keys autotune tables and the VMEM model ("none", "bias",
    "swiglu+bias+res", ...)."""
    parts = []
    if gate != "none":
        parts.append(gate)
    if has_bias:
        parts.append("bias")
    if has_residual:
        parts.append("res")
    return "+".join(parts) if parts else "none"


def _fused_multi_kernel(*refs, spec: MPFormat, out_dtype, n_out: int,
                        gate: str, has_bias: bool, has_residual: bool):
    """Grid (Mi, Nj, Kk); A block (bm,bk) f32; n_out B blocks (bk,bn) f32.

    The A tile is read and limb-decomposed ONCE per grid step and its limbs
    feed every output's MXU passes — the operand-sharing optimization that
    cuts a projection group's A-side HBM traffic and VPU limb cascades from
    ``n_out×`` to ``1×``.  Each B operand is its own pallas input (no host-
    side stack: weights stream from their parameter buffers untouched).  The
    epilogue lattice (bias add, silu-gate combine, residual add) runs in the
    flush, before the single HBM write, so fused MLP intermediates never
    materialize in HBM.
    """
    a_ref = refs[0]
    b_refs = refs[1:1 + n_out]
    idx = 1 + n_out
    bias_refs = refs[idx:idx + n_out] if has_bias else ()
    idx += n_out if has_bias else 0
    res_ref = refs[idx] if has_residual else None
    o_ref, acc_ref = refs[-2], refs[-1]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    al = _extract_limbs(a, spec.n_limbs)  # ONCE, shared by all outputs

    for t, b_ref in enumerate(b_refs):
        bl = _extract_limbs(b_ref[...].astype(jnp.float32), spec.n_limbs)
        for o in range(spec.max_order + 1):
            terms = [
                jnp.dot(al[i], bl[j], preferred_element_type=jnp.float32)
                for (i, j) in spec.products
                if i + j == o
            ]
            if not terms:
                continue
            tot = terms[0]
            for tm in terms[1:]:
                tot = tot + tm
            acc_ref[t, o] += tot

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        outs = []
        for t in range(n_out):
            y = _combine_orders(acc_ref, spec.max_order + 1, base=(t,))
            if has_bias:
                y = y + bias_refs[t][...]  # (1, bn) broadcasts over bm
            outs.append(y)
        if gate == "swiglu":
            y = jax.nn.silu(outs[0]) * outs[1]
            if has_residual:
                y = y + res_ref[...]
            o_ref[...] = y.astype(out_dtype)
        else:
            if has_residual:  # only reachable with n_out == 1
                outs[0] = outs[0] + res_ref[...]
            for t in range(n_out):
                o_ref[t] = outs[t].astype(out_dtype)


def build_fused_multi_call(
    M: int, K: int, N: int,
    n_out: int,
    mode: FormatLike,
    *,
    bm: int, bk: int, bn: int,
    gate: str = "none",
    has_bias: bool = False,
    has_residual: bool = False,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """pallas_call for the multi-output fused projection kernel.

    Inputs (padded shapes): A (M, K) f32; n_out SEPARATE B operands (K, N)
    f32 — each streams from its own parameter buffer, no host-side stack
    copy; optionally n_out biases (1, N) f32 and a residual (M, N) f32.
    Output is (n_out, M, N), or (M, N) when ``gate`` combines the stack to
    one array.  ``gate="swiglu"`` requires n_out == 2 (silu(out0) * out1); a
    residual add needs a single final output (gated, or n_out == 1).
    """
    s = resolve(mode)
    n_orders = s.max_order + 1
    if gate == "swiglu" and n_out != 2:
        raise ValueError(f"swiglu gate needs n_out == 2, got {n_out}")
    if gate not in ("none", "swiglu"):
        raise ValueError(f"unknown gate {gate!r}")
    single_out = gate != "none" or n_out == 1
    if has_residual and not single_out:
        raise ValueError("residual epilogue needs a single final output")
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
    in_specs += [pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
                 for _ in range(n_out)]
    if has_bias:
        in_specs += [pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
                     for _ in range(n_out)]
    if has_residual:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
    if single_out and gate != "none":
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)
    else:
        out_spec = pl.BlockSpec((n_out, bm, bn), lambda i, j, k: (0, i, j))
        out_shape = jax.ShapeDtypeStruct((n_out, M, N), out_dtype)
    cost = pl.CostEstimate(
        flops=2 * M * K * N * s.n_products * n_out,
        bytes_accessed=(M * K + n_out * K * N) * 4
        + (M * N if single_out else n_out * M * N)
        * jnp.dtype(out_dtype).itemsize,
        transcendentals=M * N if gate == "swiglu" else 0,
    )
    return pl.pallas_call(
        functools.partial(
            _fused_multi_kernel, spec=s, out_dtype=out_dtype, n_out=n_out,
            gate=gate, has_bias=has_bias, has_residual=has_residual),
        grid=(M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n_out, n_orders, bm, bn), jnp.float32)],
        compiler_params=_compiler_params(MATMUL_SEMANTICS),
        cost_estimate=cost,
        interpret=interpret,
        name="mp_matmul_fused_multi",
    )


KERNEL_VARIANTS = ("fused", "prelimbed_b", "prelimbed_both")


def vmem_bytes(mode: FormatLike, bm: int, bk: int, bn: int,
               out_dtype=jnp.float32, *, n_out: int = 1,
               variant: str = "fused", epilogue: str = "none") -> int:
    """VMEM footprint of one grid step — the autotuner's feasibility filter
    (kernels/autotune.py), per kernel variant:

      fused           A/B arrive f32: f32 tiles + on-the-fly bf16 limbs
      prelimbed_b     B arrives as bf16 limbs: no B f32 tile (serving path)
      prelimbed_both  both arrive as bf16 limbs: no f32 tiles at all (DD)

    ``n_out`` scales the B side, the accumulators, and the output stack for
    the multi-output fused-projection kernel; ``epilogue`` is an
    :func:`epilogue_desc` string — a gate combine collapses the output stack
    to one tile, bias adds an (n_out, 1, bn) tile, a residual adds a
    (bm, bn) input tile.
    """
    if variant not in KERNEL_VARIANTS:
        raise ValueError(f"unknown kernel variant {variant!r}; "
                         f"have {KERNEL_VARIANTS}")
    s = resolve(mode)
    a_f32 = bm * bk * 4 if variant != "prelimbed_both" else 0
    b_f32 = n_out * bk * bn * 4 if variant == "fused" else 0
    limbs = s.n_limbs * (bm * bk + n_out * bk * bn) * 2
    acc = n_out * s.n_orders * bm * bn * 4
    gated = "swiglu" in epilogue
    out = (1 if gated else n_out) * bm * bn * jnp.dtype(out_dtype).itemsize
    extra = 0
    if "bias" in epilogue:
        extra += n_out * bn * 4
    if "res" in epilogue:
        extra += bm * bn * 4
    return a_f32 + b_f32 + limbs + acc + out + extra


def build_fused_call(
    M: int, K: int, N: int,
    mode: FormatLike,
    *,
    bm: int, bk: int, bn: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """pallas_call for the fused on-the-fly-limbs kernel (padded shapes)."""
    s = resolve(mode)
    n_orders = s.max_order + 1
    cost = pl.CostEstimate(
        flops=2 * M * K * N * s.n_products,
        bytes_accessed=(M * K + K * N) * 4 + M * N * jnp.dtype(out_dtype).itemsize,
        transcendentals=0,
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, spec=s, out_dtype=out_dtype),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((n_orders, bm, bn), jnp.float32)],
        compiler_params=_compiler_params(MATMUL_SEMANTICS),
        cost_estimate=cost,
        interpret=interpret,
        name="mp_matmul_fused",
    )


def build_prelimbed_call(
    M: int, K: int, N: int,
    mode: FormatLike,
    *,
    bm: int, bk: int, bn: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
    both: bool = False,
):
    """pallas_call with B (and optionally A) pre-decomposed to bf16 limbs."""
    s = resolve(mode)
    n_orders = s.max_order + 1
    L = s.n_limbs
    if both:
        kern = functools.partial(_both_prelimbed_kernel, spec=s, out_dtype=out_dtype)
        in_specs = [
            pl.BlockSpec((L, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((L, bk, bn), lambda i, j, k: (0, k, j)),
        ]
    else:
        kern = functools.partial(_prelimbed_kernel, spec=s, out_dtype=out_dtype)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((L, bk, bn), lambda i, j, k: (0, k, j)),
        ]
    return pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((n_orders, bm, bn), jnp.float32)],
        compiler_params=_compiler_params(MATMUL_SEMANTICS),
        interpret=interpret,
        name="mp_matmul_prelimbed_both" if both else "mp_matmul_prelimbed",
    )


def build_mixed_prelimbed_call(
    M: int, K: int, N: int,
    env: FormatLike,
    *,
    bm: int, bk: int, bn: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """pallas_call for the partitioned-lane prelimbed kernel.

    Inputs (padded shapes): A (M, K) f32; B limbs (L, K, N) bf16 at the
    envelope depth; lane_n / lane_ord (M, 128) int32 — per-row lane values
    broadcast across the lane dim so the operand tiles cleanly (the kernel
    reads column 0).  Output (M, N)."""
    s = resolve(env)
    n_orders = s.max_order + 1
    L = s.n_limbs
    return pl.pallas_call(
        functools.partial(_mixed_prelimbed_kernel, env=s, out_dtype=out_dtype),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((L, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((bm, 128), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, 128), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((n_orders, bm, bn), jnp.float32)],
        compiler_params=_compiler_params(MATMUL_SEMANTICS),
        interpret=interpret,
        name="mp_matmul_mixed_prelimbed",
    )


# ---------------------------------------------------------------------------
# Standalone limb-decompose kernel (pre-limbing weights once per step / at
# model load for serving).  Elementwise; blocked over the last two dims.
# ---------------------------------------------------------------------------
def _decompose_kernel(x_ref, o_ref, *, n_limbs: int):
    r = x_ref[...].astype(jnp.float32)
    for i in range(n_limbs):
        li = r.astype(jnp.bfloat16)
        o_ref[i] = li
        if i + 1 < n_limbs:
            r = r - li.astype(jnp.float32)


def build_decompose_call(
    R: int, C: int, n_limbs: int, *, br: int, bc: int, interpret: bool = False
):
    return pl.pallas_call(
        functools.partial(_decompose_kernel, n_limbs=n_limbs),
        grid=(R // br, C // bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((n_limbs, br, bc), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_limbs, R, C), jnp.bfloat16),
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=interpret,
        name="mp_limb_decompose",
    )
