"""Pallas TPU kernels: fused multi-precision flash attention (DESIGN.md §4a).

The chunk-scan attention path (models/attention.py) launches one ``mp_matmul``
per (q-chunk, kv-chunk) pair and lets the probability matrix P round-trip
through HBM between QK^T and P·V.  These kernels fuse the whole pipeline —
the QK^T limb cascade at the ``attn_qk`` format, the online softmax (running
max / denominator / rescale), and the P·V limb cascade at the ``attn_pv``
format — into one grid program where P lives only in VMEM registers/scratch:

    HBM traffic  = read Q,K,V once + write O once        (P bytes: ZERO)
    vs chunk scan: + write P + read P  (S·T·4 bytes per head, both ways)

and K/V tiles are read once per q-block instead of once per scan iteration.
MXU passes stay mode-proportional: n_products(attn_qk) + n_products(attn_pv)
per tile pair — the paper's reconfigurable multiplier driving both attention
contractions at independently policy-resolved formats.

Three variants:

  * ``mp_attention_pallas`` — training/prefill: grid (B·H, nq, nkv), kv
    innermost sequential; per-q-block (m, d, acc) scratch persists across kv
    steps; causal blocks entirely above the diagonal skip their MXU work.
  * ``mp_paged_attention_pallas`` — serving decode: one query token per slot
    against the scheduler's paged KV pool.  The block table rides scalar
    prefetch, so each grid step DMAs exactly ONE pool block straight from
    its physical location — no ``pool[table]`` gather materializing a
    contiguous (B, W·bs) copy of the cache in HBM — and per-slot lengths
    mask the tail.  Inactive slots (all-trash rows, length 0) produce exact
    zeros.
  * ``mp_latent_paged_attention_pallas`` — the same for latent attention
    (MLA's absorbed decode): every query head of a slot against one latent
    pool entry per position, MQA over ``c_kv`` with a rope key beside it;
    each pool block is read once for all heads.

Numerical structure is shared with the ref backend: both call the
``attn_qk_logits`` / ``online_softmax_update`` helpers in kernels/ref.py, so
ref / pallas_interpret / pallas differ only in float reassociation, within
the formats' error bounds (tests/test_mp_attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FormatLike, resolve
from repro.kernels import ref as ref_backend
from repro.kernels.mp_matmul import _compiler_params, _extract_limbs

NEG_INF = ref_backend.ATTN_NEG_INF

# grid (B, W): slots are independent; the (m, d, acc) scratch accumulates
# over a slot's table columns, so W runs in order
PAGED_SEMANTICS = ("parallel", "arbitrary")

# default flash tile sizes (q rows x kv columns); autotune sweeps around them
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def attn_vmem_bytes(mode_qk: FormatLike, mode_pv: FormatLike,
                    block_q: int, block_kv: int, head_dim: int, *,
                    out_dtype=jnp.float32) -> int:
    """VMEM footprint of one flash-attention grid step — the autotuner's
    feasibility filter for the attention variant (kernels/autotune.py).

    Counts the f32 Q/K/V tiles, both operands' on-the-fly bf16 limb stacks
    (QK side at ``mode_qk``'s limb count over Q and K, PV side at
    ``mode_pv``'s over P and V), the P tile itself, the (m, d) running
    statistics, the accumulator, and the output tile.  (The paged decode
    kernel's tiles are fixed by the pool layout — one block of
    ``block_size`` positions, all kv heads — so it has no sweepable
    footprint to model.)"""
    qk, pv = resolve(mode_qk), resolve(mode_pv)
    q_tile = block_q * head_dim * 4
    kv_tiles = 2 * block_kv * head_dim * 4
    q_limbs = qk.n_limbs * block_q * head_dim * 2
    k_limbs = qk.n_limbs * block_kv * head_dim * 2
    p_tile = block_q * block_kv * 4
    p_limbs = pv.n_limbs * block_q * block_kv * 2
    v_limbs = pv.n_limbs * block_kv * head_dim * 2
    stats = 2 * block_q * 128 * 4                  # m, d scratch rows
    acc = block_q * head_dim * 4
    out = block_q * head_dim * jnp.dtype(out_dtype).itemsize
    return (q_tile + kv_tiles + q_limbs + k_limbs + p_tile + p_limbs
            + v_limbs + stats + acc + out)


# ---------------------------------------------------------------------------
# training / prefill flash kernel
# ---------------------------------------------------------------------------
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, d_scr, acc_scr, *,
                  fmt_qk, fmt_pv, causal: bool, scale: float, q_offset: int,
                  t_real: int, out_dtype):
    """Grid (B·H, nq, nkv), kv innermost sequential.  Blocks: q (1, bq, Dp),
    k/v (1, bkv, Dp), o (1, bq, Dp); scratch m/d (bq, 128), acc (bq, Dp)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq = q_ref.shape[1]
    bkv = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0)
        k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        valid = k_pos < t_real
        if causal:
            valid = valid & (q_pos >= k_pos)
        logits = ref_backend.attn_qk_logits(q, kb, fmt_qk,
                                            split=_extract_limbs)
        logits = jnp.where(valid, logits, NEG_INF)
        m, d, acc = ref_backend.online_softmax_update(
            m_scr[:, 0], d_scr[:, 0], acc_scr[...], logits, vb, fmt_pv,
            p_mask=valid, split=_extract_limbs)
        m_scr[...] = jnp.broadcast_to(m[:, None], m_scr.shape)
        d_scr[...] = jnp.broadcast_to(d[:, None], d_scr.shape)
        acc_scr[...] = acc

    if causal:
        # skip kv blocks entirely above the causal diagonal: their MXU
        # passes contribute nothing (the DMA still runs; the win is compute)
        @pl.when(ki * bkv <= q_offset + (qi + 1) * bq - 1)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _flush():
        d = jnp.maximum(d_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / d[:, None]).astype(out_dtype)


def mp_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mode_qk: FormatLike = "M16",
    mode_pv: Optional[FormatLike] = None,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    out_dtype=jnp.float32,
    interpret: bool = False,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jax.Array:
    """Fused flash attention: q (B, S, H, Dh), k/v (B, T, H, Dh) with H
    already GQA-repeated -> (B, S, H, Dh).  Head dim pads to a lane multiple
    (zero limbs contribute nothing); S/T pad to block multiples with the
    padded tail masked in-kernel."""
    B, S, H, Dh = q.shape
    T = k.shape[1]
    fmt_qk = resolve(mode_qk)
    fmt_pv = resolve(mode_pv if mode_pv is not None else mode_qk)
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))

    bq = min(block_q or DEFAULT_BLOCK_Q, _round_up(S, 8))
    bkv = min(block_kv or DEFAULT_BLOCK_KV, _round_up(T, 128))
    from repro.kernels import autotune  # deferred: autotune imports this

    budget = autotune.VMEM_BUDGET_BYTES
    Dp = _round_up(Dh, 128)
    while attn_vmem_bytes(fmt_qk, fmt_pv, bq, bkv, Dp,
                          out_dtype=out_dtype) > budget and bkv > 128:
        bkv = max(128, bkv // 2)
    while attn_vmem_bytes(fmt_qk, fmt_pv, bq, bkv, Dp,
                          out_dtype=out_dtype) > budget and bq > 8:
        bq = max(8, bq // 2)

    S_pad, T_pad = _round_up(S, bq), _round_up(T, bkv)

    def fold(x, s_pad):
        # (B, S, H, Dh) -> (B*H, S_pad, Dp)
        x = x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], Dh)
        return jnp.pad(x, [(0, 0), (0, s_pad - x.shape[1]), (0, Dp - Dh)])

    qf = fold(q.astype(jnp.float32), S_pad)
    kf = fold(k.astype(jnp.float32), T_pad)
    vf = fold(v.astype(jnp.float32), T_pad)

    grid = (B * H, S_pad // bq, T_pad // bkv)
    mxu = fmt_qk.n_products + fmt_pv.n_products
    cost = pl.CostEstimate(
        flops=2 * B * H * S_pad * T_pad * Dp * mxu,
        bytes_accessed=(B * H * (S_pad + 2 * T_pad) * Dp) * 4
        + B * H * S_pad * Dp * jnp.dtype(out_dtype).itemsize,
        transcendentals=B * H * S_pad * T_pad,
    )
    call = pl.pallas_call(
        functools.partial(
            _flash_kernel, fmt_qk=fmt_qk, fmt_pv=fmt_pv, causal=causal,
            scale=scale, q_offset=q_offset, t_real=T, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, Dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, Dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dp), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S_pad, Dp), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dp), jnp.float32),
        ],
        # kv steps carry the (m, d, acc) scratch: innermost axis in order
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
        name="mp_flash_attention",
    )
    out = call(qf, kf, vf)
    out = out[:, :S, :Dh].reshape(B, H, S, Dh)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# paged decode kernel (continuous-batching serving)
# ---------------------------------------------------------------------------
def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, d_scr, acc_scr, *, fmt_qk, fmt_pv, n_rep: int,
                  scale: float, out_dtype):
    """Grid (B, W): one (slot, table-column) per step, columns sequential.
    q (1, H, Dh); k/v (1, bs, Hkv, Dh) — the pool block the slot's table
    names for this column (trash block for the unallocated tail)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    bs = k_ref.shape[1]
    H = q_ref.shape[1]
    hk = H // n_rep

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(j * bs < length)  # skip columns entirely past the slot's length
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale      # (H, Dh)
        kb = k_ref[0].astype(jnp.float32)             # (bs, Hkv, Dh)
        vb = v_ref[0].astype(jnp.float32)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (n_rep, bs), 1)
        valid = pos < length                           # (n_rep, bs)
        ms, ds, accs = [], [], []
        for kh in range(hk):  # static GQA loop: 2-D MXU work per kv head
            sl = slice(kh * n_rep, (kh + 1) * n_rep)
            logits = ref_backend.attn_qk_logits(q[sl], kb[:, kh], fmt_qk,
                                                split=_extract_limbs)
            logits = jnp.where(valid, logits, NEG_INF)
            m, d, acc = ref_backend.online_softmax_update(
                m_scr[sl, 0], d_scr[sl, 0], acc_scr[sl], logits,
                vb[:, kh], fmt_pv, p_mask=valid, split=_extract_limbs)
            ms.append(m)
            ds.append(d)
            accs.append(acc)
        m = jnp.concatenate(ms)
        d = jnp.concatenate(ds)
        m_scr[...] = jnp.broadcast_to(m[:, None], m_scr.shape)
        d_scr[...] = jnp.broadcast_to(d[:, None], d_scr.shape)
        acc_scr[...] = jnp.concatenate(accs, axis=0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        # inactive slots (length 0) flush exact zeros: d stays 0, acc stays 0
        d = jnp.maximum(d_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / d[:, None]).astype(out_dtype)


def mp_paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    mode_qk: FormatLike = "M16",
    mode_pv: Optional[FormatLike] = None,
    *,
    scale: Optional[float] = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Paged-decode flash attention: one query token per slot against the
    scheduler's block pool, K/V blocks DMA'd straight through the block
    table (scalar prefetch) — the fallback path's ``pool[table]`` gather
    never materializes.

    q: (B, H, Dh); k_pool/v_pool: (n_blocks, bs, Hkv, Dh);
    block_table: (B, W) int32 (trash-padded); lengths: (B,) int32.
    Returns (B, H, Dh).  GQA ratio is inferred as H // Hkv.
    """
    B, H, Dh = q.shape
    n_blocks, bs, hk, dh = k_pool.shape
    assert dh == Dh and H % hk == 0, (q.shape, k_pool.shape)
    n_rep = H // hk
    W = block_table.shape[1]
    fmt_qk = resolve(mode_qk)
    fmt_pv = resolve(mode_pv if mode_pv is not None else mode_qk)
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, j, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, hk, Dh),
                         lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, hk, Dh),
                         lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda b, j, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(
            _paged_kernel, fmt_qk=fmt_qk, fmt_pv=fmt_pv, n_rep=n_rep,
            scale=scale, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), out_dtype),
        compiler_params=_compiler_params(PAGED_SEMANTICS),
        interpret=interpret,
        name="mp_paged_attention",
    )
    return call(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
                q.astype(jnp.float32), k_pool, v_pool)


# ---------------------------------------------------------------------------
# latent paged decode kernel (MLA's absorbed decode)
# ---------------------------------------------------------------------------
def _latent_paged_kernel(tbl_ref, len_ref, ql_ref, qr_ref, c_ref, r_ref,
                         o_ref, m_scr, d_scr, acc_scr, *, fmt_qk, fmt_pv,
                         scale: float, out_dtype):
    """Grid (B, W): one (slot, table-column) per step, columns sequential.
    q_lat (1, H, Dc), q_rope (1, H, Dr); c (1, bs, Dc), r (1, bs, Dr) — the
    pool block the slot's table names for this column.  Logits at the QK
    format: ``q_lat · c + q_rope · r``; the context ``p · c`` at the PV
    format."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    bs = c_ref.shape[1]
    H = ql_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(j * bs < length)  # skip columns entirely past the slot's length
    def _body():
        ql = ql_ref[0].astype(jnp.float32) * scale     # (H, Dc)
        qr = qr_ref[0].astype(jnp.float32) * scale     # (H, Dr)
        cb = c_ref[0].astype(jnp.float32)              # (bs, Dc)
        rb = r_ref[0].astype(jnp.float32)              # (bs, Dr)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        valid = pos < length
        logits = (ref_backend.attn_qk_logits(ql, cb, fmt_qk,
                                             split=_extract_limbs)
                  + ref_backend.attn_qk_logits(qr, rb, fmt_qk,
                                               split=_extract_limbs))
        logits = jnp.where(valid, logits, NEG_INF)
        m, d, acc = ref_backend.online_softmax_update(
            m_scr[:, 0], d_scr[:, 0], acc_scr[...], logits, cb, fmt_pv,
            p_mask=valid, split=_extract_limbs)
        m_scr[...] = jnp.broadcast_to(m[:, None], m_scr.shape)
        d_scr[...] = jnp.broadcast_to(d[:, None], d_scr.shape)
        acc_scr[...] = acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        d = jnp.maximum(d_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / d[:, None]).astype(out_dtype)


def mp_latent_paged_attention_pallas(
    q_lat: jax.Array,
    q_rope: jax.Array,
    c_pool: jax.Array,
    r_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    mode_qk: FormatLike = "M16",
    mode_pv: Optional[FormatLike] = None,
    *,
    scale: float,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Paged latent attention: H query heads per slot as MQA over the
    latent pool, blocks DMA'd through the scalar-prefetched block table.

    q_lat: (B, H, Dc); q_rope: (B, H, Dr); c_pool: (n_blocks, bs, Dc);
    r_pool: (n_blocks, bs, Dr); block_table: (B, W) int32; lengths: (B,)
    int32.  Returns the context (B, H, Dc): the softmax of ``scale ·
    (q_lat · c + q_rope · r)`` over the slot's positions, applied to c."""
    B, H, Dc = q_lat.shape
    Dr = q_rope.shape[-1]
    n_blocks, bs, dc = c_pool.shape
    assert dc == Dc and r_pool.shape == (n_blocks, bs, Dr), (
        q_lat.shape, q_rope.shape, c_pool.shape, r_pool.shape)
    W = block_table.shape[1]
    fmt_qk = resolve(mode_qk)
    fmt_pv = resolve(mode_pv if mode_pv is not None else mode_qk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, H, Dc), lambda b, j, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, H, Dr), lambda b, j, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, Dc), lambda b, j, tbl, ln: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, bs, Dr), lambda b, j, tbl, ln: (tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dc), lambda b, j, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Dc), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_latent_paged_kernel, fmt_qk=fmt_qk, fmt_pv=fmt_pv,
                          scale=scale, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dc), out_dtype),
        compiler_params=_compiler_params(PAGED_SEMANTICS),
        interpret=interpret,
        name="mp_latent_paged_attention",
    )
    return call(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
                q_lat.astype(jnp.float32), q_rope.astype(jnp.float32),
                c_pool, r_pool)


# ---------------------------------------------------------------------------
# partitioned-lane paged decode kernel (mixed-format micro-batches)
# ---------------------------------------------------------------------------
def _mixed_paged_kernel(tbl_ref, len_ref, lane_ref, q_ref, k_ref, v_ref,
                        o_ref, m_scr, d_scr, acc_scr, *, env_qk, env_pv,
                        n_rep: int, scale: float, out_dtype):
    """The paged kernel with per-slot lane depths: grid (B, W) makes each
    program one lane, so the scalar-prefetched lane table row collapses to
    four per-program scalars (QK/PV limb count and order cut) that feed the
    SAME masked cascade the ref realization runs
    (``kernels/ref.masked_attn_qk_logits`` /
    ``masked_online_softmax_update``).  The limb loops iterate to the
    batch-max (envelope) depth; a lane's surplus limb products are masked to
    exact zeros — the partitioned-lane analogue of the causal-block skip
    above."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    bs = k_ref.shape[1]
    H = q_ref.shape[1]
    hk = H // n_rep

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    n_qk, ord_qk = lane_ref[b, 0], lane_ref[b, 1]
    n_pv, ord_pv = lane_ref[b, 2], lane_ref[b, 3]

    @pl.when(j * bs < length)  # skip columns entirely past the slot's length
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale      # (H, Dh)
        kb = k_ref[0].astype(jnp.float32)             # (bs, Hkv, Dh)
        vb = v_ref[0].astype(jnp.float32)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (n_rep, bs), 1)
        valid = pos < length                           # (n_rep, bs)
        ms, ds, accs = [], [], []
        for kh in range(hk):  # static GQA loop: 2-D MXU work per kv head
            sl = slice(kh * n_rep, (kh + 1) * n_rep)
            logits = ref_backend.masked_attn_qk_logits(
                q[sl], kb[:, kh], env_qk, n_qk, ord_qk, split=_extract_limbs)
            logits = jnp.where(valid, logits, NEG_INF)
            m, d, acc = ref_backend.masked_online_softmax_update(
                m_scr[sl, 0], d_scr[sl, 0], acc_scr[sl], logits,
                vb[:, kh], env_pv, n_pv, ord_pv, p_mask=valid,
                split=_extract_limbs)
            ms.append(m)
            ds.append(d)
            accs.append(acc)
        m = jnp.concatenate(ms)
        d = jnp.concatenate(ds)
        m_scr[...] = jnp.broadcast_to(m[:, None], m_scr.shape)
        d_scr[...] = jnp.broadcast_to(d[:, None], d_scr.shape)
        acc_scr[...] = jnp.concatenate(accs, axis=0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        d = jnp.maximum(d_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / d[:, None]).astype(out_dtype)


def mp_mixed_paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    env_qk,
    env_pv,
    lane_qk_n: jax.Array,
    lane_qk_ord: jax.Array,
    lane_pv_n: jax.Array,
    lane_pv_ord: jax.Array,
    *,
    scale: Optional[float] = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Partitioned-lane paged decode: one launch for a mixed-format batch.

    Same shapes as :func:`mp_paged_attention_pallas` plus the per-slot lane
    tables (``lane_*`` — (B,) int32, limb count and order cut per slot for
    the QK and PV contractions) and the static envelope formats ``env_qk``
    / ``env_pv`` (the componentwise batch max — what the launch is traced
    at).  Lane data is packed into one (B, 4) scalar-prefetch operand next
    to the block table.
    """
    B, H, Dh = q.shape
    n_blocks, bs, hk, dh = k_pool.shape
    assert dh == Dh and H % hk == 0, (q.shape, k_pool.shape)
    n_rep = H // hk
    W = block_table.shape[1]
    env_qk = resolve(env_qk)
    env_pv = resolve(env_pv)
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))

    lanes = jnp.stack(
        [lane_qk_n, lane_qk_ord, lane_pv_n, lane_pv_ord], axis=1
    ).astype(jnp.int32)  # (B, 4)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, j, tbl, ln, la: (b, 0, 0)),
            pl.BlockSpec((1, bs, hk, Dh),
                         lambda b, j, tbl, ln, la: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, hk, Dh),
                         lambda b, j, tbl, ln, la: (tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh),
                               lambda b, j, tbl, ln, la: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(
            _mixed_paged_kernel, env_qk=env_qk, env_pv=env_pv, n_rep=n_rep,
            scale=scale, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), out_dtype),
        compiler_params=_compiler_params(PAGED_SEMANTICS),
        interpret=interpret,
        name="mp_mixed_paged_attention",
    )
    return call(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
                lanes, q.astype(jnp.float32), k_pool, v_pool)
