"""Pure-jnp oracle for the multi-precision limb matmul.

This is both (a) the correctness reference every Pallas kernel is allclose'd
against and (b) the backend used for whole-model lowering (dry-run), where the
HLO should reflect the real per-mode FLOP count (n_products bf16 matmuls).

Semantics: C = A @ B computed as sum of kept limb products
    C = sum_{(i,j) in spec.products} A_limb[i] @ B_limb[j]
with per-order fp32 accumulators combined smallest-order-last via compensated
summation (DESIGN.md §2: the carry-save-adder analogue).
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import limbs as limbs_lib
from repro.core.limbs import DD, PrelimbedWeight
from repro.core.formats import FormatLike, resolve

Operand = Union[jax.Array, DD, PrelimbedWeight]


def _limbs_of(x: Operand, n_limbs: int) -> jax.Array:
    if isinstance(x, DD):
        return limbs_lib.decompose_dd(x, n_limbs)
    if isinstance(x, PrelimbedWeight):
        # limbs already extracted (serving path), read at the logical shape;
        # missing ones are zero — the value simply carries no bits beyond
        # its stored precision
        limbs = x.logical_limbs()
        have = limbs.shape[0]
        if have >= n_limbs:
            return limbs[:n_limbs]
        pad = jnp.zeros((n_limbs - have,) + limbs.shape[1:], jnp.bfloat16)
        return jnp.concatenate([limbs, pad], axis=0)
    if x.dtype == jnp.bfloat16:
        # already a single-limb operand; higher limbs are zero
        pad = jnp.zeros((n_limbs - 1,) + x.shape, jnp.bfloat16)
        return jnp.concatenate([x[None], pad], axis=0) if n_limbs > 1 else x[None]
    return limbs_lib.decompose(x, n_limbs)


def _matmul_limbs(al: jax.Array, bl: jax.Array, s, out_dtype,
                  dot=None) -> jax.Array:
    """Limb-product contraction from pre-extracted limb stacks (the shared
    core of :func:`mp_matmul_ref` and :func:`mp_fused_proj_ref` — the fused
    variant extracts A's limbs ONCE and calls this per B operand).

    ``dot`` is the f32-accumulating product for one limb pair (default:
    standard matmul orientation); the attention helpers pass the
    untransposed QK contraction so ONE implementation owns the
    accumulation discipline every realization shares."""
    if dot is None:
        def dot(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    if s.n_limbs <= 3:
        # separate limb-product matmuls, PLAIN adds between them.  Operands
        # stay unflattened — a (B·S, K) reshape merges sharded batch×seq dims
        # and GSPMD silently drops the minor (seq) sharding, running every
        # dense layer at full sequence per device.  Plain adds (no Neumaier
        # compare/select) keep the products fusable/reassociable by XLA.
        out = None
        for (i, j) in s.products:  # descending order: small terms first
            p = dot(al[i], bl[j])
            out = p if out is None else out + p
        return out.astype(out_dtype)

    # high modes (M36/M52): per-order fp32 accumulators, compensated combine
    # (accuracy-critical; these modes are rare in production policies)
    by_order: dict[int, list[jax.Array]] = {}
    for (i, j) in s.products:
        p = dot(al[i], bl[j])
        by_order.setdefault(i + j, []).append(p)

    order_sums = []
    for o in sorted(by_order, reverse=True):  # smallest magnitude first
        terms = by_order[o]
        acc = terms[0]
        for t in terms[1:]:
            acc = acc + t
        order_sums.append(acc)

    out = limbs_lib.neumaier_sum(order_sums)
    return out.astype(out_dtype)


def lane_keep(i: int, j: int, lane_n, lane_ord):
    """Which lanes keep limb product ``(i, j)`` — THE partitioned-lane
    predicate every realization (ref oracle, Pallas matmul kernel, Pallas
    paged-attention kernel) shares.

    ``lane_n`` / ``lane_ord`` are per-lane int32 values (scalars inside the
    paged kernel's per-slot program, per-row arrays in the batched matmul);
    a lane at ``k`` limbs and order cut ``c`` keeps exactly the product set
    of its own format, so its masked cascade IS its homogeneous cascade.
    """
    return (i < lane_n) & (j < lane_n) & (i + j <= lane_ord)


def masked_matmul_limbs(al: jax.Array, bl: jax.Array, env, lane_n, lane_ord,
                        out_dtype, dot=None) -> jax.Array:
    """Per-lane masked limb contraction at the envelope format ``env``.

    The product loop runs the *envelope's* descending-order product
    sequence; each lane masks products outside its own format to +0.0
    (``where``, never multiply — 0·Inf would mint NaNs).  Because a lane's
    products are a subsequence of the envelope's and the masked entries add
    exact zeros, every lane's result is bit-identical to its homogeneous
    run modulo zero signs (−0 → +0 flips, which cannot change a token).

    Both accumulation disciplines of :func:`_matmul_limbs` are realized:

    * sequential plain adds (what formats with ≤ 3 limbs run), and
    * per-order partials + compensated (Neumaier) combine over orders
      descending (what > 3-limb formats run) — the leading all-zero orders
      a shallow lane contributes are exact no-ops in the compensation.

    The per-lane result selects its own format's discipline, so the mixed
    launch reproduces each lane's homogeneous accumulation exactly.  When
    the envelope itself is ≤ 3 limbs (every serving builtin up to M23) no
    lane can need the compensated branch and it is skipped statically.
    ``lane_n``/``lane_ord`` must broadcast against one limb product.
    """
    if dot is None:
        def dot(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    masked = []
    for (i, j) in env.products:  # descending order: small terms first
        p = dot(al[i], bl[j])
        masked.append(((i, j), jnp.where(lane_keep(i, j, lane_n, lane_ord),
                                         p, 0.0)))

    seq = None
    for _, p in masked:
        seq = p if seq is None else seq + p

    if env.n_limbs <= 3:
        return seq.astype(out_dtype)

    by_order: dict[int, list[jax.Array]] = {}
    for (i, j), p in masked:
        by_order.setdefault(i + j, []).append(p)
    order_sums = []
    for o in sorted(by_order, reverse=True):  # smallest magnitude first
        terms = by_order[o]
        acc = terms[0]
        for t in terms[1:]:
            acc = acc + t
        order_sums.append(acc)
    neu = limbs_lib.neumaier_sum(order_sums)

    out = jnp.where(lane_n <= 3, seq, neu)
    return out.astype(out_dtype)


def masked_matmul_ref(a: Operand, b: Operand, env, lane_n, lane_ord, *,
                      out_dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """Mixed-lane matmul oracle: a (..., M, K) × b (..., K, N) at per-lane
    depth.  ``lane_n``/``lane_ord`` must broadcast against the (..., M, N)
    product (the decode micro-batch passes (B, 1, 1) for (B, S, N))."""
    al = _limbs_of(a, env.n_limbs)
    bl = _limbs_of(b, env.n_limbs)
    return masked_matmul_limbs(al, bl, env, lane_n, lane_ord, out_dtype)


def masked_attn_qk_logits(q: jax.Array, k: jax.Array, env, lane_n,
                          lane_ord, *, split=limbs_lib.decompose) -> jax.Array:
    """Per-lane :func:`attn_qk_logits`: the same untransposed contraction
    fed through the masked cascade (shared by the ref mixed decode path and
    the Pallas mixed paged kernel, where ``lane_n`` is the program's
    scalar-prefetched per-slot value)."""
    al = split(q, env.n_limbs)
    bl = split(k, env.n_limbs)
    return masked_matmul_limbs(al, bl, env, lane_n, lane_ord, jnp.float32,
                               dot=_dot_nt)


def masked_attn_pv(p: jax.Array, v: jax.Array, env, lane_n,
                   lane_ord, *, split=limbs_lib.decompose) -> jax.Array:
    al = split(p, env.n_limbs)
    bl = split(v, env.n_limbs)
    return masked_matmul_limbs(al, bl, env, lane_n, lane_ord, jnp.float32)


def masked_online_softmax_update(m, d, acc, logits, v, env_pv, lane_n,
                                 lane_ord, *, p_mask=None,
                                 split=limbs_lib.decompose):
    """:func:`online_softmax_update` with the P·V contraction at per-lane
    depth — the softmax bookkeeping itself is format-free and unchanged."""
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    if p_mask is not None:
        p = jnp.where(p_mask, p, 0.0)
    alpha = jnp.exp(m - m_new)
    d_new = d * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] \
        + masked_attn_pv(p, v, env_pv, lane_n, lane_ord, split=split)
    return m_new, d_new, acc_new


def mp_matmul_ref(
    a: Operand,
    b: Operand,
    mode: FormatLike = "M16",
    *,
    out_dtype: jnp.dtype = jnp.float32,
    dim_numbers: Optional[str] = None,
) -> jax.Array:
    """Multi-precision matmul oracle.

    a: (..., M, K), b: (..., K, N) with broadcastable leading batch dims
    (jnp.matmul semantics).  Returns (..., M, N) in ``out_dtype``.
    """
    s = resolve(mode)

    if s.n_limbs == 1:
        # mode M8: plain bf16 matmul with fp32 accumulation — one MXU pass.
        a1 = _limbs_of(a, 1)[0] if isinstance(a, PrelimbedWeight) \
            else (a.hi if isinstance(a, DD) else a).astype(jnp.bfloat16)
        b1 = _limbs_of(b, 1)[0] if isinstance(b, PrelimbedWeight) \
            else (b.hi if isinstance(b, DD) else b).astype(jnp.bfloat16)
        out = jnp.matmul(a1, b1, preferred_element_type=jnp.float32)
        return out.astype(out_dtype)

    al = _limbs_of(a, s.n_limbs)  # (L, ..., M, K) bf16
    bl = _limbs_of(b, s.n_limbs)  # (L, ..., K, N) bf16
    return _matmul_limbs(al, bl, s, out_dtype)


def apply_epilogue(raws, *, gate: str = "none", biases=None, residual=None,
                   out_dtype=None):
    """The epilogue lattice on raw projection outputs: per-branch bias add,
    gate combine (``silu(raws[0]) * raws[1]``), then residual add.  Returns
    the combined array, the lone output (n_out == 1 unwraps), or the output
    tuple.  This is THE non-kernel epilogue: the ref oracle, the sequential
    fallbacks (dispatch extension backends, pre-limbed/AUTO operands), and
    the rematerializing AD forward in core/mpmatmul.py all call it, so every
    realization applies bit-identical epilogue math."""
    raws = list(raws)
    if biases is not None:
        raws = [r if b is None else r + b.astype(r.dtype)
                for r, b in zip(raws, biases)]
    if gate == "swiglu":
        if len(raws) != 2:
            raise ValueError(f"swiglu gate needs 2 outputs, got {len(raws)}")
        out = jax.nn.silu(raws[0].astype(jnp.float32)) \
            * raws[1].astype(jnp.float32)
    elif gate == "none":
        out = None
    else:
        raise ValueError(f"unknown gate {gate!r}")
    if residual is not None:
        if out is None and len(raws) != 1:
            raise ValueError("residual epilogue needs a single final output")
        out = (raws[0] if out is None else out) + residual
    if out is None:
        outs = tuple(r.astype(out_dtype) for r in raws) if out_dtype \
            else tuple(raws)
        return outs[0] if len(outs) == 1 else outs
    return out.astype(out_dtype) if out_dtype else out


def mp_fused_proj_ref(
    x: Operand,
    ws,
    mode: FormatLike,
    *,
    gate: str = "none",
    biases=None,
    residual=None,
    out_dtype: jnp.dtype = jnp.float32,
):
    """Operand-shared fused projection oracle: ``n_out`` contractions of one
    activation ``x`` against stacked weights, decomposing x's limbs ONCE.

    x: (..., M, K); ws: sequence of (K, N_t) (or PrelimbedWeight).  Returns a
    tuple of (..., M, N_t) outputs, or a single array when the epilogue
    combines them (gate) / n_out == 1.  This is also what the XLA ("ref") and
    sharded backends run — sharing the one-time A decomposition is the fused
    win those backends can realize without a Pallas kernel.
    """
    s = resolve(mode)
    al = _limbs_of(x, s.n_limbs)  # ONCE, shared across all n_out products
    raws = []
    for w in ws:
        if s.n_limbs == 1:
            b1 = _limbs_of(w, 1)[0]
            raw = jnp.matmul(al[0], b1, preferred_element_type=jnp.float32)
        else:
            raw = _matmul_limbs(al, _limbs_of(w, s.n_limbs), s, jnp.float32)
        raws.append(raw)
    return apply_epilogue(raws, gate=gate, biases=biases, residual=residual,
                          out_dtype=out_dtype)


def mp_matmul_partials(
    a: Operand,
    b: Operand,
    mode: FormatLike,
) -> jax.Array:
    """Per-order partial sums: (n_orders, ..., M, N) fp32, order o at index o.

    The sharded backend's local compute step (DESIGN.md §5): each device
    accumulates its K-slice's limb products *per order* and the cross-device
    psum reduces this stack — the compensated cross-order combine
    (``combine_partials``) then runs once on the fully-reduced partials, so
    the K partition does not change which terms each compensation sees."""
    s = resolve(mode)
    al = _limbs_of(a, s.n_limbs)
    bl = _limbs_of(b, s.n_limbs)
    by_order: dict[int, jax.Array] = {}
    for (i, j) in s.products:
        p = jnp.matmul(al[i], bl[j], preferred_element_type=jnp.float32)
        o = i + j
        by_order[o] = p if o not in by_order else by_order[o] + p
    return jnp.stack([by_order[o] for o in range(s.n_orders)], axis=0)


def combine_partials(
    partials: jax.Array,
    mode: FormatLike,
    *,
    out_dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """Compensated cross-order combine of a ``mp_matmul_partials`` stack.

    Order o carries magnitude ~2^-8o, so summation runs highest order first
    (smallest magnitude -> largest), matching the ref/Pallas accumulation
    order."""
    s = resolve(mode)
    terms = [partials[o] for o in range(s.n_orders - 1, -1, -1)]
    return limbs_lib.neumaier_sum(terms).astype(out_dtype)


def matmul_golden_f64(a, b) -> np.ndarray:
    """Host-side float64 golden product (numpy) — the accuracy yardstick."""
    a64 = (
        limbs_lib.dd_to_f64(a) if isinstance(a, DD) else np.asarray(a, np.float64)
    )
    b64 = (
        limbs_lib.dd_to_f64(b) if isinstance(b, DD) else np.asarray(b, np.float64)
    )
    return a64 @ b64


def mp_wgrad_ref(
    a: jax.Array,
    g: jax.Array,
    mode: FormatLike,
    *,
    out_dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """Weight gradient a^T·g contracting ALL leading dims at once:
    a (..., K), g (..., N) -> (K, N).

    dot_general with multi-dim contraction keeps the (batch, seq) shardings
    visible to GSPMD (local partial wgrad + one reduce over the token axes)
    instead of flatten-then-matmul which gathers the sequence axis."""
    s = resolve(mode)
    lead = tuple(range(a.ndim - 1))
    if s.n_limbs == 1:
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), g.astype(jnp.bfloat16),
            ((lead, lead), ((), ())),
            preferred_element_type=jnp.float32).astype(out_dtype)
    al = limbs_lib.decompose(a, s.n_limbs)
    gl = limbs_lib.decompose(g.astype(jnp.float32), s.n_limbs)
    a_sel = jnp.stack([al[i] for (i, j) in s.products])
    g_sel = jnp.stack([gl[j] for (i, j) in s.products])
    lead_p = tuple(range(a_sel.ndim - 1))  # (P, *lead)
    out = jax.lax.dot_general(
        a_sel, g_sel, ((lead_p, lead_p), ((), ())),
        preferred_element_type=jnp.float32)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Fused multi-precision attention: shared online-softmax core + ref oracle.
#
# The helpers below are pure jnp and are THE attention math for every
# realization: the ref oracle loops them over (q, kv) blocks, and the Pallas
# kernels (kernels/mp_attention.py) call the very same functions on VMEM
# tiles — so ref, pallas_interpret, and pallas agree structurally (same limb
# cascades, same order combine, same running-max/denominator updates), and
# "chunked vs fused" differences reduce to float reassociation within the
# format's error bound (DESIGN.md §4a).
# ---------------------------------------------------------------------------
ATTN_NEG_INF = -1e30


def _dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """(..., M, D) x (..., T, D) -> (..., M, T): contract the trailing head
    dim of two *untransposed* operands (matching leading dims are batch).
    Lets the kernels feed (bq, Dh)/(bkv, Dh) VMEM tiles without a transpose."""
    nb = a.ndim - 2
    dn = (((a.ndim - 1,), (b.ndim - 1,)),
          (tuple(range(nb)), tuple(range(nb))))
    return jax.lax.dot_general(a, b, dn, preferred_element_type=jnp.float32)


def attn_qk_logits(q: jax.Array, k: jax.Array, mode: FormatLike, *,
                   split=limbs_lib.decompose) -> jax.Array:
    """Attention logits for one block pair at the QK format:
    q (..., M, D) f32 (pre-scaled), k (..., T, D) f32 -> (..., M, T) f32.
    The limb cascade runs on both operands (activations x activations —
    unlike the dense layers there is no static weight side to pre-limb);
    accumulation is :func:`_matmul_limbs`' own discipline, with the
    untransposed contraction plugged in as the limb-pair product.

    ``split`` extracts the limbs: ``limbs.decompose`` for XLA, the Pallas
    kernels' own convert-pair cascade inside Mosaic."""
    s = resolve(mode)
    al = split(q, s.n_limbs)
    bl = split(k, s.n_limbs)
    return _matmul_limbs(al, bl, s, jnp.float32, dot=_dot_nt)


def attn_pv(p: jax.Array, v: jax.Array, mode: FormatLike, *,
            split=limbs_lib.decompose) -> jax.Array:
    """Probability-value contraction at the PV format:
    p (..., M, T) f32, v (..., T, D) f32 -> (..., M, D) f32."""
    s = resolve(mode)
    al = split(p, s.n_limbs)
    bl = split(v, s.n_limbs)
    return _matmul_limbs(al, bl, s, jnp.float32)


def online_softmax_update(m, d, acc, logits, v, mode_pv, *, p_mask=None,
                          split=limbs_lib.decompose):
    """One kv-block step of the running (max, denom, accum) softmax.

    m, d: (..., M); acc: (..., M, D); logits: (..., M, T_blk) f32 with
    invalid positions already at ``ATTN_NEG_INF``; v: (..., T_blk, D).
    ``p_mask`` (broadcastable to logits) re-zeroes probabilities explicitly —
    required wherever a whole row of a block can be masked (a fully-masked
    row has max == ATTN_NEG_INF, so exp(logit - max) == 1, not 0).
    """
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    if p_mask is not None:
        p = jnp.where(p_mask, p, 0.0)
    alpha = jnp.exp(m - m_new)
    d_new = d * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + attn_pv(p, v, mode_pv, split=split)
    return m_new, d_new, acc_new


def mp_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mode_qk: FormatLike = "M16",
    mode_pv: Optional[FormatLike] = None,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    out_dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """Fused multi-precision flash-attention oracle (pure jnp).

    q: (B, S, H, Dh); k/v: (B, T, H, Dh) with H already GQA-repeated.
    QK^T runs the limb cascade at ``mode_qk`` and P·V at ``mode_pv``
    (defaults to ``mode_qk``) — the two op classes the policy resolves as
    ``attn_qk`` / ``attn_pv``.  ``block_q``/``block_kv`` default to the full
    sequence (the *unchunked* oracle); any blocking agrees with it within
    the formats' error bounds because the per-block update is the exact
    shared core the Pallas kernel runs.  ``q_offset`` shifts the causal
    query positions (prefill at a nonzero cache offset).
    """
    B, S, H, Dh = q.shape
    T = k.shape[1]
    fmt_pv = resolve(mode_pv if mode_pv is not None else mode_qk)
    fmt_qk = resolve(mode_qk)
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))

    bq = S if block_q is None else max(1, min(block_q, S))
    bkv = T if block_kv is None else max(1, min(block_kv, T))
    nq, nkv = -(-S // bq), -(-T // bkv)
    S_pad, T_pad = nq * bq, nkv * bkv

    # (B, S, H, Dh) -> (B, H, S, Dh), zero-padded to block multiples
    qh = jnp.pad(q.transpose(0, 2, 1, 3).astype(jnp.float32) * scale,
                 [(0, 0), (0, 0), (0, S_pad - S), (0, 0)])
    kh = jnp.pad(k.transpose(0, 2, 1, 3).astype(jnp.float32),
                 [(0, 0), (0, 0), (0, T_pad - T), (0, 0)])
    vh = jnp.pad(v.transpose(0, 2, 1, 3).astype(jnp.float32),
                 [(0, 0), (0, 0), (0, T_pad - T), (0, 0)])

    outs = []
    for qi in range(nq):
        q_blk = qh[:, :, qi * bq:(qi + 1) * bq]
        q_pos = q_offset + qi * bq + jnp.arange(bq)
        m = jnp.full((B, H, bq), ATTN_NEG_INF, jnp.float32)
        d = jnp.zeros((B, H, bq), jnp.float32)
        acc = jnp.zeros((B, H, bq, Dh), jnp.float32)
        for ki in range(nkv):
            if causal and ki * bkv > q_offset + (qi + 1) * bq - 1:
                continue  # block entirely above the causal diagonal
            k_blk = kh[:, :, ki * bkv:(ki + 1) * bkv]
            v_blk = vh[:, :, ki * bkv:(ki + 1) * bkv]
            k_pos = ki * bkv + jnp.arange(bkv)
            valid = k_pos[None, :] < T
            if causal:
                valid = valid & (q_pos[:, None] >= k_pos[None, :])
            logits = attn_qk_logits(q_blk, k_blk, fmt_qk)
            logits = jnp.where(valid, logits, ATTN_NEG_INF)
            m, d, acc = online_softmax_update(
                m, d, acc, logits, v_blk, fmt_pv, p_mask=valid)
        outs.append(acc / jnp.maximum(d[..., None], 1e-30))
    out = jnp.concatenate(outs, axis=2)[:, :, :S]
    return out.transpose(0, 2, 1, 3).astype(out_dtype)


def naive_multipass_ref(
    a: jax.Array, b: jax.Array, mode: FormatLike
) -> jax.Array:
    """The *unoptimized* baseline the paper compares against (schoolbook):
    all n_limbs^2 limb products, no order cut, naive left-to-right fp32 sum.
    Used by benchmarks/table4_comparison.py."""
    s = resolve(mode)
    al = _limbs_of(a, s.n_limbs)
    bl = _limbs_of(b, s.n_limbs)
    out = jnp.zeros(a.shape[:-1] + b.shape[-1:], jnp.float32)
    for i in range(s.n_limbs):
        for j in range(s.n_limbs):
            out = out + jnp.matmul(al[i], bl[j], preferred_element_type=jnp.float32)
    return out
