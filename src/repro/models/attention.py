"""GQA / MHA attention: fused multi-precision flash attention (Pallas kernel
or blocked-jnp oracle via ``mp_attention``), the chunk-scan fallback (pure
JAX, online softmax — memory O(q_chunk × kv_chunk) instead of O(S²)),
KV-cache decode, and encoder (bidirectional) mode.

All projections and both attention einsums run through the mp dispatch layer
(``mp_qkv_proj`` / ``mp_attention`` / ``mp_matmul``), so the whole attention
block obeys the run-time precision policy on every path — training prefill,
dense decode, and paged scheduled decode included.  The attention
contractions resolve the ``attn_qk`` (QK^T) and ``attn_pv`` (P·V) op
classes, which alias to the legacy ``attn_logits`` / ``attn_out`` rules for
pre-split policies (core/policy.py).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dispatch as dispatch_lib
from repro.core import lanes as lanes_lib
from repro.core.formats import is_auto
from repro.core.mpmatmul import mp_attention, mp_dense, mp_matmul, mp_qkv_proj
from repro.core.policy import PrecisionPolicy
from repro.models.layers import apply_rope, dense_init
from repro.serve.kv_cache import PagedKVCache

NEG_INF = -1e30

# ceiling on the rematerialized probability matrix (B·H·S·T f32 elements)
# the fused path's dense backward may form; longer sequences fall back to
# the chunk-scan, whose scan-carried backward stays O(chunk²)
FUSED_P_MAX_ELEMENTS = 1 << 24


class KVCache(NamedTuple):
    k: jax.Array        # (B, S_max, Hkv, Dh)
    v: jax.Array        # (B, S_max, Hkv, Dh)
    length: jax.Array   # scalar int32: valid prefix length


@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    causal: bool = True


def init_attn_params(key, dims: AttnDims, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    d, h, hk, dh = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    return {
        "wq": dense_init(ks[0], d, h * dh, dtype),
        "wk": dense_init(ks[1], d, hk * dh, dtype),
        "wv": dense_init(ks[2], d, hk * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype),
    }


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, Dh) -> (B, S, Hkv*n_rep, Dh) — GQA head sharing."""
    if n_rep == 1:
        return x
    b, s, hk, dh = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, hk, n_rep, dh)
                            ).reshape(b, s, hk * n_rep, dh)


def chunked_attention(
    q: jax.Array,            # (B, S, H, Dh)
    k: jax.Array,            # (B, T, H, Dh)
    v: jax.Array,            # (B, T, H, Dh)
    policy: PrecisionPolicy,
    *,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Flash-style attention: scan over query chunks; inner scan over kv chunks
    with running (max, denom, accum).  Peak memory O(q_chunk × kv_chunk) per
    head instead of O(S·T) — mandatory for the 32k-seq cells."""
    from repro.dist import sharding as _sh

    B, S, H, Dh = q.shape
    T = k.shape[1]
    # chunk-count selection with ragged support: the historical divisible
    # shapes keep their exact chunking (bit-stable numerics); ragged lengths
    # cap the chunk at q_chunk/kv_chunk and pad-and-mask the tail chunk, so
    # the serving scheduler can admit arbitrary-length prompts
    nq = max(1, S // q_chunk)
    nk = max(1, T // kv_chunk)
    if S % nq:
        qc = max(1, min(q_chunk, S))
        nq = -(-S // qc)
    else:
        qc = S // nq
    if T % nk:
        kc = max(1, min(kv_chunk, T))
        nk = -(-T // kc)
    else:
        kc = T // nk

    # parallelization strategy over the model axis:
    #   heads divisible  -> Ulysses (seq<->heads all-to-all), serial q-chunks
    #   heads indivisible-> sequence-parallel q chunks: the q-chunk dim is
    #                       sharded over model and chunks run under vmap
    #                       (k/v replicated across model for the inner scan)
    rules = _sh.current_rules()
    m_size = (rules.mesh.shape.get(rules.model_axis, 1)
              if rules is not None else 1)
    want_model_parallel = (
        rules is not None and rules.seq_axes and S > 1
        and rules.model_axis not in rules.batch_axes)
    heads_mode = want_model_parallel and H % m_size == 0
    if (want_model_parallel and not heads_mode and nq % m_size != 0
            and S % m_size == 0):
        # adaptive chunking: make the q-chunk count a multiple of the model
        # axis so the chunk dim can shard (e.g. S=4096, m=16: nq 4 -> 16)
        cand = m_size * max(1, nq // m_size)
        if S % cand == 0:
            nq, qc = cand, S // cand
    seq_mode = (want_model_parallel and not heads_mode and nq % m_size == 0
                and S == nq * qc)

    if heads_mode:
        q = _sh.constrain(q, "attn_heads")
        k = _sh.constrain(k, "attn_heads")
        v = _sh.constrain(v, "attn_heads")
    scale = 1.0 / jnp.sqrt(Dh)

    S_pad, T_pad = nq * qc, nk * kc
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))

    mode_l = policy.mode("attn_qk")    # alias: attn_logits (core/policy.py)
    mode_o = policy.mode("attn_pv")    # alias: attn_out
    bwd = policy.bwd_kwargs("attn_qk")
    bwd_o = policy.bwd_kwargs("attn_pv")

    # (B, S_pad, H, Dh) -> (nq, B, H, qc, Dh)
    qr = q.reshape(B, nq, qc, H, Dh).transpose(1, 0, 3, 2, 4) * scale
    kr = k.reshape(B, nk, kc, H, Dh).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(B, nk, kc, H, Dh).transpose(1, 0, 3, 2, 4)

    q_pos = q_offset + jnp.arange(S_pad).reshape(nq, qc)
    k_pos = jnp.arange(T_pad).reshape(nk, kc)

    def per_q_chunk(qi, q_blk):
        def per_kv_chunk(carry, inp):
            m_run, d_run, acc = carry
            ki, k_blk, v_blk = inp
            logits = mp_matmul(
                q_blk, jnp.swapaxes(k_blk, -1, -2), mode_l, **bwd
            )  # (B, H, qc, kc)
            if causal:
                mask = q_pos[qi][:, None] >= k_pos[ki][None, :]
                if T_pad != T:  # padded tail keys are not real positions
                    mask = mask & (k_pos[ki][None, :] < T)
                logits = jnp.where(mask, logits, NEG_INF)
            elif T_pad != T:
                logits = jnp.where(k_pos[ki][None, :] < T, logits, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            d_new = d_run * alpha + jnp.sum(p, axis=-1)
            pv = mp_matmul(p.astype(jnp.float32), v_blk, mode_o, **bwd_o)
            acc = acc * alpha[..., None] + pv
            return (m_new, d_new, acc), None

        m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
        d0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, Dh), jnp.float32)
        (m, d, acc), _ = jax.lax.scan(
            per_kv_chunk, (m0, d0, a0),
            (jnp.arange(nk), kr, vr),
        )
        return acc / jnp.maximum(d[..., None], 1e-30)

    if seq_mode:
        # shard the chunk dim over the model axis and vmap: each device runs
        # its own nq/m chunks in parallel; the inner kv scan stays serial
        # (memory-bounded), k/v are replicated across model by GSPMD.
        qr = jax.lax.with_sharding_constraint(
            qr, rules.sharding(rules.model_axis, rules.batch,
                               None, None, None))
        out = jax.vmap(per_q_chunk)(jnp.arange(nq), qr)
        out = jax.lax.with_sharding_constraint(
            out, rules.sharding(rules.model_axis, rules.batch,
                                None, None, None))
    else:
        out = jax.lax.map(lambda args: per_q_chunk(*args),
                          (jnp.arange(nq), qr))
    # (nq, B, H, qc, Dh) -> (B, S_pad, H, Dh); drop padded query rows
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, S_pad, H, Dh)
    return out[:, :S] if S_pad != S else out


def _self_attention(
    q: jax.Array,            # (B, S, H, Dh), H already GQA-repeated
    k: jax.Array,
    v: jax.Array,
    policy: PrecisionPolicy,
    *,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Route full self-attention: the fused flash path (``mp_attention`` —
    QK^T and P·V at independently resolved formats, P never in HBM on the
    Pallas backends) when eligible, else the chunk-scan.

    Chunk-scan fallbacks: AUTO formats (per-op operand analysis needs the
    per-chunk ``mp_matmul`` calls), active sharding rules (Ulysses /
    sequence-parallel chunk layouts own the partitioning), and very long
    sequences (the fused VJP rematerializes the (B, H, S, T) probability
    matrix densely in the backward)."""
    from repro.dist import sharding as _sh

    B, S, H, Dh = q.shape
    T = k.shape[1]
    fmt_qk = policy.mode("attn_qk")
    fmt_pv = policy.mode("attn_pv")
    if (is_auto(fmt_qk) or is_auto(fmt_pv)
            or _sh.current_rules() is not None
            or B * H * S * T > FUSED_P_MAX_ELEMENTS):
        return chunked_attention(q, k, v, policy, causal=causal,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk)
    bwd_qk = policy.bwd_kwargs("attn_qk")
    bwd_pv = policy.bwd_kwargs("attn_pv")
    return mp_attention(
        q, k, v, fmt_qk, fmt_pv, causal=causal,
        dgrad_qk_mode=bwd_qk["dgrad_mode"],
        wgrad_qk_mode=bwd_qk["wgrad_mode"],
        dgrad_pv_mode=bwd_pv["dgrad_mode"],
        wgrad_pv_mode=bwd_pv["wgrad_mode"])


def gqa_forward(
    params: dict,
    x: jax.Array,            # (B, S, D)
    dims: AttnDims,
    policy: PrecisionPolicy,
    *,
    positions: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Full attention block.  Training/prefill when cache is None or S>1;
    single-token decode updates the cache in place (dynamic_update_slice)."""
    B, S, D = x.shape
    h, hk, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    mode_qkv = policy.mode("qkv")
    bwd = policy.bwd_kwargs("qkv")

    lanes = lanes_lib.current_lanes()
    if lanes is not None:
        # partitioned-lane mixed decode: per-branch masked matmuls at each
        # slot's own qkv format under the batch envelope (one launch)
        env, ln, lo = lanes.for_class("qkv")
        q, k, v = dispatch_lib.mixed_fused_proj(
            x, (params["wq"], params["wk"], params["wv"]), env, ln, lo)
    else:
        # one fused projection group: x is read + limb-decomposed once for
        # all three (GQA widths concat along N in the ops layer — DESIGN.md
        # §4)
        q, k, v = mp_qkv_proj(x, params["wq"], params["wk"], params["wv"],
                              mode_qkv, **bwd)
    q = q.reshape(B, S, h, dh)
    k = k.reshape(B, S, hk, dh)
    v = v.reshape(B, S, hk, dh)

    if positions is None:
        if cache is not None:
            base = cache.length  # scalar, or (B,) for paged per-slot lengths
            base = base[:, None] if getattr(base, "ndim", 0) else base
            positions = base + jnp.arange(S)[None, :]
        else:
            positions = jnp.arange(S)[None, :]
        positions = jnp.broadcast_to(positions, (B, S))

    if dims.rope_theta > 0:
        q = apply_rope(q, positions, dims.rope_theta, dims.rope_fraction)
        k = apply_rope(k, positions, dims.rope_theta, dims.rope_fraction)

    new_cache = None
    if isinstance(cache, PagedKVCache):
        new_cache = _paged_write(cache, k, v, positions)
        if S == 1:
            out = _paged_decode_attention(q, new_cache, dims, policy)
        else:
            # paged prefill is always into a fresh slot (scheduler invariant:
            # per-slot length == 0), so attention is plain self-attention
            # over the just-computed K/V — nothing to gather from the pool
            kk = _repeat_kv(k, h // hk)
            vv = _repeat_kv(v, h // hk)
            out = _self_attention(q, kk, vv, policy, causal=dims.causal,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk)
    elif cache is not None:
        kc = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype),
                                                 cache.length, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype),
                                                 cache.length, axis=1)
        new_cache = KVCache(kc, vc, cache.length + S)
        if S == 1:
            out = _decode_attention(q, kc, vc, new_cache.length, dims, policy)
        else:  # prefill into an empty cache: attend over the written prefix
            kk = _repeat_kv(k, h // hk)
            vv = _repeat_kv(v, h // hk)
            out = _self_attention(q, kk, vv, policy, causal=dims.causal,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk)
    else:
        kk = _repeat_kv(k, h // hk)
        vv = _repeat_kv(v, h // hk)
        out = _self_attention(q, kk, vv, policy, causal=dims.causal,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)

    if S > 1:
        from repro.dist import sharding as _sh2
        out = _sh2.constrain(out, "attn_out_seq")
    out = out.reshape(B, S, h * dh)
    if lanes is not None:
        env, ln, lo = lanes.for_class("attn_out")
        out = dispatch_lib.dispatch_mixed_matmul(out, params["wo"], env,
                                                 ln, lo)
    else:
        out = mp_dense(out, params["wo"], policy.mode("attn_out"),
                       **policy.bwd_kwargs("attn_out"))
    return out, new_cache


def _decode_attention(q, k_cache, v_cache, length, dims: AttnDims,
                      policy: PrecisionPolicy) -> jax.Array:
    """One-token attention against the cache, masked by ``length`` (scalar
    for the dense cache, (B,) per-slot for a paged micro-batch).

    Both einsums route through ``mp_matmul`` at the policy-resolved
    ``attn_qk`` / ``attn_pv`` formats (core/dispatch.py
    ``masked_decode_attention``), so decode obeys the precision policy on
    every backend — and the contractions stay plain batched matmuls on the
    ref/sharded backends, so GSPMD can still shard the cache sequence dim
    across the model axis and insert the partial-softmax collectives
    automatically (sequence-parallel decode)."""
    from repro.dist import sharding as _sh

    B, S1, h, dh = q.shape  # S1 == 1
    hk = dims.n_kv_heads
    n_rep = h // hk

    rules = _sh.current_rules()
    if rules is not None:
        m = rules.mesh.shape.get(rules.model_axis, 1)
        if h % m == 0 and hk % m == 0:
            # head-parallel decode: q heads follow the cache's head sharding
            # so attention is local per shard (no per-layer cache gather)
            q = jax.lax.with_sharding_constraint(
                q, rules.sharding(rules.batch, None, rules.model_axis, None))

    kk = _repeat_kv(k_cache.astype(jnp.float32), n_rep)  # (B, T, H, Dh)
    vv = _repeat_kv(v_cache.astype(jnp.float32), n_rep)
    return dispatch_lib.masked_decode_attention(
        q, kk, vv, length, policy.mode("attn_qk"), policy.mode("attn_pv"))


def _paged_write(cache: PagedKVCache, k: jax.Array, v: jax.Array,
                 positions: jax.Array) -> PagedKVCache:
    """Scatter S new K/V tokens per slot into the current layer's blocks of
    the paged pool, in place (k/v (B, S) + the pool's entry shape: (Hkv,
    Dh), or a latent width).

    ``positions`` (B, S) are the absolute token positions being written; each
    maps to global block ``global_table[pos // bs]`` at offset ``pos % bs``
    of the pool with its layer and block axes merged.  Positions past a
    slot's reserved blocks land in the layer's trash block (table rows are
    trash-padded; positions past the table itself are redirected to it
    explicitly — clamping them into the last column could corrupt a full
    row's final real block) or in the row's own reserved tail, which is
    rewritten before any read (serve/kv_cache.py invariants) — so the write
    needs no predication.
    """
    B, S = positions.shape
    bs = cache.block_size
    table = cache.global_table()
    max_blocks = table.shape[1]
    col = positions // bs
    blk = jnp.take_along_axis(table, jnp.clip(col, 0, max_blocks - 1), axis=1)
    blk = jnp.where(col < max_blocks, blk, cache.base)          # (B, S)
    idx = (blk.reshape(-1), (positions % bs).reshape(-1))
    pk, pv = cache.flat()
    kp = pk.at[idx].set(k.astype(pk.dtype).reshape((B * S,) + pk.shape[2:]))
    vp = pv.at[idx].set(v.astype(pv.dtype).reshape((B * S,) + pv.shape[2:]))
    return cache._replace(k=kp.reshape(cache.k.shape),
                          v=vp.reshape(cache.v.shape),
                          length=cache.length + S)


def _paged_decode_attention(q: jax.Array, cache: PagedKVCache,
                            dims: AttnDims, policy: PrecisionPolicy
                            ) -> jax.Array:
    """One-token attention against the paged pool, via the dispatch layer.

    The kernels get the whole pool by global block id and the layer's
    table in those ids, so no layer's pool is sliced out.  Pallas backends
    run the paged flash kernel: K/V blocks are DMA'd straight through the
    scalar-prefetched block table with per-slot length masking — the
    contiguous ``pool[table]`` gather never materializes.
    Other backends gather the table's columns — bounded, because the
    scheduler slices each bucket's table to its used-block count
    (serve/scheduler.py) instead of all ``max_blocks`` trash-padded columns
    — and run the policy-obeying masked einsums."""
    pk, pv = cache.flat()
    table = cache.global_table()
    lanes = lanes_lib.current_lanes()
    if lanes is not None:
        env_qk, ln_qk, lo_qk = lanes.for_class("attn_qk")
        env_pv, ln_pv, lo_pv = lanes.for_class("attn_pv")
        return dispatch_lib.dispatch_mixed_paged_attention(
            q, pk, pv, table, cache.length,
            env_qk, env_pv, ln_qk, lo_qk, ln_pv, lo_pv)
    return dispatch_lib.dispatch_paged_attention(
        q, pk, pv, table, cache.length,
        policy.mode("attn_qk"), policy.mode("attn_pv"))


def make_kv_cache(batch: int, max_seq: int, dims: AttnDims,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, dims.n_kv_heads, dims.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        length=jnp.zeros((), jnp.int32),
    )
