"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434) with the
compressed-latent KV cache and weight-absorbed decode path.

The key/value path projects x down to the latent ``c_kv`` (kv_lora wide),
normalises it (``kv_a_layernorm``: an RMSNorm with a gain of its own), and
up-projects it to per-head keys (nope part) and values; a shared rope key of
qk_rope dims comes straight from x.  RoPE may be YaRN-scaled
(``MLADims.yarn_factor``), which also raises the softmax scale by the
squared YaRN temperature.

Training/prefill: standard up-projected attention (latent -> per-head K/V).
Decode: the cache stores only the normed ``c_kv`` + the rotated ``k_rope``
per token — 576 floats/token for DeepSeek-V2 instead of 2·H·Dh — and the K
up-projection is *absorbed* into the query, so attention runs as MQA over
the latent: ``q_lat[h] = q_nope[h] · W_uk[h]^T``, logits ``q_lat · c_kv +
q_rope · k_rope``, context ``p · c_kv``, output ``context[h] · W_uv[h]``.
The dense cache and the serving scheduler's paged latent pool (``k`` holds
``c_kv``, ``v`` holds ``k_rope``) run the same math.

All projections route through mp_matmul (the framework's reconfigurable
multiplier), and both latent attention contractions run at the policy's
``attn_qk`` / ``attn_pv`` formats.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import dispatch as dispatch_lib
from repro.core.mpmatmul import mp_dense, mp_fused_proj, mp_matmul
from repro.core.policy import PrecisionPolicy
from repro.models.attention import _paged_write, _self_attention
from repro.models.layers import (apply_rope, dense_init, rms_norm,
                                 yarn_frequencies, yarn_mscale)
from repro.serve.kv_cache import PagedKVCache


class MLACache(NamedTuple):
    c_kv: jax.Array     # (B, S_max, kv_lora)
    k_rope: jax.Array   # (B, S_max, qk_rope)
    length: jax.Array   # scalar int32


@dataclasses.dataclass(frozen=True)
class MLADims:
    d_model: int
    n_heads: int
    kv_lora: int           # latent width (512 for DeepSeek-V2)
    q_lora: int = 0        # 0 = no query compression
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6         # the latent's RMSNorm (kv_a_layernorm)
    # YaRN (HF ``rope_scaling`` of type "yarn"); factor 0 is plain RoPE
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def rope_inv_freq(self):
        """The rope dims' inverse frequencies: YaRN's blend, or None for
        plain RoPE at ``rope_theta``."""
        if not self.yarn_factor:
            return None
        return yarn_frequencies(self.qk_rope_dim, self.rope_theta,
                                self.yarn_factor, self.yarn_original_max_pos,
                                self.yarn_beta_fast, self.yarn_beta_slow)

    @property
    def rope_cos_scale(self) -> float:
        if not self.yarn_factor:
            return 1.0
        return (yarn_mscale(self.yarn_factor, self.yarn_mscale)
                / yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-0.5``, times YaRN's ``mscale(all_dim)^2``."""
        scale = self.qk_head_dim ** -0.5
        if self.yarn_factor and self.yarn_mscale_all_dim:
            scale *= yarn_mscale(self.yarn_factor,
                                 self.yarn_mscale_all_dim) ** 2
        return scale


def init_mla_params(key, dims: MLADims, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    d, h = dims.d_model, dims.n_heads
    p = {
        # KV path: down to latent, up to per-head K(nope)/V
        "w_dkv": dense_init(ks[0], d, dims.kv_lora, dtype),
        "kv_norm": jnp.ones((dims.kv_lora,), dtype),
        "w_uk": dense_init(ks[1], dims.kv_lora, h * dims.qk_nope_dim, dtype),
        "w_uv": dense_init(ks[2], dims.kv_lora, h * dims.v_head_dim, dtype),
        # decoupled shared rope key (one per token, shared across heads)
        "w_kr": dense_init(ks[3], d, dims.qk_rope_dim, dtype),
        "w_o": dense_init(ks[4], h * dims.v_head_dim, d, dtype),
    }
    if dims.q_lora > 0:
        p["w_dq"] = dense_init(ks[5], d, dims.q_lora, dtype)
        p["w_uq"] = dense_init(ks[6], dims.q_lora, h * dims.qk_head_dim, dtype)
    else:
        p["w_q"] = dense_init(ks[7], d, h * dims.qk_head_dim, dtype)
    return p


def _input_projections(params, x, dims: MLADims, policy: PrecisionPolicy):
    """The three/four projections that consume ``x``, as ONE fused group.

    q (or its LoRA down-projection), the KV latent, and the shared rope key
    all contract the same activation — mp_fused_proj reads and
    limb-decomposes x once for the whole group (DESIGN.md §4).  Returns
    (q_nope, q_rope, c_kv, k_rope) with rope NOT yet applied.
    """
    B, S, _ = x.shape
    mode, bwd = policy.mode("qkv"), policy.bwd_kwargs("qkv")
    wq = params["w_dq"] if dims.q_lora > 0 else params["w_q"]
    q, c_kv, k_rope = mp_fused_proj(
        x, (wq, params["w_dkv"], params["w_kr"]), mode, **bwd)
    if dims.q_lora > 0:
        q = mp_dense(q, params["w_uq"], mode, **bwd)
    q = q.reshape(B, S, dims.n_heads, dims.qk_head_dim)
    return (q[..., : dims.qk_nope_dim], q[..., dims.qk_nope_dim:],
            c_kv, k_rope)


def _rope(x: jax.Array, positions: jax.Array, dims: MLADims) -> jax.Array:
    return apply_rope(x, positions, dims.rope_theta,
                      inv_freq=dims.rope_inv_freq(),
                      cos_scale=dims.rope_cos_scale)


def head_matrices(w, dims: MLADims, key: str):
    """The absorbed decode's per-head matrices of a latent up-projection:
    ``W_uk[h]^T`` (qk_nope, kv_lora) for ``w_uk``, ``W_uv[h]`` (kv_lora,
    v_head) for ``w_uv``.  A prelimbed params tree stores them so, one limb
    stack per head (serve/engine.prelimb_dense_params)."""
    if isinstance(w, (list, tuple)):
        return w
    dn = dims.qk_nope_dim if key == "w_uk" else dims.v_head_dim
    heads = [w[:, h * dn:(h + 1) * dn] for h in range(dims.n_heads)]
    return [m.T for m in heads] if key == "w_uk" else heads


def _per_head(x: jax.Array, ws, mode) -> jax.Array:
    """x (B, H, K) · ws[h] (K, N) for every head h -> (B, H, N)."""
    return jnp.stack([mp_matmul(x[:, h], w, mode) for h, w in enumerate(ws)],
                     axis=1)


def _absorbed_decode(params, q_nope, q_rope, attend, dims: MLADims,
                     policy: PrecisionPolicy) -> jax.Array:
    """Weight-absorbed single-token decode in latent space, at the
    policy's formats: the query absorbs ``W_uk`` head by head (qkv),
    ``attend(q_lat, q_rope)`` runs MQA of the heads over the latent cache
    (attn_qk / attn_pv) and returns the context (B, H, kv_lora), which
    ``W_uv`` lifts head by head (qkv) before ``w_o`` (attn_out)."""
    B = q_nope.shape[0]
    mode = policy.mode("qkv")
    q_lat = _per_head(q_nope[:, 0], head_matrices(params["w_uk"], dims,
                                                  "w_uk"), mode)
    ctx = attend(q_lat, q_rope[:, 0])
    out = _per_head(ctx, head_matrices(params["w_uv"], dims, "w_uv"), mode)
    out = out.reshape(B, 1, dims.n_heads * dims.v_head_dim)
    return mp_dense(out, params["w_o"], policy.mode("attn_out"),
                    **policy.bwd_kwargs("attn_out"))


def mla_forward(
    params: dict,
    x: jax.Array,
    dims: MLADims,
    policy: PrecisionPolicy,
    *,
    positions: Optional[jax.Array] = None,
    cache=None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
):
    """MLA block.  ``cache`` is None (training), an :class:`MLACache`
    (dense decode cache) or a paged latent pool view
    (:class:`~repro.serve.kv_cache.PagedKVCache`, ``k`` the c_kv pool and
    ``v`` the k_rope pool).  Returns (out, updated cache or None)."""
    B, S, _ = x.shape
    h = dims.n_heads
    mode, bwd = policy.mode("qkv"), policy.bwd_kwargs("qkv")
    mode_qk, mode_pv = policy.mode("attn_qk"), policy.mode("attn_pv")

    if positions is None:
        base = cache.length if cache is not None else 0
        base = base[:, None] if getattr(base, "ndim", 0) else base
        positions = jnp.broadcast_to(base + jnp.arange(S)[None, :], (B, S))

    q_nope, q_rope, c_kv, k_rope = _input_projections(params, x, dims, policy)
    c_kv = rms_norm(c_kv, params["kv_norm"], dims.norm_eps)
    q_rope = _rope(q_rope, positions, dims)
    k_rope = _rope(k_rope[:, :, None, :], positions, dims)[:, :, 0, :]

    new_cache = None
    if isinstance(cache, PagedKVCache):
        new_cache = _paged_write(cache, c_kv, k_rope, positions)
        if S == 1:
            def attend(q_lat, q_r):
                return dispatch_lib.dispatch_latent_paged_attention(
                    q_lat, q_r, *new_cache.flat(), new_cache.global_table(),
                    new_cache.length, mode_qk, mode_pv,
                    scale=dims.softmax_scale)

            return (_absorbed_decode(params, q_nope, q_rope, attend, dims,
                                     policy), new_cache)
        # paged prefill is always into a fresh slot (scheduler invariant),
        # so attention is plain self-attention over the prompt
    elif cache is not None:
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache.c_kv, c_kv.astype(cache.c_kv.dtype), cache.length, axis=1)
        krc = jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, k_rope.astype(cache.k_rope.dtype), cache.length,
            axis=1)
        new_cache = MLACache(ckv, krc, cache.length + S)
        if S == 1:
            def attend(q_lat, q_r):
                return dispatch_lib.masked_latent_attention(
                    q_lat, q_r, ckv, krc, new_cache.length, mode_qk, mode_pv,
                    scale=dims.softmax_scale)

            return (_absorbed_decode(params, q_nope, q_rope, attend, dims,
                                     policy), new_cache)

    # train / prefill: up-project latent to per-head K, V (unabsorbed) —
    # both contract c_kv, so they share one fused A decomposition too
    k_nope, v = mp_fused_proj(c_kv, (params["w_uk"], params["w_uv"]),
                              mode, **bwd)
    k_nope = k_nope.reshape(B, S, h, dims.qk_nope_dim)
    v = v.reshape(B, S, h, dims.v_head_dim)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, S, h, dims.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # the attention kernels scale by qk_head_dim^-0.5: fold YaRN's
    # temperature into the query
    temp = dims.softmax_scale * dims.qk_head_dim ** 0.5
    if temp != 1.0:
        q = q * temp
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    # pad V's head dim up to the QK head dim so one attention kernel serves
    # both (values ignore the pad after the contraction)
    pad = dims.qk_head_dim - dims.v_head_dim
    v_p = jnp.pad(v, [(0, 0), (0, 0), (0, 0), (0, pad)]) if pad > 0 else v
    out = _self_attention(q, k, v_p, policy, causal=True,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out[..., : dims.v_head_dim]
    if S > 1:
        from repro.dist import sharding as _sh
        out = _sh.constrain(out, "attn_out_seq")
    out = out.reshape(B, S, h * dims.v_head_dim)
    out = mp_dense(out, params["w_o"], policy.mode("attn_out"),
                   **policy.bwd_kwargs("attn_out"))
    return out, new_cache


def make_mla_cache(batch: int, max_seq: int, dims: MLADims,
                   dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((batch, max_seq, dims.kv_lora), dtype),
        k_rope=jnp.zeros((batch, max_seq, dims.qk_rope_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )
