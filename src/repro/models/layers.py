"""Shared layer primitives.  Every dense contraction routes through the
multi-precision matmul so the whole network obeys one PrecisionPolicy."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as dispatch_lib
from repro.core import lanes as lanes_lib
from repro.core.mpmatmul import mp_dense, mp_swiglu
from repro.core.policy import PrecisionPolicy


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * weight).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * weight + bias).astype(dt)


def swiglu_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array, policy: PrecisionPolicy,
               op_class: str = "ffn") -> jax.Array:
    """LLaMA-style gated MLP: down( silu(x@gate) * (x@up) ).

    The gate/up pair runs as ONE fused projection (x read and
    limb-decomposed once, the silu-gate combine applied in the kernel's
    flush — DESIGN.md §4), so the g/u intermediates never round-trip HBM."""
    lanes = lanes_lib.current_lanes()
    if lanes is not None:
        # partitioned-lane mixed decode: every slot runs this MLP at its own
        # format inside one launch (per-branch masked matmuls, same epilogue)
        env, ln, lo = lanes.for_class(op_class)
        h = dispatch_lib.mixed_fused_proj(x, (w_gate, w_up), env, ln, lo,
                                          epilogue="swiglu")
        return dispatch_lib.dispatch_mixed_matmul(h, w_down, env, ln, lo)
    mode = policy.mode(op_class)
    bwd = policy.bwd_kwargs(op_class)
    h = mp_swiglu(x, w_gate, w_up, mode, **bwd)
    return mp_dense(h, w_down, mode, **bwd)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """Token embedding lookup (gather; sharding-friendly on the D dim)."""
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, w_head: jax.Array, policy: PrecisionPolicy) -> jax.Array:
    """LM head: (..., D) @ (D, V) at the logits mode (precision-sensitive)."""
    lanes = lanes_lib.current_lanes()
    if lanes is not None:
        env, ln, lo = lanes.for_class("lm_head")
        return dispatch_lib.dispatch_mixed_matmul(x, w_head, env, ln, lo)
    return mp_dense(x, w_head, policy.mode("lm_head"),
                    **policy.bwd_kwargs("lm_head"))


# --------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 · mscale · ln(factor) + 1``
    (1 when the context is not stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies of a ``dim``-wide rotary part (HF
    ``DeepseekV2YarnRotaryEmbedding``): pair i keeps its own frequency
    ``theta^(-2i/dim)`` below the correction range's low end, takes it
    divided by ``factor`` above its high end, and a linear blend between.
    The range is where a pair turns between ``beta_fast`` and ``beta_slow``
    times over ``original_max_pos`` positions."""
    def turn_dim(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float32)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    keep = 1.0 - ramp
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               fraction: float = 1.0, inv_freq=None,
               cos_scale: float = 1.0) -> jax.Array:
    """Rotary embedding on the leading ``fraction`` of the head dim.

    x: (B, S, H, Dh); positions: (B, S).  fraction=0.5 gives ChatGLM's
    2D-RoPE layout (first half rotary, second half pass-through).
    ``inv_freq`` (rot/2,) replaces the plain ``theta`` frequencies (YaRN),
    and ``cos_scale`` multiplies cos and sin."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = (rope_frequencies(rot, theta) if inv_freq is None
             else jnp.asarray(inv_freq, jnp.float32))          # (rot/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, rot/2)
    cos = jnp.cos(angles)[..., None, :]                        # (B, S, 1, rot/2)
    sin = jnp.sin(angles)[..., None, :]
    if cos_scale != 1.0:
        cos, sin = cos * cos_scale, sin * cos_scale
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < dh else out


# --------------------------------------------------------------- init utils
def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)
