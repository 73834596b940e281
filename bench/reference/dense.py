"""Plain float32 reference of the dense GQA transformer the cells serve.

One sequence at a time, the whole forward pass over its tokens, in
``jax.numpy`` with no kernels, cache or batching.  It follows the
published decoder block of both configurations:

    h = embed[tokens]
    per layer:  a = rmsnorm(h) ; h += wo · attn(rope(a·wq), rope(a·wk), a·wv)
                m = rmsnorm(h) ; h += w_down · (silu(m·w_gate) * (m·w_up))
    logits = rmsnorm(h) · lm_head

with causal softmax attention scaled by 1/sqrt(head_dim), query head i
reading key/value head i // (n_heads / n_kv_heads), and RoPE on the leading
``rope_fraction`` of each head.  Departures from the published models, the
same in the serving program: rotary pairs are (j, j + rot/2) within the
rotary part (ChatGLM3 pairs adjacent dims, the same model under a
permutation of the wq/wk columns), and ChatGLM3's QKV bias is left out.

The gap that decides ``correct`` (``gaps``) takes the lm_head of the
compared tokens in float64 from the float32 final hidden state, so the
reference's own float32 rounding of the logits does not enter it.  The
dense block has no discrete choice inside it (no routing), so this
reference decides every position: ``gaps`` returns no ``compared`` mask.
``prefill_flops`` and ``decode_flops`` are the family's model FLOPs, which
``step_mfu`` reads.

Every matmul runs at one ``precision``: ``highest`` (float32, the
reference), or one of the lower controls: ``high`` (three bf16 passes, hi·hi
+ hi·lo + lo·hi), ``bf16`` (one pass) and ``fp8`` (e4m3 operands, each
scaled by its largest magnitude).  The lower ones are computed by hand, so
they mean the same on every backend.
"""
from __future__ import annotations

import functools

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.roofline import layer_matmuls, lm_head_matmul

PRECISIONS = ("highest", "high", "bf16", "fp8")
_F8_MAX = 448.0


def _split_bf16(x):
    """x = hi + lo (to float32 rounding), hi and lo bfloat16.  hi is x
    rounded to bfloat16 (nearest, ties to even) on the bits, because XLA on
    a TPU may fold a float32 -> bfloat16 -> float32 round trip into the
    identity, which makes every lo zero and ``high`` one bf16 pass."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u32 = functools.partial(jnp.asarray, dtype=jnp.uint32)
    bits = (bits + u32(0x7FFF) + ((bits >> 16) & u32(1))) & u32(0xFFFF0000)
    hi32 = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi32.astype(jnp.bfloat16), (x - hi32).astype(jnp.bfloat16)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return q, scale


def mm(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32 out, at one precision."""
    f32 = jnp.float32
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)
    one = functools.partial(jnp.einsum, spec, preferred_element_type=f32)
    if precision == "high":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        return one(ah, bl) + one(al, bh) + one(ah, bh)
    if precision == "bf16":
        return one(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if precision == "fp8":
        (aq, sa), (bq, sb) = _fp8(a), _fp8(b)
        return one(aq, bq) * (sa * sb)
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta: float, fraction: float):
    """RoPE on the leading ``fraction`` of the last dim of x (T, H, dh)."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    half = rot // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions[:, None].astype(jnp.float32) * freqs     # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _layer(model: dict, precision: str, h, lp):
    T, d = h.shape
    H, Hk = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // H
    eps, theta, frac = (model["norm_eps"], model["rope_theta"],
                        model["rope_fraction"])
    pos = jnp.arange(T)
    a = rms_norm(h, lp["ln1"]["w"], eps)
    at = lp["attn"]
    q = mm("td,dn->tn", a, at["wq"], precision).reshape(T, H, dh)
    k = mm("td,dn->tn", a, at["wk"], precision).reshape(T, Hk, dh)
    v = mm("td,dn->tn", a, at["wv"], precision).reshape(T, Hk, dh)
    q, k = rope(q, pos, theta, frac), rope(k, pos, theta, frac)
    rep = H // Hk
    q = q.reshape(T, Hk, rep, dh)
    s = mm("tgrd,sgd->grts", q, k, precision) * dh ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("grts,sgd->tgrd", p, v, precision).reshape(T, H * dh)
    h = h + mm("tn,nd->td", o, at["wo"], precision)
    m = rms_norm(h, lp["ln2"]["w"], eps)
    mp = lp["mlp"]
    g = mm("td,df->tf", m, mp["w_gate"], precision)
    u = mm("td,df->tf", m, mp["w_up"], precision)
    return h + mm("tf,fd->td", jax.nn.silu(g) * u, mp["w_down"], precision)


def final_hidden(params: dict, tokens, model: dict,
                 precision: str = "highest"):
    """(T, d) float32 output of the final norm, the lm_head's input."""
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(
        lambda h, lp: (_layer(model, precision, h, lp), None), h,
        params["layers"])
    return rms_norm(h, params["ln_final"]["w"], model["norm_eps"])


def logits(params: dict, tokens, model: dict, precision: str = "highest"):
    """(T, vocab) float32 logits of every position of one sequence."""
    h = final_hidden(params, tokens, model, precision)
    out = mm("td,dv->tv", h, params["lm_head"]["w"], precision)
    return out[:, : model["vocab"]]


TOP_K = 4   # reference candidates per position for the float64 lm_head


@functools.partial(jax.jit, static_argnames=("model_items", "precision"))
def _read(params, tokens, model_items, precision):
    model = dict(model_items)
    h = final_hidden(params, tokens, model, "highest")
    ref = mm("td,dv->tv", h, params["lm_head"]["w"], "highest")
    _, top = jax.lax.top_k(ref[:, : model["vocab"]], TOP_K)
    if precision == "highest":
        pick = top[:, 0]
    else:
        pick = jnp.argmax(logits(params, tokens, model, precision), axis=-1)
    return h, top, pick.astype(jnp.int32)


def exact_logits(h, head_t, ids):
    """float64 logits ``h[t] . head[:, ids[t, c]]`` (numpy): the
    reference's lm_head without float32 rounding, at chosen tokens only.
    ``head_t`` is the lm_head transposed to (vocab, d)."""
    h64 = np.asarray(h, np.float64)
    return np.stack([np.einsum("td,td->t", h64,
                               head_t[ids[:, c]].astype(np.float64))
                     for c in range(ids.shape[1])], axis=1)


def gaps(params: dict, head_t, tokens, cand, model: dict,
         precision: str = "highest"):
    """Per position t: how far the chosen next token's reference logit lies
    below the reference's best, both in float64 over the reference's
    float32 top candidates, their lm_head twins (``id ^ 1``) and the chosen
    token.  At ``highest`` the chosen token is ``cand[t]`` (what the
    program served); at a lower precision it is that precision's own argmax
    (the control).  Returns (gaps, chosen) as numpy arrays: every position
    is decided, so there is no third array (``compared``)."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if not isinstance(v, (dict, list))))
    h, top, pick = _read(params, tokens, items, precision)
    chosen = np.asarray(cand if precision == "highest" else pick, np.int64)
    top = np.asarray(top, np.int64)
    ids = np.concatenate([top, top ^ 1, chosen[:, None],
                          chosen[:, None] ^ 1], axis=1)
    ids = np.minimum(ids, model["vocab"] - 1)
    ex = exact_logits(h, head_t, ids)
    return ex.max(axis=1) - ex[:, 2 * TOP_K], chosen


def _matmul_params(model: dict) -> Tuple[int, int]:
    layers = model["n_layers"] * sum(k * n for k, n in layer_matmuls(model))
    k, n = lm_head_matmul(model)
    return layers, k * n


def _attn_width(model: dict) -> int:
    dh = model.get("head_dim") or model["d_model"] // model["n_heads"]
    return model["n_heads"] * dh


def prefill_flops(model: dict, n: int) -> float:
    """Model FLOPs of prefilling ``n`` prompt tokens: 2 per matmul weight
    per token, causal attention at 4·L·context·d_attn per token (context t
    for position t), and the lm_head once (only the last position is
    needed)."""
    layers, head = _matmul_params(model)
    attn = 4.0 * model["n_layers"] * _attn_width(model) * n * (n + 1) / 2
    return 2.0 * layers * n + attn + 2.0 * head


def decode_flops(model: dict, context: int) -> float:
    """Model FLOPs of one decoded token attending over ``context``
    positions (itself included)."""
    layers, head = _matmul_params(model)
    return (2.0 * (layers + head)
            + 4.0 * model["n_layers"] * _attn_width(model) * context)
