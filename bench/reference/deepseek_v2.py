"""Plain float32 reference of the DeepSeek-V2 block (latent attention and
routed experts) that the DeepSeek-V2-Lite cell serves.

One sequence at a time, the whole forward pass over its tokens, in
``jax.numpy`` with no kernels, cache or batching, every matmul through
``dense.mm`` at one precision.  It follows the published model
(hf deepseek-ai/DeepSeek-V2-Lite, ``modeling_deepseek.py``):

    h = embed[tokens]
    per layer:  a = rmsnorm(h)
                q = a·w_q -> per head (q_nope 128, q_pe 64)
                c = rmsnorm(a·w_dkv) (kv_a_layernorm) ; k_pe = a·w_kr
                q_pe, k_pe = yarn_rope(q_pe), yarn_rope(k_pe)
                k_nope = c·w_uk, v = c·w_uv           (per head)
                s = (q_nope·k_nope + q_pe·k_pe) · 192^-0.5 · mscale(40, 0.707)^2
                h += w_o · (causal softmax(s) · v)
                m = rmsnorm(h)
                dense layer:  h += w_down · (silu(m·w_gate) * (m·w_up))
                MoE layer:    p = softmax(m·router) over all 64 experts (f32)
                              greedy top-6, weight = p · routed_scaling_factor
                              (no renormalisation: norm_topk_prob false)
                              h += Σ_{held e} weight_e · swiglu_e(m) + shared(m)
    logits = rmsnorm(h) · lm_head

YaRN (``DeepseekV2YarnRotaryEmbedding``): the rope dims' inverse
frequencies blend ``theta^(-2i/64)`` with it divided by ``factor`` over the
correction range of ``beta_fast``/``beta_slow`` turns in
``original_max_position_embeddings`` positions; cos and sin are scaled by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, with
``mscale(f, m) = 0.1 · m · ln f + 1``.

The expert layer holds ``n_held`` of the ``n_experts`` experts from
``first_held`` on, as one chip of an expert-parallel deployment: the router
keeps all its outputs and its top-k, and only the held experts' part of the
routed sum is computed.  Departures from the published model, the same in
the serving program (listed under ``assumed`` in the configuration file):
the rope dims pair i with i + 32 (the published code interleaves them: a
permutation of the rope columns), and the key/value projections are held as
separate matrices (a permutation of columns).

``gaps`` decides ``correct`` as ``dense.gaps`` does (float64 logits of the
compared tokens from the float32 final hidden state), and returns which
positions it decides (``compared``).  The router is a discrete choice: where
a MoE layer's 6th and 7th router logits at a position lie within
``TIE_MULTIPLE`` times the M23 bound of the router's dot products there
(``M23_BOUND · max_j Σ_i |m_i w_ij|``), and an expert held here lies within
that margin of the two (of a two-way tie, one of the two is held), the
program's route and this one's may differ, and attention carries a
difference to every later position.  Such a position and every later one of
its sequence are not compared.  The multiple is four times the widest
difference, in units of that bound, between the program's router logits
and these that ``bench/router_probe.py`` measured on the chip, rounded up
(PERF.md gives the readings).

``prefill_flops`` and ``decode_flops`` are the family's model FLOPs:
latent attention as computed (unabsorbed in prefill, weight-absorbed in
decode), the held experts' expected routed rows (top_k · n_held /
n_experts a token), the shared experts, the dense layer and the lm_head.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import TOP_K, exact_logits, mm, rms_norm

M23_BOUND = 2.0 ** -19   # the M23 format's relative error bound of one dot
TIE_MULTIPLE = 0.5       # the tie margin, in units of the router's M23 bound


def yarn_inv_freq(dim: int, theta: float, s: dict) -> np.ndarray:
    """YaRN inverse frequencies of ``dim`` rope dims (float64 -> float32)."""
    def turn_dim(rotations):
        return (dim * math.log(s["original_max_pos"] / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turn_dim(s["beta_fast"])), 0)
    high = min(math.ceil(turn_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / s["factor"] * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn(mla: dict) -> dict:
    return {"factor": mla["yarn_factor"],
            "original_max_pos": mla["yarn_original_max_pos"],
            "beta_fast": mla["yarn_beta_fast"],
            "beta_slow": mla["yarn_beta_slow"]}


def rope(x, positions, inv_freq, scale: float):
    """Half-split rotary embedding of x (T, H, dim) at ``inv_freq``."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(model: dict, precision: str, a, p):
    """Latent attention of one sequence, unabsorbed: (T, d) -> (T, d)."""
    mla = model["mla"]
    T = a.shape[0]
    H, dn, dr = mla["n_heads"], mla["qk_nope_dim"], mla["qk_rope_dim"]
    dv = mla["v_head_dim"]
    pos = jnp.arange(T)
    f = mla["yarn_factor"]
    inv = yarn_inv_freq(dr, mla["rope_theta"], _yarn(mla))
    rot = mscale(f, mla["yarn_mscale"]) / mscale(f, mla["yarn_mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * mscale(f, mla["yarn_mscale_all_dim"]) ** 2
    q = mm("td,dn->tn", a, p["w_q"], precision).reshape(T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, inv, rot)
    c = rms_norm(mm("td,dc->tc", a, p["w_dkv"], precision), p["kv_norm"],
                 mla["norm_eps"])
    k_pe = rope(mm("td,dr->tr", a, p["w_kr"], precision)[:, None, :], pos,
                inv, rot)[:, 0]
    k_nope = mm("tc,cn->tn", c, p["w_uk"], precision).reshape(T, H, dn)
    v = mm("tc,cn->tn", c, p["w_uv"], precision).reshape(T, H, dv)
    s = (mm("thd,shd->hts", q_nope, k_nope, precision)
         + mm("thd,sd->hts", q_pe, k_pe, precision)) * scale
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), v, precision)
    return mm("tn,nd->td", o.reshape(T, H * dv), p["w_o"], precision)


def swiglu(m, w_gate, w_up, w_down, precision: str):
    g = mm("td,df->tf", m, w_gate, precision)
    u = mm("td,df->tf", m, w_up, precision)
    return mm("tf,fd->td", jax.nn.silu(g) * u, w_down, precision)


def route(moe: dict, logits):
    """(T, held) weight of each held expert: its softmax probability where
    it is among the greedy top-k, else 0."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, moe["top_k"])
    if moe.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * moe.get("routed_scaling_factor", 1.0)
    held = moe["first_held"] + jnp.arange(moe["n_held"] or moe["n_experts"])
    return jnp.sum(jnp.where(top_i[:, :, None] == held, top_p[:, :, None],
                             0.0), axis=1)


def router_logits(m, router, precision: str):
    """Router logits and the M23 bound of each position's dot products."""
    logits = mm("td,de->te", m, router, precision)
    bound = M23_BOUND * jnp.max(
        mm("td,de->te", jnp.abs(m), jnp.abs(router), "highest"), axis=-1)
    return logits, bound


def near_tie(moe: dict, logits, bound):
    """Positions whose k-th and (k+1)-th router logits lie within the tie
    margin of each other, with an expert held here among those within the
    margin of the two (so, of a two-way tie, one of the two)."""
    k = moe["top_k"]
    margin = TIE_MULTIPLE * bound
    vals, _ = jax.lax.top_k(logits, k + 1)
    lk, lk1 = vals[:, k - 1:k], vals[:, k:k + 1]
    lo = moe["first_held"]
    held = logits[:, lo:lo + (moe["n_held"] or moe["n_experts"])]
    m = margin[:, None]
    near = (held >= lk1 - m) & (held <= lk + m)
    return (lk - lk1 <= m)[:, 0] & jnp.any(near, axis=-1)


def _layers(params, key):
    stack = params.get(key)
    if stack is None:
        return []
    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    return [jax.tree_util.tree_map(lambda x, i=i: x[i], stack)
            for i in range(n)]


def forward(params: dict, tokens, model: dict, precision: str = "highest"):
    """(final hidden (T, d), router logits and bounds of each MoE layer,
    ties (T,): a MoE layer's near tie at the position)."""
    eps, moe = model["norm_eps"], model["moe"]
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    for lp in _layers(params, "dense_layers"):
        h = h + attention(model, precision, rms_norm(h, lp["ln1"]["w"], eps),
                          lp["mla"])
        mp = lp["mlp"]
        h = h + swiglu(rms_norm(h, lp["ln2"]["w"], eps), mp["w_gate"],
                       mp["w_up"], mp["w_down"], precision)
    routers, ties = [], jnp.zeros(tokens.shape, bool)
    for lp in _layers(params, "layers"):
        h = h + attention(model, precision, rms_norm(h, lp["ln1"]["w"], eps),
                          lp["mla"])
        m = rms_norm(h, lp["ln2"]["w"], eps)
        e = lp["moe"]
        rl, bound = router_logits(m, e["router"], precision)
        routers.append((rl, bound))
        ties = ties | near_tie(moe, rl, bound)
        gates = route(moe, rl)
        out = jnp.zeros_like(h)
        for i in range(gates.shape[1]):
            out = out + gates[:, i:i + 1] * swiglu(
                m, e["w_gate"][i], e["w_up"][i], e["w_down"][i], precision)
        sh = e["shared"]
        h = h + out + swiglu(m, sh["w_gate"], sh["w_up"], sh["w_down"],
                             precision)
    return rms_norm(h, params["ln_final"]["w"], eps), routers, ties


def logits(params: dict, tokens, model: dict, precision: str = "highest"):
    """(T, vocab) float32 logits of every position of one sequence."""
    h, _, _ = forward(params, tokens, model, precision)
    return mm("td,dv->tv", h, params["lm_head"]["w"],
              precision)[:, : model["vocab"]]


@functools.partial(jax.jit, static_argnames=("model_json", "precision"))
def _read(params, tokens, model_json, precision):
    model = json.loads(model_json)
    h, _, ties = forward(params, tokens, model, "highest")
    ref = mm("td,dv->tv", h, params["lm_head"]["w"], "highest")
    _, top = jax.lax.top_k(ref[:, : model["vocab"]], TOP_K)
    if precision == "highest":
        pick = top[:, 0]
    else:
        pick = jnp.argmax(logits(params, tokens, model, precision), axis=-1)
    return h, top, pick.astype(jnp.int32), ties


def gaps(params: dict, head_t, tokens, cand, model: dict,
         precision: str = "highest"):
    """Per position t: how far the chosen next token's reference logit lies
    below the reference's best (as ``dense.gaps``), and whether t is
    compared: no near tie of a held expert in any MoE layer at t or before.
    Returns (gaps, chosen, compared) as numpy arrays.

    The forward runs on the sequence padded to ``max_seq`` (causal: the
    pad changes no earlier position), so one compiled program serves every
    sequence length."""
    n = len(tokens)
    padded = np.zeros(max(n, model.get("max_seq", n)), np.int32)
    padded[:n] = tokens
    h, top, pick, ties = _read(params, padded,
                               json.dumps(model, sort_keys=True), precision)
    h, top, pick, ties = (np.asarray(x)[:n] for x in (h, top, pick, ties))
    chosen = np.asarray(cand if precision == "highest" else pick, np.int64)
    top = np.asarray(top, np.int64)
    ids = np.concatenate([top, top ^ 1, chosen[:, None],
                          chosen[:, None] ^ 1], axis=1)
    ids = np.minimum(ids, model["vocab"] - 1)
    ex = exact_logits(h, head_t, ids)
    compared = np.cumsum(np.asarray(ties)) == 0
    return ex.max(axis=1) - ex[:, 2 * TOP_K], chosen, compared


# --- model FLOPs -------------------------------------------------------------

def _projections(model: dict) -> int:
    """Weights of one layer's attention projections read by every token:
    w_q, w_dkv, w_kr, w_o."""
    d, mla = model["d_model"], model["mla"]
    H, dn, dr, dv = (mla["n_heads"], mla["qk_nope_dim"], mla["qk_rope_dim"],
                     mla["v_head_dim"])
    return d * H * (dn + dr) + d * mla["kv_lora"] + d * dr + H * dv * d


def _ffn_weights(model: dict) -> int:
    """Weights of the layers' FFNs one token multiplies: the dense layers'
    FFN, and per MoE layer the router, the shared experts and the held
    experts' expected share of the top_k routed ones."""
    d, moe = model["d_model"], model["moe"]
    n_dense = model["first_k_dense"]
    shared = moe.get("shared_ff") or moe["n_shared"] * moe["expert_ff"]
    held = moe["n_held"] or moe["n_experts"]
    routed = moe["top_k"] * held / moe["n_experts"]
    per_moe = (d * moe["n_experts"] + 3 * d * shared
               + routed * 3 * d * moe["expert_ff"])
    return (n_dense * 3 * d * model["dense_ff"]
            + (model["n_layers"] - n_dense) * per_moe)


def prefill_flops(model: dict, n: int) -> float:
    """Model FLOPs of prefilling ``n`` prompt tokens: 2 per weight per token
    (the key/value up-projections too: unabsorbed), causal attention at
    2·H·(qk_head + v_head)·context per token and layer, the lm_head once."""
    mla, L = model["mla"], model["n_layers"]
    H, lora = mla["n_heads"], mla["kv_lora"]
    up = lora * H * (mla["qk_nope_dim"] + mla["v_head_dim"])
    per_token = L * (_projections(model) + up) + _ffn_weights(model)
    qk = mla["qk_nope_dim"] + mla["qk_rope_dim"]
    attn = 2.0 * L * H * (qk + mla["v_head_dim"]) * n * (n + 1) / 2
    return 2.0 * per_token * n + attn + 2.0 * model["d_model"] * model["vocab"]


def decode_flops(model: dict, context: int) -> float:
    """Model FLOPs of one decoded token over ``context`` positions, weight-
    absorbed: the query's and the output's per-head latent projections,
    attention as MQA over the latent (2·H·context·(kv_lora + rope) for the
    logits, 2·H·context·kv_lora for the context) and the lm_head."""
    mla, L = model["mla"], model["n_layers"]
    H, lora, dr = mla["n_heads"], mla["kv_lora"], mla["qk_rope_dim"]
    absorbed = H * lora * (mla["qk_nope_dim"] + mla["v_head_dim"])
    weights = (L * (_projections(model) + absorbed) + _ffn_weights(model)
               + model["d_model"] * model["vocab"])
    return 2.0 * weights + 2.0 * L * H * context * (2 * lora + dr)
