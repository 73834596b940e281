"""Unified model stack for all assigned architecture families.

Families:
  dense   — GQA or MLA attention + swiglu MLP          (llama/mistral/chatglm/minicpm3)
  moe     — attention + (shared + routed experts) FFN  (deepseek-v2/-lite)
  ssm     — Mamba2 SSD mixer only                      (mamba2-130m)
  hybrid  — Mamba2 layers + ONE shared attention block invoked every
            ``hybrid_attn_every`` layers with per-invocation LoRA (zamba2)
  vlm     — dense backbone; precomputed patch embeddings prepended (stub
            frontend per spec)                          (llava-next)
  audio   — encoder-only bidirectional; precomputed frame embeddings in,
            frame-level cluster logits out              (hubert)

Layers are *scanned* with stacked params (compile-time O(1) in depth) and
rematerialized (jax.checkpoint) — both mandatory at 60-88 layers.  Serving
decode on prelimbed weights passes per-layer trees instead, and the layers
unroll (``_scan_layers``).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.policy import PrecisionPolicy
from repro.dist import sharding
from repro.models import attention as attn_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import AttnDims, KVCache
from repro.models.layers import (dense_init, embed, embed_init, rms_norm,
                                 swiglu_mlp, unembed)
from repro.models.mla import MLACache
from repro.models.ssm import SSMCache
from repro.serve.kv_cache import PagedKVCache


# =========================================================================
# parameter init
# =========================================================================
def _attn_dims(cfg: ModelConfig) -> AttnDims:
    return AttnDims(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        rope_fraction=cfg.rope_fraction, causal=not cfg.encoder_only,
    )


def _init_dense_layer(key, cfg: ModelConfig, ff: int, dtype) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d = cfg.d_model
    if cfg.mla is not None:
        attn = {"mla": mla_lib.init_mla_params(k1, cfg.mla, dtype)}
    else:
        attn = {"attn": attn_lib.init_attn_params(k1, _attn_dims(cfg), dtype)}
    return {
        **attn,
        "mlp": {
            "w_gate": dense_init(k2, d, ff, dtype),
            "w_up": dense_init(k3, d, ff, dtype),
            "w_down": dense_init(k4, ff, d, dtype),
        },
        "ln1": {"w": jnp.ones((d,), dtype)},
        "ln2": {"w": jnp.ones((d,), dtype)},
    }


def _init_moe_layer(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    if cfg.mla is not None:
        attn = {"mla": mla_lib.init_mla_params(k1, cfg.mla, dtype)}
    else:
        attn = {"attn": attn_lib.init_attn_params(k1, _attn_dims(cfg), dtype)}
    return {
        **attn,
        "moe": moe_lib.init_moe_params(k2, cfg.moe, dtype),
        "ln1": {"w": jnp.ones((d,), dtype)},
        "ln2": {"w": jnp.ones((d,), dtype)},
    }


def _init_ssm_layer(key, cfg: ModelConfig, dtype) -> dict:
    return {
        "ssm": ssm_lib.init_ssm_params(key, cfg.ssm, dtype),
        "ln1": {"w": jnp.ones((cfg.d_model,), dtype)},
    }


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    ke, kl, kh, ks = jax.random.split(key, 4)
    d = cfg.d_model
    params: Dict[str, Any] = {}
    if cfg.family != "audio":
        params["embed"] = {"table": embed_init(ke, cfg.padded_vocab, d, dtype)}

    if cfg.family in ("dense", "vlm"):
        params["layers"] = _stack_init(
            lambda k: _init_dense_layer(k, cfg, cfg.d_ff, dtype), kl,
            cfg.n_layers)
    elif cfg.family == "audio":
        params["layers"] = _stack_init(
            lambda k: _init_dense_layer(k, cfg, cfg.d_ff, dtype), kl,
            cfg.n_layers)
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            params["dense_layers"] = _stack_init(
                lambda k: _init_dense_layer(k, cfg, cfg.dense_ff or cfg.d_ff,
                                            dtype), kh, cfg.first_k_dense)
        params["layers"] = _stack_init(
            lambda k: _init_moe_layer(k, cfg, dtype), kl,
            cfg.n_layers - cfg.first_k_dense)
    elif cfg.family == "ssm":
        params["layers"] = _stack_init(
            lambda k: _init_ssm_layer(k, cfg, dtype), kl, cfg.n_layers)
    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // every
        # stacked mamba layers, grouped (G, every, ...)
        flat = _stack_init(lambda k: _init_ssm_layer(k, cfg, dtype), kl,
                           cfg.n_layers)
        params["layers"] = jax.tree_util.tree_map(
            lambda x: x.reshape((n_groups, every) + x.shape[1:]), flat)
        # ONE shared attention+mlp block
        params["shared_block"] = _init_dense_layer(kh, cfg, cfg.d_ff, dtype)
        if cfg.hybrid_lora_rank:
            r = cfg.hybrid_lora_rank
            dh = cfg.resolved_head_dim

            def lora_pair(k, dout):
                ka, kb = jax.random.split(k)
                return {"a": dense_init(ka, d, r, dtype, scale=0.01),
                        "b": jnp.zeros((r, dout), dtype)}

            def group_lora(k):
                kq, ko = jax.random.split(k)
                return {"q": lora_pair(kq, cfg.n_heads * dh),
                        "o": lora_pair(ko, d)}

            params["shared_lora"] = _stack_init(group_lora, ks, n_groups)
    else:
        raise ValueError(cfg.family)

    kf, kv = jax.random.split(ks)
    params["ln_final"] = {"w": jnp.ones((d,), dtype)}
    head_name = "ctc_head" if cfg.family == "audio" else "lm_head"
    params[head_name] = {"w": dense_init(kv, d, cfg.padded_vocab, dtype)}
    return params


# =========================================================================
# layer bodies
# =========================================================================
def _attn_block(lp, h, cfg: ModelConfig, policy, positions, cache, lora=None):
    if cfg.mla is not None:
        return mla_lib.mla_forward(
            lp["mla"], h, cfg.mla, policy, positions=positions, cache=cache,
            q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    p = lp["attn"]
    if lora is not None:  # zamba2 per-invocation LoRA on shared weights
        p = dict(p)
        p["wq"] = p["wq"] + lora["q"]["a"] @ lora["q"]["b"]
        p["wo"] = p["wo"] + lora["o"]["a"] @ lora["o"]["b"]
    return attn_lib.gqa_forward(
        p, h, _attn_dims(cfg), policy, positions=positions, cache=cache,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)


def _dense_layer_fwd(lp, h, cfg, policy, positions, cache, ff_class="ffn"):
    a_in = rms_norm(h, lp["ln1"]["w"], cfg.norm_eps)
    a_out, new_cache = _attn_block(lp, a_in, cfg, policy, positions, cache)
    h = sharding.constrain(h + a_out, "activations_seq")
    m_in = rms_norm(h, lp["ln2"]["w"], cfg.norm_eps)
    m = lp["mlp"]
    h = h + swiglu_mlp(m_in, m["w_gate"], m["w_up"], m["w_down"], policy,
                       op_class=ff_class)
    h = sharding.constrain(h, "activations_seq")
    return h, new_cache


def _moe_layer_fwd(lp, h, cfg, policy, positions, cache, mesh):
    a_in = rms_norm(h, lp["ln1"]["w"], cfg.norm_eps)
    a_out, new_cache = _attn_block(lp, a_in, cfg, policy, positions, cache)
    h = h + a_out
    m_in = rms_norm(h, lp["ln2"]["w"], cfg.norm_eps)
    rules = sharding.current_rules()
    kw = {}
    if rules is not None:
        kw["extra_data_axes"] = tuple(
            a for a in rules.batch_axes
            if a and a not in ("data", rules.model_axis))
        kw["tokens_on_model"] = (
            rules.model_axis in (rules.seq_axes or ())
            or rules.model_axis in rules.batch_axes)
        kw["x_pspec"] = (rules.batch,
                         (rules.seq_axes if rules.seq_axes else None))
    moe_out, aux = (moe_lib.moe_forward(lp["moe"], m_in, cfg.moe, policy,
                                        mesh=mesh, **kw)
                    if mesh is not None else
                    moe_lib.moe_forward(lp["moe"], m_in, cfg.moe, policy))
    h = h + moe_out
    h = sharding.constrain(h, "activations_seq")
    return h, new_cache, aux


def _ssm_layer_fwd(lp, h, cfg, policy, cache):
    s_in = rms_norm(h, lp["ln1"]["w"], cfg.norm_eps)
    s_out, new_cache = ssm_lib.ssm_forward(lp["ssm"], s_in, cfg.ssm, policy,
                                           cache=cache)
    h = h + s_out
    h = sharding.constrain(h, "activations_seq")
    return h, new_cache


# =========================================================================
# caches
# =========================================================================
class ModelCache(NamedTuple):
    """Stacked per-layer caches; fields unused by a family are None."""
    attn: Optional[Any] = None        # (L, ...) KVCache / MLACache
    dense_attn: Optional[Any] = None  # moe first-k-dense layers
    ssm: Optional[Any] = None         # (L, ...) or (G, every, ...) SSMCache
    shared_attn: Optional[Any] = None # hybrid: (G, ...) KVCache


def _stack_caches(make_one, n: int):
    one = make_one()
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), one)


def make_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> ModelCache:
    if cfg.encoder_only:
        raise ValueError("encoder-only archs have no decode cache")
    if cfg.family in ("dense", "vlm"):
        if cfg.mla is not None:
            mk = lambda: mla_lib.make_mla_cache(batch, max_seq, cfg.mla, dtype)
        else:
            mk = lambda: attn_lib.make_kv_cache(batch, max_seq,
                                                _attn_dims(cfg), dtype)
        return ModelCache(attn=_stack_caches(mk, cfg.n_layers))
    if cfg.family == "moe":
        if cfg.mla is not None:
            mk = lambda: mla_lib.make_mla_cache(batch, max_seq, cfg.mla, dtype)
        else:
            mk = lambda: attn_lib.make_kv_cache(batch, max_seq,
                                                _attn_dims(cfg), dtype)
        dense = (_stack_caches(mk, cfg.first_k_dense)
                 if cfg.first_k_dense else None)
        return ModelCache(
            attn=_stack_caches(mk, cfg.n_layers - cfg.first_k_dense),
            dense_attn=dense)
    if cfg.family == "ssm":
        mk = lambda: ssm_lib.make_ssm_cache(batch, cfg.ssm, jnp.float32)
        return ModelCache(ssm=_stack_caches(mk, cfg.n_layers))
    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // every
        mk_s = lambda: ssm_lib.make_ssm_cache(batch, cfg.ssm, jnp.float32)
        ssm_flat = _stack_caches(mk_s, cfg.n_layers)
        ssm_grp = jax.tree_util.tree_map(
            lambda x: x.reshape((n_groups, every) + x.shape[1:]), ssm_flat)
        mk_a = lambda: attn_lib.make_kv_cache(batch, max_seq,
                                              _attn_dims(cfg), dtype)
        return ModelCache(ssm=ssm_grp,
                          shared_attn=_stack_caches(mk_a, n_groups))
    raise ValueError(cfg.family)


# =========================================================================
# forward
# =========================================================================
def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _scan_layers(layer_fn, h, layer_params, stacked_cache, cfg):
    """Run the layer stack over ``layer_params``: one stacked tree (a
    leading layer dim on every leaf) or a sequence of per-layer trees.

    A stacked tree is scanned (``lax.scan``: compile O(1) in depth), or
    walked in an unrolled python loop when cfg.scan_layers=False (small
    smoke models debug).  A sequence is always walked in Python: each
    layer's weights are then their own buffers, which a Pallas kernel reads
    in place, where a scan would copy every layer's slice out of the stack
    on each call.  The serving engine's prelimbed decode params are such a
    sequence (serve/engine.prelimb_dense_params); training, prefill and
    AUTO serving pass stacked params and scan.  A stacked cache is sliced
    per layer and the layers' new caches stacked, as the scan's outputs
    are; a paged pool is threaded through the layers instead
    (:func:`_thread_pool`).

    ``cfg.scan_group > 1`` nests the scan: the outer scan (rematerialized)
    saves one residual carry per *group* of layers instead of per layer —
    activation memory L/g × h instead of L × h at the cost of one in-group
    forward recompute during backward (same recompute as plain per-layer
    remat).  Mandatory for the 88-layer × 12288-wide cells."""
    if isinstance(stacked_cache, PagedKVCache):
        return _thread_pool(layer_fn, h, layer_params, stacked_cache, cfg)
    per_layer = isinstance(layer_params, (list, tuple))
    aux0 = _aux_zeros()
    if cfg.scan_layers and not per_layer:
        n = jax.tree_util.tree_leaves(layer_params)[0].shape[0]

        def body(carry, xs):
            h, aux_acc = carry
            lp, lc = xs
            h, new_c, aux = layer_fn(lp, h, lc)
            aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux)
            return (h, aux_acc), new_c

        g = max(1, getattr(cfg, "scan_group", 1))
        if g > 1 and n % g == 0 and n > g:
            regroup = lambda t: jax.tree_util.tree_map(
                lambda x: x.reshape((n // g, g) + x.shape[1:]), t)
            gp = regroup(layer_params)
            gc = (regroup(stacked_cache) if stacked_cache is not None
                  else None)

            def group_body(carry, xs):
                glp, glc = xs
                (h, aux_acc), new_cs = jax.lax.scan(body, carry, (glp, glc))
                return (h, aux_acc), new_cs

            (h, aux), new_caches = jax.lax.scan(
                _maybe_remat(group_body, cfg), (h, aux0), (gp, gc))
            if new_caches is not None:
                new_caches = jax.tree_util.tree_map(
                    lambda x: x.reshape((n,) + x.shape[2:]), new_caches)
            return h, aux, new_caches

        (h, aux), new_caches = jax.lax.scan(
            _maybe_remat(body, cfg), (h, aux0), (layer_params, stacked_cache))
        return h, aux, new_caches
    # unrolled
    n = (len(layer_params) if per_layer
         else jax.tree_util.tree_leaves(layer_params)[0].shape[0])
    aux_tot = aux0
    new_cs = []
    for i in range(n):
        lp = (layer_params[i] if per_layer
              else jax.tree_util.tree_map(lambda x: x[i], layer_params))
        lc = (jax.tree_util.tree_map(lambda x: x[i], stacked_cache)
              if stacked_cache is not None else None)
        h, nc, aux = layer_fn(lp, h, lc)
        aux_tot = jax.tree_util.tree_map(jnp.add, aux_tot, aux)
        new_cs.append(nc)
    new_caches = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_cs)
                  if new_cs and new_cs[0] is not None else None)
    return h, aux_tot, new_caches


def _thread_pool(layer_fn, h, layer_params, pool: PagedKVCache, cfg):
    """Run the layer stack over the paged pool as a carry: model layer
    ``i`` reads and writes pool layer ``pool.layer + i`` in place, and its
    new pool is the next layer's input — nothing slices a layer out of the
    pool or stacks the layers' pools back.  Returns the pool positioned at
    the layer after the stack, so a second stack (an MoE model's layers
    after its leading dense ones) continues where this one ends."""
    first = pool.layer
    per_layer = isinstance(layer_params, (list, tuple))
    n = (len(layer_params) if per_layer
         else jax.tree_util.tree_leaves(layer_params)[0].shape[0])

    def run(lp, h, k, v, layer, aux_acc):
        h, new, aux = layer_fn(lp, h, pool._replace(k=k, v=v, layer=layer))
        return h, new.k, new.v, jax.tree_util.tree_map(jnp.add, aux_acc, aux)

    k, v, aux = pool.k, pool.v, _aux_zeros()
    if cfg.scan_layers and not per_layer:
        def body(carry, xs):
            lp, i = xs
            h, k, v, aux_acc = carry
            return run(lp, h, k, v, first + i, aux_acc), None

        (h, k, v, aux), _ = jax.lax.scan(
            _maybe_remat(body, cfg), (h, k, v, aux),
            (layer_params, jnp.arange(n, dtype=jnp.int32)))
    else:
        for i in range(n):
            lp = (layer_params[i] if per_layer
                  else jax.tree_util.tree_map(lambda x: x[i], layer_params))
            h, k, v, aux = run(lp, h, k, v, first + i, aux)
    return h, aux, pool._replace(k=k, v=v, layer=first + n)


def _aux_zeros():
    return {"moe_aux": jnp.zeros((), jnp.float32),
            "moe_zloss": jnp.zeros((), jnp.float32)}


_NO_AUX = {"moe_aux": jnp.zeros(()), "moe_zloss": jnp.zeros(())}


def forward(
    params: dict,
    inputs: Dict[str, jax.Array],
    cfg: ModelConfig,
    policy: PrecisionPolicy,
    *,
    cache: Optional[ModelCache] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[ModelCache]]:
    """Returns (logits (B,S,V), aux losses, updated cache or None).

    inputs: {"tokens": (B,S) int32} and/or {"embeds": (B,S,D)} (audio) and
    optionally {"patch_embeds": (B,P,D)} (vlm prefill/train)."""
    if "tokens" in inputs:
        h = embed(inputs["tokens"], params["embed"]["table"])
        if "patch_embeds" in inputs and cfg.family == "vlm":
            h = jnp.concatenate(
                [inputs["patch_embeds"].astype(h.dtype), h], axis=1)
    else:
        h = inputs["embeds"]
    h = sharding.constrain(h, "activations")
    B, S, _ = h.shape

    if cache is not None:
        base = _cache_length(cache, cfg)  # scalar, or (B,) for paged caches
        base = base[:, None] if base.ndim else base
        positions = jnp.broadcast_to(base + jnp.arange(S)[None, :], (B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    aux = dict(_NO_AUX)
    new_cache = None

    if cfg.family in ("dense", "vlm", "audio"):
        def layer_fn(lp, h, lc):
            h, nc = _dense_layer_fwd(lp, h, cfg, policy, positions, lc)
            return h, nc, dict(_NO_AUX)

        h, aux, nc = _scan_layers(layer_fn, h, params["layers"],
                                  cache.attn if cache is not None else None,
                                  cfg)
        if cache is not None:
            new_cache = ModelCache(attn=nc)

    elif cfg.family == "moe":
        attn_c = cache.attn if cache is not None else None
        dense_c = cache.dense_attn if cache is not None else None
        # a paged pool holds every layer: the leading dense layers run its
        # first layers, and the MoE layers go on with the pool they return
        pooled = isinstance(attn_c, PagedKVCache)
        nc_dense = None
        if cfg.first_k_dense:
            def dense_fn(lp, h, lc):
                h, nc = _dense_layer_fwd(lp, h, cfg, policy, positions, lc,
                                         ff_class="ffn")
                return h, nc, dict(_NO_AUX)

            h, _, nc_dense = _scan_layers(
                dense_fn, h, params["dense_layers"],
                attn_c if pooled else dense_c, cfg)
            if pooled:
                attn_c, nc_dense = nc_dense, None

        def moe_fn(lp, h, lc):
            h, nc, aux = _moe_layer_fwd(lp, h, cfg, policy, positions, lc,
                                        mesh)
            return h, nc, aux

        h, aux, nc = _scan_layers(moe_fn, h, params["layers"], attn_c, cfg)
        if cache is not None:
            new_cache = ModelCache(attn=nc, dense_attn=nc_dense)

    elif cfg.family == "ssm":
        def ssm_fn(lp, h, lc):
            h, nc = _ssm_layer_fwd(lp, h, cfg, policy, lc)
            return h, nc, dict(_NO_AUX)

        h, aux, nc = _scan_layers(ssm_fn, h, params["layers"],
                                  cache.ssm if cache is not None else None,
                                  cfg)
        if cache is not None:
            new_cache = ModelCache(ssm=nc)

    elif cfg.family == "hybrid":
        h, aux, new_cache = _hybrid_forward(params, h, cfg, policy, positions,
                                            cache)
    else:
        raise ValueError(cfg.family)

    h = rms_norm(h, params["ln_final"]["w"], cfg.norm_eps)
    head = params["ctc_head"] if cfg.family == "audio" else params["lm_head"]
    logits = unembed(h, head["w"], policy)
    logits = sharding.constrain(logits, "logits")
    if cfg.padded_vocab != cfg.vocab:
        logits = logits[..., : cfg.vocab]
    return logits, aux, new_cache


def _hybrid_forward(params, h, cfg, policy, positions, cache):
    """zamba2: scan over groups; each group = shared attn block (with this
    group's per-invocation LoRA) followed by ``every`` mamba layers.

    Optional scan inputs (LoRA / caches) ride along as dict entries; absent
    ones are ``None``, which lax.scan treats as empty subtrees."""
    shared = params["shared_block"]
    has_cache = cache is not None

    def body(carry, xs):
        h = carry
        gp = xs["layers"]
        g_lora = xs.get("lora")
        g_ssm_c = xs.get("ssm") if has_cache else None
        g_attn_c = xs.get("attn") if has_cache else None

        a_in = rms_norm(h, shared["ln1"]["w"], cfg.norm_eps)
        a_out, new_attn_c = _attn_block(shared, a_in, cfg, policy, positions,
                                        g_attn_c, lora=g_lora)
        h = h + a_out
        m_in = rms_norm(h, shared["ln2"]["w"], cfg.norm_eps)
        m = shared["mlp"]
        h = h + swiglu_mlp(m_in, m["w_gate"], m["w_up"], m["w_down"], policy)

        def inner(carry, xs2):
            h = carry
            h, nc = _ssm_layer_fwd(xs2["lp"], h, cfg, policy, xs2.get("lc"))
            return h, nc

        inner_xs = {"lp": gp}
        if has_cache:
            inner_xs["lc"] = g_ssm_c
        h, new_ssm_c = jax.lax.scan(inner, h, inner_xs)
        return h, (new_ssm_c, new_attn_c) if has_cache else None

    xs = {"layers": params["layers"]}
    if "shared_lora" in params:
        xs["lora"] = params["shared_lora"]
    if has_cache:
        xs["ssm"] = cache.ssm
        xs["attn"] = cache.shared_attn

    fn = jax.checkpoint(body) if cfg.remat else body
    h, outs = jax.lax.scan(fn, h, xs)
    if has_cache:
        new_ssm, new_attn = outs
        return h, dict(_NO_AUX), ModelCache(ssm=new_ssm, shared_attn=new_attn)
    return h, dict(_NO_AUX), None


def _cache_length(cache: ModelCache, cfg: ModelConfig):
    for c in (cache.attn, cache.ssm, cache.shared_attn):
        if c is not None:
            ln = c.length
            if isinstance(c, PagedKVCache):
                return ln  # (B,) per-slot lengths
            return ln[tuple(0 for _ in range(ln.ndim))] if ln.ndim else ln
    raise ValueError("empty cache")
