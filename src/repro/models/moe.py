"""Mixture-of-Experts FFN (DeepSeek-V2 style: shared + routed experts, top-k
softmax gating) with two dispatch realizations:

``dense``  — every held expert runs on every token, gated combine (a zero
             gate where the token did not pick the expert).  Exact (no
             capacity drops); O(T·E·F) compute.  The layer may hold a share
             of the experts (``MoEDims.n_held`` from ``first_held``): it
             routes over all ``n_experts`` and computes its own experts'
             part of the result, as one chip of an expert-parallel
             deployment does without the exchange.  Serving, smoke tests,
             oracle for the EP path.

``ep``     — production expert parallelism: shard_map over (data, model);
             tokens are split along the model axis, routed with a sort-based
             capacity-bounded dispatch, exchanged with all_to_all along the
             model axis (experts live there), expert FFNs run on gathered
             fp32 weights (FSDP-style per-expert all-gather over data), and
             the inverse all_to_all + gated combine restores token order.
             Dispatch is chunked over tokens (``n_chunks``) to bound buffer
             memory and let XLA overlap chunk i+1's all_to_all with chunk i's
             expert compute.

Router runs at the policy's ``moe_router`` mode (default M23 — routing is the
paper's 'accuracy-critical application'); expert FFNs at ``moe_expert``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mpmatmul import mp_matmul
from repro.core.policy import PrecisionPolicy
from repro.models.layers import dense_init, swiglu_mlp


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    n_experts: int
    top_k: int
    expert_ff: int
    n_shared: int = 0
    shared_ff: int = 0           # defaults to n_shared * expert_ff
    capacity_factor: float = 1.25
    n_chunks: int = 1            # token-chunked dispatch (memory / overlap)
    dispatch_dtype: str = "float32"
    norm_topk_prob: bool = True  # renormalise the top-k weights to sum 1
    routed_scaling_factor: float = 1.0
    n_held: int = 0              # experts this layer holds (0: all of them)
    first_held: int = 0          # ... from this expert id on

    @property
    def shared_ff_dim(self) -> int:
        return self.shared_ff or self.n_shared * self.expert_ff

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


def init_moe_params(key, dims: MoEDims, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 7)
    d, e, f = dims.d_model, dims.held, dims.expert_ff
    p = {
        "router": dense_init(ks[0], d, dims.n_experts, dtype, scale=0.02),
        # stacked weights of the held experts: (E, D, F) / (E, F, D)
        "w_gate": jax.vmap(lambda k: dense_init(k, d, f, dtype))(
            jax.random.split(ks[1], e)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, f, dtype))(
            jax.random.split(ks[2], e)),
        "w_down": jax.vmap(lambda k: dense_init(k, f, d, dtype))(
            jax.random.split(ks[3], e)),
    }
    if dims.n_shared > 0:
        sf = dims.shared_ff_dim
        p["shared"] = {
            "w_gate": dense_init(ks[4], d, sf, dtype),
            "w_up": dense_init(ks[5], d, sf, dtype),
            "w_down": dense_init(ks[6], sf, d, dtype),
        }
    return p


def _route(x2d: jax.Array, w_router: jax.Array, dims: MoEDims,
           policy: PrecisionPolicy):
    """Router: logits -> softmax over all experts in f32 -> greedy top-k;
    each pick weighs its probability (renormalised over the k when
    ``norm_topk_prob``) times ``routed_scaling_factor``."""
    logits = mp_matmul(x2d, w_router, policy.mode("moe_router"),
                       **policy.bwd_kwargs("moe_router"))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, dims.top_k)        # (T, k)
    if dims.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = top_p * dims.routed_scaling_factor
    # load-balance aux (Switch-style): E * sum_e f_e * P_e
    T = x2d.shape[0]
    me = jnp.mean(probs, axis=0)                            # mean prob per e
    counts = jnp.zeros((dims.n_experts,), jnp.float32).at[top_i.reshape(-1)
                      ].add(1.0) / (T * dims.top_k)
    aux = dims.n_experts * jnp.sum(me * counts)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_p, top_i, {"moe_aux": aux, "moe_zloss": zloss}


# ----------------------------------------------------------------- dense path
def held_gates(top_p: jax.Array, top_i: jax.Array, dims: MoEDims
               ) -> jax.Array:
    """(T, held) gate of each held expert per token: its routing weight
    where the token picked it, else 0."""
    ids = dims.first_held + jnp.arange(dims.held)
    return jnp.sum(jnp.where(top_i[:, :, None] == ids, top_p[:, :, None],
                             0.0), axis=1)


def moe_forward_dense(params: dict, x: jax.Array, dims: MoEDims,
                      policy: PrecisionPolicy) -> Tuple[jax.Array, dict]:
    """Every held expert on every token, gated: exact, drops no token.

    The expert weights are (E, D, F)-style stacks (mapped over) or, on the
    serving path, one prelimbed tree per expert (``w_gate`` etc. are lists,
    each kernel reading its expert's limb stack in place).  The combine is
    an f32 multiply-add per expert, in expert order."""
    B, S, D = x.shape
    x2 = x.reshape(-1, D)
    top_p, top_i, aux = _route(x2, params["router"], dims, policy)
    gates = held_gates(top_p, top_i, dims)
    ws = (params["w_gate"], params["w_up"], params["w_down"])

    def expert(w):
        return swiglu_mlp(x2, *w, policy, op_class="moe_expert")

    if isinstance(ws[0], (list, tuple)):
        outs = [expert(w) for w in zip(*ws)]
    else:
        outs = jax.lax.map(expert, ws)                       # (E, T, D)
    out = jnp.zeros(x2.shape, jnp.float32)
    for e in range(dims.held):
        out = out + gates[:, e:e + 1] * outs[e]
    out = out.reshape(B, S, D).astype(x.dtype)
    out = out + _shared_out(params, x, dims, policy)
    return out, aux


def _shared_out(params, x, dims: MoEDims, policy) -> jax.Array:
    if dims.n_shared == 0:
        return jnp.zeros_like(x)
    sp = params["shared"]
    return swiglu_mlp(x, sp["w_gate"], sp["w_up"], sp["w_down"], policy,
                      op_class="moe_expert")


# -------------------------------------------------------------------- EP path
def _dispatch_chunk(x_chunk, top_p, top_i, dims: MoEDims, cap: int):
    """Sort-based capacity dispatch bookkeeping for one token chunk.

    Returns (send_buffer (E*cap, D), keep mask, flat buffer index) so the
    combine step can invert the scatter."""
    T, D = x_chunk.shape
    E, k = dims.n_experts, dims.top_k
    e_flat = top_i.reshape(-1)                                  # (T*k,)
    # rank of each assignment within its expert (stable order = token order)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts                        # exclusive
    ranks_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[e_sorted]
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(ranks_sorted)
    keep = ranks < cap                                          # capacity drop
    buf_idx = jnp.where(keep, e_flat * cap + ranks, E * cap)    # OOB -> drop
    tok_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    dtype = jnp.dtype(dims.dispatch_dtype)
    send = jnp.zeros((E * cap, D), dtype)
    send = send.at[buf_idx].set(x_chunk[tok_idx].astype(dtype), mode="drop")
    return send, keep, buf_idx


def _expert_ffn_gathered(recv, params, dims: MoEDims, policy: PrecisionPolicy,
                         data_axis: str, e_local: int):
    """recv: (E_local, Tcap, D).  Scan over local experts; each step
    all-gathers that expert's (data-sharded) weights — FSDP-style — so peak
    weight memory is one expert, and runs the swiglu FFN at moe_expert mode."""
    mode = policy.mode("moe_expert")
    bwd = policy.bwd_kwargs("moe_expert")

    def one_expert(carry, inp):
        xe, wg_s, wu_s, wd_s = inp
        wg = jax.lax.all_gather(wg_s, data_axis, axis=0, tiled=True)
        wu = jax.lax.all_gather(wu_s, data_axis, axis=0, tiled=True)
        wd = jax.lax.all_gather(wd_s, data_axis, axis=0, tiled=True)
        g = mp_matmul(xe.astype(jnp.float32), wg, mode, **bwd)
        u = mp_matmul(xe.astype(jnp.float32), wu, mode, **bwd)
        y = mp_matmul(jax.nn.silu(g) * u, wd, mode, **bwd)
        return carry, y.astype(recv.dtype)

    _, out = jax.lax.scan(
        one_expert, 0,
        (recv, params["w_gate"], params["w_up"], params["w_down"]),
    )
    return out  # (E_local, Tcap, D)


def moe_forward_ep(params: dict, x: jax.Array, dims: MoEDims,
                   policy: PrecisionPolicy, mesh: jax.sharding.Mesh,
                   *, data_axis: str = "data", model_axis: str = "model",
                   extra_data_axes: Tuple[str, ...] = (),
                   tokens_on_model: bool = False,
                   x_pspec=None,
                   ) -> Tuple[jax.Array, dict]:
    """Expert-parallel MoE.  x: (B, S, D) sharded (data, None, None); experts
    sharded over the model axis; expert weights additionally sharded over data
    (FSDP) on their D/F dims.  See module docstring for the dance.

    tokens_on_model=True (FSDP-only layout): the batch dim is already sharded
    over the model axis too, so each device dispatches its own tokens directly
    (no slice, no output all_gather)."""
    from jax.sharding import PartitionSpec as P

    if dims.held != dims.n_experts:
        raise ValueError("the EP path holds every expert across the mesh; "
                         "a layer holding a share runs moe_forward_dense")
    B, S, D = x.shape
    E = dims.n_experts
    m_size = mesh.shape[model_axis]
    d_axes = tuple(extra_data_axes) + (data_axis,)
    assert E % m_size == 0, (E, m_size)
    e_local = E // m_size

    def local_decode_fn(x_loc, router_w, wg, wu, wd, shared):
        """Decode path (few tokens/device): tokens stay replicated across the
        model axis; each model column serves only the assignments routed to
        ITS local experts, partial outputs are psum'd across the model axis.
        No all_to_all — at decode batch the dispatch buffer is tiny and the
        psum is one small collective (DESIGN.md §3)."""
        Bl = x_loc.shape[0]
        T_all = Bl * S
        m_idx = jax.lax.axis_index(model_axis)
        x_flat = x_loc.reshape(T_all, D)
        top_p, top_i, aux = _route(x_flat, router_w, dims, policy)
        for ax in (model_axis,) + d_axes:
            aux = {k: jax.lax.pmean(v, ax) for k, v in aux.items()}
        cap = max(1, math.ceil(T_all * dims.top_k * dims.capacity_factor / E))
        send, keep, buf_idx = _dispatch_chunk(x_flat, top_p, top_i, dims, cap)
        # take only this column's experts
        local = jax.lax.dynamic_slice_in_dim(
            send.reshape(E, cap, D), m_idx * e_local, e_local, axis=0
        ).reshape(e_local, cap, D)
        lp = {"w_gate": wg, "w_up": wu, "w_down": wd}
        eout = _expert_ffn_gathered(local, lp, dims, policy, data_axis,
                                    e_local)
        # scatter back into the global buffer slot, combine across columns
        full = jnp.zeros((E, cap, D), eout.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, eout.reshape(e_local, cap, D), m_idx * e_local, axis=0)
        full = jax.lax.psum(full, model_axis).reshape(E * cap, D)
        vals = jnp.take(full, jnp.clip(buf_idx, 0, E * cap - 1), axis=0)
        vals = vals * (keep[:, None] * top_p.reshape(-1)[:, None]
                       ).astype(vals.dtype)
        y = jnp.sum(vals.reshape(T_all, dims.top_k, D), axis=1
                    ).reshape(Bl, S, D).astype(jnp.float32)
        if dims.n_shared > 0:
            y = y + swiglu_mlp(x_loc, shared["w_gate"], shared["w_up"],
                               shared["w_down"], policy, op_class="moe_expert")
        return y, aux

    def local_fn(x_loc, router_w, wg, wu, wd, shared):
        # x_loc: (B_l, S, D).  With tokens_on_model the model axis already
        # carries distinct tokens; otherwise x_loc is identical across the
        # model axis and each column takes its slice.
        Bl, S_loc, _ = x_loc.shape
        T_all = Bl * S_loc
        if tokens_on_model:   # x arrives seq-sharded over the model axis
            T_loc = T_all
            x_slice = x_loc.reshape(T_all, D)
        else:
            m_idx = jax.lax.axis_index(model_axis)
            T_loc = T_all // m_size
            x_flat = x_loc.reshape(T_all, D)
            x_slice = jax.lax.dynamic_slice_in_dim(x_flat, m_idx * T_loc,
                                                   T_loc)

        top_p, top_i, aux = _route(x_slice, router_w, dims, policy)
        for ax in (model_axis,) + d_axes:
            aux = {k: jax.lax.pmean(v, ax) for k, v in aux.items()}

        n_chunks = max(1, dims.n_chunks)
        Tc = T_loc // n_chunks
        cap = max(1, math.ceil(Tc * dims.top_k * dims.capacity_factor / E))
        lp = {"w_gate": wg, "w_up": wu, "w_down": wd}

        def per_chunk(carry, cidx):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, cidx * Tc, Tc)
            xc, pp, ii = sl(x_slice), sl(top_p), sl(top_i)
            send, keep, buf_idx = _dispatch_chunk(xc, pp, ii, dims, cap)
            send = send.reshape(m_size, e_local * cap, D)
            recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # (m_src, E_l*cap, D) -> (E_l, m_src*cap, D)
            recv = recv.reshape(m_size, e_local, cap, D)
            recv = recv.transpose(1, 0, 2, 3).reshape(e_local, m_size * cap, D)
            eout = _expert_ffn_gathered(recv, lp, dims, policy, data_axis,
                                        e_local)
            # reverse path
            back = eout.reshape(e_local, m_size, cap, D).transpose(1, 0, 2, 3)
            back = back.reshape(m_size, e_local * cap, D)
            ret = jax.lax.all_to_all(back, model_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            ret = ret.reshape(E * cap, D)
            # gated combine: out[t] = sum_k gate * ret[buf_idx[t,k]]
            vals = jnp.take(ret, jnp.clip(buf_idx, 0, E * cap - 1), axis=0)
            vals = vals * (keep[:, None] * pp.reshape(-1)[:, None]
                           ).astype(vals.dtype)
            yc = jnp.sum(vals.reshape(Tc, dims.top_k, D), axis=1)
            return carry, yc.astype(jnp.float32)

        _, ys = jax.lax.scan(per_chunk, 0, jnp.arange(n_chunks))
        y_slice = ys.reshape(T_loc, D)
        if tokens_on_model:
            y = y_slice.reshape(Bl, S_loc, D)
        else:  # reassemble full local tokens across the model axis
            y_full = jax.lax.all_gather(y_slice, model_axis, axis=0,
                                        tiled=True)
            y = y_full.reshape(Bl, S, D)
        # shared experts: dense, every token (replicated compute over model)
        if dims.n_shared > 0:
            y = y + swiglu_mlp(x_loc, shared["w_gate"], shared["w_up"],
                               shared["w_down"], policy, op_class="moe_expert")
        return y, aux

    shared = params.get("shared",
                        {"w_gate": jnp.zeros((0,)), "w_up": jnp.zeros((0,)),
                         "w_down": jnp.zeros((0,))})
    if x_pspec is not None:
        pspec_x = P(x_pspec[0], x_pspec[1], None)
    else:
        bax = d_axes if len(d_axes) > 1 else d_axes[0]
        pspec_x = P(bax, model_axis if tokens_on_model else None, None)
    wspec = P(model_axis, data_axis, None)
    # per-device token count decides the dispatch strategy: the split +
    # all_to_all path needs tokens divisible by the model axis; decode-sized
    # batches use the replicated path (see local_decode_fn docstring)
    data_size = 1
    for ax in d_axes + ((model_axis,) if tokens_on_model else ()):
        data_size *= mesh.shape[ax]
    t_all = (B * S) // data_size
    if tokens_on_model:
        fn = local_fn
    else:
        fn = local_fn if t_all % m_size == 0 and t_all >= m_size else \
            local_decode_fn
    out, aux = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(pspec_x, P(None, None), wspec, wspec, wspec, P(None)),
        out_specs=(pspec_x, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"],
      shared)
    return out, aux


def moe_forward(params: dict, x: jax.Array, dims: MoEDims,
                policy: PrecisionPolicy,
                mesh: Optional[jax.sharding.Mesh] = None,
                **kw) -> Tuple[jax.Array, dict]:
    if mesh is not None:
        return moe_forward_ep(params, x, dims, policy, mesh, **kw)
    return moe_forward_dense(params, x, dims, policy)
