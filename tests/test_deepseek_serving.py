"""DeepSeek-V2's block served through the paged scheduler: YaRN rope and its
softmax scale, the latent norm, routing without renormalisation, an expert
layer that holds a share of the experts, the latent paged decode kernel,
and paged prefill + absorbed decode against the plain full forward."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.policy import PrecisionPolicy
from repro.models import moe as moe_lib
from repro.models import transformer as T
from repro.models.mla import MLADims, init_mla_params, mla_forward
from repro.serve.engine import (ServeEngine, expert_limb_bytes,
                                pool_entry_shapes)
from repro.serve.kv_cache import PagedKVCache, PagedKVPool
from repro.serve.scheduler import ContinuousScheduler, ScheduledRequest

M23 = PrecisionPolicy.serve_default().overlay("M23")
M16 = PrecisionPolicy.serve_default().overlay("M16")

# DeepSeek-V2-Lite's rope settings (hf config.json, rope_scaling)
LITE_MLA = MLADims(d_model=2048, n_heads=16, kv_lora=512, qk_nope_dim=128,
                   qk_rope_dim=64, v_head_dim=128, yarn_factor=40.0,
                   yarn_original_max_pos=4096, yarn_beta_fast=32.0,
                   yarn_beta_slow=1.0, yarn_mscale=0.707,
                   yarn_mscale_all_dim=0.707)

_BASE = get_config("deepseek-v2-lite-16b", smoke=True)
# the smoke model with the published block's settings: YaRN on, no top-k
# renormalisation, and half of the 4 experts held (experts 2 and 3)
DS = dataclasses.replace(
    _BASE,
    mla=dataclasses.replace(_BASE.mla, yarn_factor=40.0, yarn_mscale=0.707,
                            yarn_mscale_all_dim=0.707),
    moe=dataclasses.replace(_BASE.moe, norm_topk_prob=False, n_held=2,
                            first_held=2))


@pytest.fixture(scope="module")
def ds_params():
    return jax.jit(lambda k: T.init_params(DS, k))(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def ds_engine(ds_params):
    return ServeEngine(DS, ds_params, max_batch=2, max_seq=32, policy=M23)


def test_yarn_frequencies_and_softmax_scale():
    """Against HF's formulas worked by hand: the correction range of 32 and
    1 turns in 4096 positions is dims [floor(10.47), ceil(22.51)] = [10,
    23]; below it a pair keeps 10000^(-2i/64), above it that over 40,
    between a linear blend.  mscale(40, 0.707) = 0.1*0.707*ln(40) + 1."""
    inv = LITE_MLA.rope_inv_freq()
    want = {0: 1.0, 10: 0.05623413251903491, 22: 0.0001778279410038922,
            31: 3.3338035804083097e-06}
    for i, v in want.items():
        assert inv[i] == pytest.approx(v, rel=1e-6), i
    ramp = (22 - 10) / 13
    extra = 10000.0 ** (-44 / 64)
    assert inv[22] == pytest.approx(extra / 40 * ramp + extra * (1 - ramp),
                                    rel=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608037774058554)
    assert LITE_MLA.softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2,
                                                   rel=1e-7)
    assert LITE_MLA.rope_cos_scale == 1.0
    plain = dataclasses.replace(LITE_MLA, yarn_factor=0.0)
    assert plain.rope_inv_freq() is None
    assert plain.softmax_scale == 192 ** -0.5


def test_latent_norm_and_rope_key_written_to_the_pool():
    """Paged prefill stores rmsnorm(x·w_dkv)·gain (kv_a_layernorm) in the
    pool's k and the rotated rope key in its v (position 0: unrotated)."""
    dims = dataclasses.replace(DS.mla)
    p = init_mla_params(jax.random.PRNGKey(0), dims)
    p["kv_norm"] = jnp.linspace(0.8, 1.2, dims.kv_lora)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 5, dims.d_model)), jnp.float32)
    bs = 4
    cache = PagedKVCache(
        k=jnp.zeros((1, 4, bs, dims.kv_lora)),
        v=jnp.zeros((1, 4, bs, dims.qk_rope_dim)),
        block_table=jnp.asarray([[1, 2]], jnp.int32),
        length=jnp.zeros((1,), jnp.int32))
    _, new = jax.jit(lambda p, x, c: mla_forward(
        p, x, dims, M23, positions=jnp.arange(5)[None], cache=c))(p, x, cache)
    x64 = np.asarray(x[0], np.float64)
    c = x64 @ np.asarray(p["w_dkv"], np.float64)
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + dims.norm_eps)
    c = c * np.asarray(p["kv_norm"], np.float64)
    got = np.concatenate([np.asarray(new.k[0, 1]),
                          np.asarray(new.k[0, 2])])[:5]
    np.testing.assert_allclose(got, c, rtol=2e-5, atol=2e-6)
    kr0 = x64[0] @ np.asarray(p["w_kr"], np.float64)
    np.testing.assert_allclose(np.asarray(new.v[0, 1, 0]), kr0, rtol=2e-5,
                               atol=2e-6)
    assert np.asarray(new.length).tolist() == [5]


def test_routing_without_renormalisation():
    """Softmax over every expert, greedy top-k, weights the probabilities
    times routed_scaling_factor; renormalised only with norm_topk_prob."""
    logit_rows = np.array([[2.0, 1.0, 0.0, -1.0], [0.0, 3.0, 0.0, 1.0]])
    x = jnp.eye(2, 4, dtype=jnp.float32)
    router = jnp.asarray(np.concatenate([logit_rows, np.zeros((2, 4))]),
                         jnp.float32)
    z0 = math.exp(2) + math.exp(1) + 1 + math.exp(-1)
    z1 = math.exp(3) + 2 + math.exp(1)
    want = np.array([[math.exp(2) / z0, math.exp(1) / z0],
                     [math.exp(3) / z1, math.exp(1) / z1]])
    dims = moe_lib.MoEDims(d_model=4, n_experts=4, top_k=2, expert_ff=8,
                           norm_topk_prob=False, routed_scaling_factor=2.5)
    top_p, top_i, _ = moe_lib._route(x, router, dims, M23)
    assert np.asarray(top_i).tolist() == [[0, 1], [1, 3]]
    np.testing.assert_allclose(np.asarray(top_p), 2.5 * want, rtol=1e-6)
    dims = dataclasses.replace(dims, norm_topk_prob=True,
                               routed_scaling_factor=1.0)
    top_p, _, _ = moe_lib._route(x, router, dims, M23)
    np.testing.assert_allclose(np.asarray(top_p),
                               want / want.sum(-1, keepdims=True), rtol=1e-6)
    gates = moe_lib.held_gates(jnp.asarray(want), jnp.asarray(top_i),
                               dataclasses.replace(dims, n_held=2,
                                                   first_held=1))
    np.testing.assert_allclose(np.asarray(gates),
                               [[want[0, 1], 0.0], [want[1, 0], 0.0]])


def test_held_shares_add_up_to_the_uncut_layer():
    """Every disjoint share of 2 of 8 experts, each routed over all 8: the
    routed parts of the four shares plus the shared expert once give the
    layer that holds all 8."""
    dims = moe_lib.MoEDims(d_model=32, n_experts=8, top_k=3, expert_ff=16,
                           n_shared=1, norm_topk_prob=False)
    p = moe_lib.init_moe_params(jax.random.PRNGKey(5), dims)
    p["router"] = p["router"] * 50.0          # spread the routing
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 6, 32)),
                    jnp.float32)
    def layer(p, d):
        return jax.jit(lambda p: moe_lib.moe_forward_dense(p, x, d, M23)[0])(p)

    whole = layer(p, dims)
    shared = jax.jit(lambda p: moe_lib._shared_out(p, x, dims, M23))(p)
    total = shared
    for first in range(0, 8, 2):
        share = dataclasses.replace(dims, n_held=2, first_held=first)
        sp = dict(p, **{k: p[k][first:first + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        total = total + (layer(sp, share) - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(whole - shared))) > 0.1


def test_latent_paged_kernel_matches_the_einsum_path():
    """The latent paged kernel (interpreter) against the gather + einsum
    path at M23, and both against float64; an inactive slot (length 0)
    reads exact zeros."""
    from repro.core.dispatch import masked_latent_attention
    from repro.kernels.mp_attention import mp_latent_paged_attention_pallas

    rng = np.random.default_rng(4)
    B, H, Dc, Dr, bs, nb = 3, 4, 32, 8, 4, 9
    q_lat = rng.standard_normal((B, H, Dc)).astype(np.float32)
    q_rope = rng.standard_normal((B, H, Dr)).astype(np.float32)
    c_pool = rng.standard_normal((nb, bs, Dc)).astype(np.float32)
    r_pool = rng.standard_normal((nb, bs, Dr)).astype(np.float32)
    table = np.array([[1, 2, 3], [4, 5, 6], [0, 0, 0]], np.int32)
    lengths = np.array([5, 12, 0], np.int32)
    scale = 0.11
    got = np.asarray(mp_latent_paged_attention_pallas(
        q_lat, q_rope, c_pool, r_pool, table, lengths, "M23", "M23",
        scale=scale, interpret=True))
    c, r = c_pool[table].reshape(B, -1, Dc), r_pool[table].reshape(B, -1, Dr)
    ein = np.asarray(masked_latent_attention(
        q_lat, q_rope, jnp.asarray(c), jnp.asarray(r), jnp.asarray(lengths),
        "M23", "M23", scale=scale))
    want = np.zeros((B, H, Dc))
    for b in range(B):
        n = lengths[b]
        if not n:
            continue
        s = scale * (q_lat[b].astype(np.float64) @ c[b, :n].T
                     + q_rope[b].astype(np.float64) @ r[b, :n].T)
        pr = np.exp(s - s.max(-1, keepdims=True))
        want[b] = (pr / pr.sum(-1, keepdims=True)) @ c[b, :n]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ein, want, rtol=1e-5, atol=1e-5)
    assert not got[2].any() and not ein[2].any()


@jax.jit
def _full_logits(params, tokens):
    return T.forward(params, {"tokens": tokens[None]}, DS, M23)[0][0]


def _paged_logits(eng, params, policy, handed=None):
    """Paged prefill of a 7-token prompt, then 6 decode steps on greedy
    tokens, through the engine's jit'd paged steps: each step's logits,
    the sequence and the last pools (``handed`` collects the pools each
    step was handed)."""
    prefill, decode = eng.paged_steps_for(policy)
    pool = PagedKVPool(DS.n_layers, 9, 4, *pool_entry_shapes(DS),
                       max_blocks_per_seq=4)
    prompt = np.random.default_rng(0).integers(0, DS.vocab, 7)
    table = jnp.asarray(pool.table_row([1, 2, 3, 4])[None])
    toks = np.zeros((1, 8), np.int32)
    toks[0, :7] = prompt
    k, v = pool.k, pool.v
    handed = [] if handed is None else handed
    handed.append((k, v))
    last, _, k, v = prefill(params, k, v, table, jnp.zeros((1,), jnp.int32),
                            jnp.asarray(toks), np.int32(6))
    got, seq = [np.asarray(last[0, 0])], [int(t) for t in prompt]
    dp = eng._decode_params_for(policy)
    for _ in range(6):
        tok = int(np.argmax(got[-1]))
        seq.append(tok)
        handed.append((k, v))
        logits, _, k, v = decode(dp, k, v, table,
                                 jnp.asarray([len(seq) - 1], jnp.int32),
                                 jnp.asarray([[tok]], jnp.int32))
        got.append(np.asarray(logits[0, 0]))
    return np.stack(got), seq, (k, v)


def test_paged_prefill_and_decode_match_the_full_forward(ds_engine,
                                                        ds_params):
    """Served logits (paged prefill, then weight-absorbed decode on
    prelimbed weights over the latent pool) against the plain full forward
    at M23, relative to the largest logit.  Tolerance 4e-6: M23 bounds each
    contraction's relative error by 1.9e-6, and the two paths order the
    latent products differently (absorbed against unabsorbed); the
    measured difference is 4.4e-7.  Serving at M16 (bound 1.2e-4) reads
    2.9e-5, outside it."""
    for policy, inside in ((M23, True), (M16, False)):
        got, seq, _ = _paged_logits(ds_engine, ds_params, policy)
        want = np.asarray(_full_logits(ds_params, jnp.asarray(seq)))
        want = want[6:6 + len(got)]
        err = np.abs(got - want).max() / np.abs(want).max()
        assert (err <= 4e-6) == inside, (policy, err)


def test_donated_steps_match_slice_and_stack(ds_params, slice_and_stack):
    """The paged prefill and absorbed decode steps take the latent pool
    donated (every pool they were handed is deleted) and update it in
    place, one pool threaded through the dense layer (pool layer 0) and
    the MoE layer (pool layer 1): logits and the last pool bit for bit as
    the per-layer slice-and-stack path computes them."""
    def run():
        eng = ServeEngine(DS, ds_params, max_batch=2, max_seq=32,
                          policy=M23)
        return _paged_logits(eng, ds_params, M23, handed)

    handed = []
    got, seq, (k, v) = run()
    assert len(handed) == 7
    assert all(a.is_deleted() and b.is_deleted() for a, b in handed)
    with slice_and_stack():
        want, want_seq, (ref_k, ref_v) = run()
    assert seq == want_seq
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ref_v))
    assert np.asarray(k)[:, 1:5].any(axis=(1, 2, 3)).all()  # both layers


def test_scheduler_serves_the_full_forwards_tokens_and_counts(ds_engine,
                                                             ds_params):
    """Through ContinuousScheduler (paged prefill, then decode of both
    requests in one launch): the greedy tokens of the full forward, and the
    set-up counters of the latent pool and the held experts."""
    sched = ContinuousScheduler(ds_engine, n_blocks=9, block_size=4)
    rng = np.random.default_rng(6)
    reqs = [ScheduledRequest(rid=i, prompt=rng.integers(0, DS.vocab, n),
                             max_new=4) for i, n in enumerate((5, 9))]
    done = sched.run(reqs)
    for r in done:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        logits = _full_logits(ds_params, jnp.asarray(seq))
        assert r.out == np.asarray(
            jnp.argmax(logits, -1))[len(r.prompt) - 1:].tolist()
    s = sched.stats()
    assert s["experts_held"] == 2
    # one MoE layer x 2 experts x (gate, up, down), 3 limbs, 128 x 128 bf16
    m23 = expert_limb_bytes(ds_engine._decode_params_for(M23))
    assert m23 == 1 * 2 * 3 * 3 * 128 * 128 * 2
    assert s["expert_limb_bytes"] >= m23     # every cached limb depth
    m = DS.mla
    assert s["latent_pool_bytes"] == (DS.n_layers * 9 * 4
                                      * (m.kv_lora + m.qk_rope_dim) * 4)
    assert s["blocks_live"] == 0 and s["completed"] == 2


def test_mixed_lanes_refuse_latent_attention(ds_engine):
    from repro.core.lanes import envelope_of

    with pytest.raises(NotImplementedError, match="latent attention"):
        ds_engine.mixed_decode_step_for(envelope_of([M23]))
