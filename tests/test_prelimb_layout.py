"""Prelimbed decode weights laid out as the kernel reads them: one limb
stack per layer, zero-padded to the kernel's blocks.

The per-layer, padded layout (``serve/engine.prelimb_dense_params``) must
decode bit-identically to the stacked, unpadded layout that the layer scan
slices, on the ref backend and in the Pallas interpreter, through every
decode step that reads it: the paged per-policy step, the mixed-lane step
and ``generate()``'s dense-cache step.  The smoke model's widths (d_model
64, d_ff 200) leave every limb stack padded (64 -> 128, 200 -> 256).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import lanes as lanes_lib
from repro.core.dispatch import pin_backend
from repro.core.limbs import PrelimbedWeight, prelimb_weight
from repro.core.policy import PrecisionPolicy
from repro.models import transformer as T
from repro.serve.engine import (ServeEngine, _policy_prelimb_limbs,
                                make_mixed_decode_step,
                                make_paged_decode_step, prelimb_dense_params)
from repro.serve.scheduler import ContinuousScheduler, ScheduledRequest

CFG = dataclasses.replace(get_config("paper-mpfp-100m", smoke=True), d_ff=200)
BACKENDS = ("ref", "pallas_interpret")
B, BLOCK, N_BLOCKS, TABLE_W = 4, 8, 17, 4


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _stacked(params, n_limbs):
    """The stacked, unpadded layout: (L, n, K, N) stacks a scan slices."""
    layers = {g: ({k: prelimb_weight(w, n_limbs) for k, w in sub.items()}
                  if g in ("attn", "mlp") else sub)
              for g, sub in params["layers"].items()}
    return {**params, "layers": layers,
            "lm_head": {"w": prelimb_weight(params["lm_head"]["w"],
                                            n_limbs)}}


def _prelimbed(tree):
    return [w for w in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, PrelimbedWeight))
        if isinstance(w, PrelimbedWeight)]


def _paged_inputs(seed):
    rng = np.random.default_rng(seed)
    shape = (CFG.n_layers, N_BLOCKS, BLOCK, CFG.n_kv_heads,
             CFG.resolved_head_dim)
    pool_k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    table = jnp.asarray(
        1 + np.arange(B * TABLE_W).reshape(B, TABLE_W), jnp.int32)
    lengths = jnp.asarray([5, 12, 1, 30], jnp.int32)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab, (B, 1)), jnp.int32)
    return pool_k, pool_v, table, lengths, tokens


def _assert_same(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_layout_is_per_layer_and_padded(params):
    pp = prelimb_dense_params(params, 3)
    assert isinstance(pp["layers"], list)
    assert len(pp["layers"]) == CFG.n_layers
    mlp = pp["layers"][0]["mlp"]
    assert mlp["w_gate"].limbs.shape == (3, 128, 256)
    assert mlp["w_gate"].shape == (CFG.d_model, 200)
    assert mlp["w_down"].limbs.shape == (3, 256, 128)
    assert mlp["w_down"].shape == (200, CFG.d_model)
    # the norm gains are split per layer too; prefill's raw params stay
    # stacked
    assert pp["layers"][1]["ln1"]["w"].shape == (CFG.d_model,)
    assert params["layers"]["mlp"]["w_gate"].shape == (CFG.n_layers,
                                                       CFG.d_model, 200)
    for w in _prelimbed(pp):
        K, N = w.kn
        assert not np.asarray(w.limbs[:, K:, :]).any()
        assert not np.asarray(w.limbs[:, :, N:]).any()


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("mode", ["M8", "M23"])
def test_paged_decode_matches_stacked(params, backend, mode):
    policy = PrecisionPolicy.serve_default().overlay(mode)
    n = _policy_prelimb_limbs(policy)
    step = jax.jit(pin_backend(make_paged_decode_step(CFG, policy), backend))
    args = _paged_inputs(1)
    got = step(prelimb_dense_params(params, n), *args)
    want = step(_stacked(params, n), *args)
    _assert_same(got, want)  # logits, guard stats, both pools


@pytest.mark.parametrize("backend", BACKENDS)
def test_mixed_decode_matches_stacked(params, backend):
    policies = [PrecisionPolicy.serve_default().overlay(m)
                for m in ("M23", "M8", "M16", "M23")]
    env = lanes_lib.envelope_of(policies)
    lane_n, lane_ord = lanes_lib.lane_tables(policies, B)
    step = jax.jit(pin_backend(make_mixed_decode_step(CFG, env), backend))
    args = _paged_inputs(2) + (jnp.asarray(lane_n), jnp.asarray(lane_ord))
    got = step(prelimb_dense_params(params, env.max_limbs), *args)
    want = step(_stacked(params, env.max_limbs), *args)
    _assert_same(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_generate_decode_matches_stacked(params, backend):
    eng = ServeEngine(CFG, params, max_batch=2, max_seq=32,
                      policy=PrecisionPolicy.serve_default().overlay("M23"),
                      matmul_backend=backend)
    toks = jnp.asarray([[3, 7, 1, 4], [0, 2, 9, 5]], jnp.int32)
    cache = T.make_cache(CFG, 2, 32, dtype=jnp.float32)
    _, cache = eng._prefill(params, {"tokens": toks}, cache)
    cur = jnp.asarray([[6], [8]], jnp.int32)
    got = eng._decode(eng._decode_params, cache, cur)
    want = eng._decode(_stacked(params, 3), cache, cur)
    _assert_same(got, want)  # logits and every layer's new dense cache
    raw = ServeEngine(CFG, params, max_batch=2, max_seq=32,
                      policy=eng.policy, matmul_backend=backend,
                      prelimb_weights=False)
    prompts = [np.asarray([1, 2, 3], np.int32)]
    assert eng.generate(prompts, max_new=3) == raw.generate(prompts,
                                                            max_new=3)


def test_prelimb_cache_entries_pad_bytes_and_mode_join(params):
    eng = ServeEngine(CFG, params, max_batch=4, max_seq=48,
                      policy=PrecisionPolicy.serve_default(),
                      matmul_backend="ref")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab, size=s).astype(np.int32)
               for s in (5, 3)]

    def run():
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        # M16 joins the streaming M23 request mid-flight
        done = sched.run([
            ScheduledRequest(rid=0, prompt=prompts[0], max_new=6, mode="M23",
                             arrival=0),
            ScheduledRequest(rid=1, prompt=prompts[1], max_new=2, mode="M16",
                             arrival=2)])
        return {r.rid: r.out for r in done}

    first = run()
    traces = eng.trace_events
    assert run() == first
    assert eng.trace_events == traces, "re-trace on a mid-stream join"
    # one entry per limb count, each holding per-layer padded stacks
    limbs = [n for n, _ in eng._prelimb_cache]
    assert sorted(limbs) == sorted(set(limbs))
    zeros = 0
    for tree in eng._prelimb_cache.values():
        assert isinstance(tree["layers"], list)
        for w in _prelimbed(tree):
            K, N = w.kn
            stored = np.asarray(w.limbs)
            assert not stored[..., K:, :].any()
            assert not stored[..., :, N:].any()
            zeros += (stored.size - stored[..., :K, :N].size) \
                * stored.itemsize
    assert zeros > 0
    assert eng.cache_stats()["prelimb_pad_bytes"] == zeros
