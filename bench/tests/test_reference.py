"""The plain reference against the serving program's paged prefill and
decode logits, at the smoke sizes of both configurations (CPU, Pallas
kernels in the interpreter)."""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import SMOKE, TWIN_SCALE, smoke_conf

from bench import weights
from bench.reference import dense


def _program_logits(model: dict, params, prompt, n_decode: int, mode: str):
    """Prefill ``prompt`` through the engine's paged prefill step, then
    decode greedily through its paged decode step; returns every logits
    row (prefill's last position first) and the tokens fed."""
    import repro.mp as mp
    from repro.configs.base import ModelConfig
    from repro.core.policy import PrecisionPolicy
    from repro.launch.serve import make_scheduler

    mp.configure(backend="pallas_interpret")
    cfg = ModelConfig(name="smoke", **model)
    policy = PrecisionPolicy.serve_default().overlay(mode)
    sched = make_scheduler(cfg, params, slots=1, max_seq=64, policy=policy)
    eng, pool = sched.engine, sched.pool
    prefill, decode = eng.paged_steps_for(policy)
    n = len(prompt)
    blocks = pool.try_alloc(pool.blocks_for_tokens(n + n_decode + 1))
    table = pool.table_row(blocks)[None, :]
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :n] = prompt
    logits, _, k, v = prefill(eng.params, pool.k, pool.v, jnp.asarray(table),
                              jnp.zeros((1,), jnp.int32), jnp.asarray(tokens),
                              np.int32(n - 1))
    pool.update(k, v)
    rows = [np.asarray(logits[0, 0])]
    fed = []
    for i in range(n_decode):
        tok = int(np.argmax(rows[-1]))
        fed.append(tok)
        logits, _, k, v = decode(
            eng._decode_params_for(policy), pool.k, pool.v,
            jnp.asarray(table), jnp.asarray([n + i], jnp.int32),
            jnp.asarray([[tok]], jnp.int32))
        pool.update(k, v)
        rows.append(np.asarray(logits[0, -1]))
    return np.stack(rows), fed


@pytest.mark.parametrize("config", sorted(SMOKE))
def test_reference_matches_paged_prefill_then_decode(config):
    model = SMOKE[config]
    params = weights.make_params(smoke_conf(config), 7)
    prompt = np.random.default_rng(3).integers(0, model["vocab"], 11)
    got, fed = _program_logits(model, params, prompt, 6, "M23")
    seq = np.concatenate([prompt, fed]).astype(np.int32)
    ref = np.asarray(dense.logits(params, seq, model, "highest"))
    want = ref[len(prompt) - 1:]
    assert got.shape == want.shape
    # M23 carries float32's 24-bit mantissa: the two agree to float32
    # rounding through two layers (|logits| about 1-4)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("precision,lo,hi", [
    ("high", 1e-7, 1e-3), ("bf16", 1e-4, 1e-1), ("fp8", 1e-3, 1.0)])
def test_lower_precisions_depart_by_their_rounding(precision, lo, hi):
    model = SMOKE["chatglm3-6b-3l"]
    params = weights.make_params(smoke_conf("chatglm3-6b-3l"), 8)
    seq = np.random.default_rng(4).integers(0, model["vocab"], 40)
    ref = np.asarray(dense.logits(params, seq, model, "highest"))
    low = np.asarray(dense.logits(params, seq, model, precision))
    err = np.max(np.abs(low - ref)) / np.max(np.abs(ref))
    assert lo < err < hi


def test_twin_columns_put_a_near_tie_at_every_position():
    """The top token's twin lies about ``TWIN_SCALE`` below or above it."""
    model = SMOKE["chatglm3-6b-3l"]
    params = weights.make_params(smoke_conf("chatglm3-6b-3l"), 9)
    seq = np.random.default_rng(5).integers(0, model["vocab"], 40)
    ref = np.asarray(dense.logits(params, seq, model, "highest"))
    top = np.argmax(ref, -1)
    tie = np.abs(ref[np.arange(len(seq)), top]
                 - ref[np.arange(len(seq)), top ^ 1])
    assert np.all(tie < 10 * TWIN_SCALE)
    assert np.median(tie) > 0.1 * TWIN_SCALE


def test_gap_is_zero_for_the_reference_choice_and_the_twin_margin_else():
    model = SMOKE["chatglm3-6b-3l"]
    params = weights.make_params(smoke_conf("chatglm3-6b-3l"), 10)
    seq = np.random.default_rng(6).integers(0, model["vocab"], 32)
    seq = seq.astype(np.int32)
    head_t = np.ascontiguousarray(np.asarray(params["lm_head"]["w"]).T)
    h = np.asarray(dense.final_hidden(params, seq, model))
    ex = h.astype(np.float64) @ head_t[: model["vocab"]].T.astype(np.float64)
    best = np.argmax(ex, -1)
    g, _ = dense.gaps(params, head_t, seq, best, model)
    assert np.all(g == 0)
    g, _ = dense.gaps(params, head_t, seq, best ^ 1, model)
    want = ex.max(-1) - ex[np.arange(len(seq)), best ^ 1]
    np.testing.assert_allclose(g, want, rtol=0, atol=1e-12)
    assert np.all(g > 0)
