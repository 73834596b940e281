"""The DeepSeek-V2-Lite configuration and its cell, added as files: the
configuration loads and builds the program's dimensions, its weights module
builds the program's tree, a whole run at smoke size serves tokens the
reference passes, and a planted tie or fault does what the check promises.
Its two kernel families match nothing in the glm cell's chip traces, and
its two readers read a number from a synthetic trace."""
import dataclasses
import gzip
import json
import re
import time
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, smoke_traffic

import bench.run as R
from bench import check, weights

CELL = "dsv2l-m23-closed"
CONFIG = "deepseek-v2-lite-5l"
# the configuration cut to the smoke size: 1 dense + 1 MoE layer, 16
# experts routed top-6 with 8 held (from expert 4 on) and weighed 16 times
# (DeepSeek-V2's routed_scaling_factor), YaRN on
SMOKE = {
    "family": "moe", "n_layers": 2, "d_model": 64, "vocab": 256,
    "n_heads": 4, "n_kv_heads": 4, "d_ff": 32, "rope_theta": 10000.0,
    "norm_eps": 1e-06, "first_k_dense": 1, "dense_ff": 96, "max_seq": 128,
    "attn_q_chunk": 16, "attn_kv_chunk": 16,
    "mla": {"d_model": 64, "n_heads": 4, "kv_lora": 32, "q_lora": 0,
            "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16,
            "rope_theta": 10000.0, "norm_eps": 1e-06, "yarn_factor": 40.0,
            "yarn_original_max_pos": 64, "yarn_beta_fast": 32.0,
            "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
            "yarn_mscale_all_dim": 0.707},
    "moe": {"d_model": 64, "n_experts": 16, "top_k": 6, "expert_ff": 32,
            "n_shared": 2, "norm_topk_prob": False,
            "routed_scaling_factor": 16.0, "n_held": 8, "first_held": 4},
}
# limits for the smoke size, by the rule the cell's limits follow: the
# program's M23 reads under 1e-6 at 64 wide, the control at `high` above
SMOKE_LIMITS = {"gap_M23": 2e-6, "wrong_length": 0.0, "excluded_M23": 0.5}


def smoke_cell(**traffic) -> R.Cell:
    cell = R.load_cell(CELL)
    conf = dict(cell.config, model=SMOKE)
    return dataclasses.replace(cell, config=conf,
                               traffic=smoke_traffic(cell.traffic, **traffic),
                               limits=SMOKE_LIMITS)


def run(cell, seed, seconds=2.0, **kw):
    return R.run_cell(cell, seed, seconds, False, backend="pallas_interpret",
                      t_start=time.perf_counter(), **kw)


def test_configuration_loads_and_builds_the_program_dims():
    from repro.models.mla import MLADims
    from repro.models.moe import MoEDims

    cell = R.load_cell(CELL)
    assert cell.config["name"] == CONFIG and cell.chips == 1
    assert set(cell.config["reduced"]) == {"num_hidden_layers",
                                           "n_routed_experts", "torch_dtype"}
    cfg = R.model_config(cell.config)
    assert isinstance(cfg.mla, MLADims) and isinstance(cfg.moe, MoEDims)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.dense_ff) == (5, 1, 10944)
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 6)
    assert not cfg.moe.norm_topk_prob
    assert cfg.mla.rope_cos_scale == 1.0
    assert cfg.mla.softmax_scale == pytest.approx(192 ** -0.5 * 1.58962616)
    # the catalog's published values beside the served cut
    assert cell.config["n_routed_experts"] == cfg.moe.held
    assert cell.config["num_experts_per_tok"] == cfg.moe.top_k
    assert {m["name"] for m in cell.per_layer} == {"mla_roofline.decode",
                                                  "expert_roofline.decode"}


def test_weights_module_builds_the_program_tree():
    from repro.models import transformer as T

    cfg = R.model_config({"name": CONFIG, "model": SMOKE})
    conf = dict(R.load_cell(CELL).config, model=SMOKE)
    got = weights.make_params(conf, 2**33 + 41)
    want = jax.eval_shape(lambda k: T.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    router = np.asarray(got["layers"]["moe"]["router"])
    assert router.shape == (1, 64, 16)
    assert 0.8 < router.std() * 64 ** 0.5 < 1.2        # N(0, 1/d_model)


# a sample large enough that the control's widest gap shows
BIG_CHECK = {"max_requests_per_mode": 80, "tokens": 2000}


@pytest.fixture(scope="module")
def smoke_run():
    return run(smoke_cell(check=BIG_CHECK), 2**33 + 43, seconds=6.0,
               controls=("high",))


def test_served_tokens_pass(smoke_run):
    assert smoke_run["window"]["compiles"] == 0
    assert smoke_run["correct"], smoke_run["checks"]
    assert 0 <= smoke_run["checks"]["excluded_M23"]["value"] \
        <= SMOKE_LIMITS["excluded_M23"]


def test_control_at_high_fails(smoke_run):
    control = {"gap_M23": smoke_run["controls"]["gap_M23@high"],
               "excluded_M23": smoke_run["controls"]["excluded_M23@high"],
               "wrong_length": 0.0}
    ok, _ = check.verdict(control, SMOKE_LIMITS)
    assert not ok, control


def test_router_at_bf16_is_not_correct(monkeypatch):
    """The timed path with the router one bf16 pass: its routes flip where
    the k-th and (k+1)-th logits lie within bf16 rounding, far outside the
    tie margin, and a flipped route moves the served token (the smoke
    configuration weighs routed experts 16 times, as DeepSeek-V2 does, so
    a flip shows within one run).  Experts at bf16 move a token's logit and
    its twin's alike, which the comparison cannot see (PERF.md §7)."""
    from repro.core import context as context_lib

    real = context_lib.resolve_request_policy
    monkeypatch.setattr(
        context_lib, "resolve_request_policy",
        lambda **kw: real(**kw).overlay({"moe_router": "M8"}))
    res = run(smoke_cell(check=BIG_CHECK), 2**33 + 44, seconds=6.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["gap_M23"]["value"] > 100 * SMOKE_LIMITS["gap_M23"]


def test_planted_near_tie_is_excluded():
    """A router whose columns are all equal ties every expert at every
    position: nothing after the first position is decided."""
    from bench.reference import deepseek_v2 as ref

    conf = dict(R.load_cell(CELL).config, model=SMOKE)
    params = weights.make_params(conf, 2**33 + 45)
    head_t = np.ascontiguousarray(np.asarray(params["lm_head"]["w"]).T)
    tokens = np.arange(1, 17, dtype=np.int32)
    _, _, compared = ref.gaps(params, head_t, tokens, tokens, SMOKE)
    assert compared.all()
    tied = dict(params, layers=dict(params["layers"]))
    tied["layers"]["moe"] = dict(params["layers"]["moe"], router=jnp.ones_like(
        params["layers"]["moe"]["router"]))
    _, _, compared = ref.gaps(tied, head_t, tokens, tokens, SMOKE)
    assert not compared.any()


def test_near_tie_counts_only_a_held_expert():
    """The rule on hand-made router logits: the k-th and (k+1)-th within the
    margin tie only where one of the two is held here (4-7)."""
    from bench.reference import deepseek_v2 as ref

    moe = dict(SMOKE["moe"], top_k=3, first_held=4, n_held=4)
    base = -np.arange(16, dtype=np.float32)          # expert i at rank i
    rows = np.stack([base] * 3)
    rows[0, [2, 3]] = -2.0          # ranks 3 and 4: experts 2, 3, not held
    rows[1, [2, 4]] = rows[1, [4, 2]]
    rows[1, 2] = rows[1, 4] = -2.0  # experts 4 (held) and 2 tie at rank 3
    bound = np.full(3, 1.0, np.float32)
    ties = np.asarray(ref.near_tie(moe, jnp.asarray(rows), jnp.asarray(
        bound / ref.TIE_MULTIPLE * 1e-3)))
    assert ties.tolist() == [False, True, False]


TRACES = sorted((ROOT / "bench" / "tests" / "data").glob(
    "chip_trace_2ticks*.json.gz"))


def test_router_probe_reads_the_program_routers():
    """``router_probe.program_routers`` records one (T, n_experts) array of
    router logits per MoE layer from the program's own forward pass, and
    they lie within the M23 bound of the reference's."""
    import repro.mp as mp
    from bench.reference import deepseek_v2 as ref
    from bench.router_probe import program_routers

    conf = dict(R.load_cell(CELL).config, model=SMOKE)
    params = weights.make_params(conf, 2**33 + 47)
    cfg = R.model_config(conf)
    policy = R.engine_policy(R.load_cell(CELL).traffic)
    tokens = jnp.arange(3, 35, dtype=jnp.int32)
    with mp.context(backend="pallas_interpret"):
        got = jax.jit(lambda p, t: program_routers(p, t, cfg, policy))(
            params, tokens)
    want = ref.forward(params, tokens, SMOKE)[1]
    assert len(got) == len(want) == SMOKE["n_layers"] - SMOKE["first_k_dense"]
    for g, (w, bound) in zip(got, want):
        assert g.shape == (32, SMOKE["moe"]["n_experts"])
        diff = np.abs(np.asarray(g, np.float64) - np.asarray(w, np.float64))
        assert 0 < np.max(diff.max(-1) / np.asarray(bound)) < 1


@pytest.mark.parametrize("family", ["mla_attention", "moe_expert"])
def test_new_kernel_families_match_nothing_of_the_glm_cell(family):
    pats = [re.compile(p) for p in R.kernel_families()[family]["patterns"]]
    assert TRACES
    for path in TRACES:
        for ops in json.load(gzip.open(path))["device_ops"].values():
            for ev in ops:
                text = ev[3] if len(ev) > 3 else ev[0]
                assert not any(p.search(text) for p in pats), text[:200]


def _event(name: str, text: str, t0: float, t1: float):
    return (name, t0, t1, text)


LATENT = ("%mp_latent_paged_attention.5 = f32[64,16,512]{2,1,0} custom-call("
          "%a, %b, %c, %d, %e, %f), custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={s32[64,40]{1,0}, s32[64]{0}, "
          "f32[64,16,512]{2,1,0}, f32[64,16,64]{2,1,0}, "
          "f32[2561,64,512]{2,1,0}, f32[2561,64,64]{2,1,0}}")
EXPERT = ("%mp_matmul_prelimbed.9 = f32[64,1536]{1,0} custom-call(%a, %b), "
          "custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={f32[64,2048]{1,0}, "
          "bf16[3,2048,1536]{2,1,0}}")
OTHER = ("%mp_matmul_prelimbed.3 = f32[64,2816]{1,0} custom-call(%a, %b), "
         "custom_call_target=\"tpu_custom_call\", "
         "operand_layout_constraints={f32[64,2048]{1,0}, "
         "bf16[3,2048,2816]{2,1,0}}")


@pytest.mark.parametrize("reader", ["mla_roofline", "expert_roofline"])
def test_readers_read_a_synthetic_trace(reader):
    """Two decode ticks of 64 tokens at context 500: each reader reads its
    kernel's events only, and a share between 0 and 100."""
    from bench import roofline, trace as trace_lib
    from bench.driver import StepRec

    model = R.load_cell(CELL).config["model"]
    ms = 1e6
    ops = []
    for k in range(2):
        t = k * 40 * ms
        ops += [_event("a", LATENT, t, t + 3 * ms),
                _event("b", EXPERT, t + 3 * ms, t + 5 * ms),
                _event("c", OTHER, t + 5 * ms, t + 9 * ms)]
    tr = trace_lib.Trace({"/device:TPU:0": ops}, [
        ("bench.traced", 0.0, 80 * ms),
        ("bench.step", 0.0, 30 * ms), ("bench.step", 40 * ms, 70 * ms)])
    steps = [StepRec(t0=0.0, t1=0.03, did=True,
                     decodes=[(500, "M23")] * 64) for _ in range(2)]
    ctx = R.ReadContext(model=model, peak=roofline.PEAKS["TPU v5 lite"],
                        window_steps=steps, reference=None,
        trace=tr, periods=trace_lib.step_periods(tr, steps),
        kernels=R.kernel_families())
    got = R._load_reader(f"{reader}.decode")(ctx, "decode")
    assert got is not None and 0 < got < 100
    mod = __import__(f"bench.metrics.{reader}", fromlist=["work"])
    if reader == "mla_roofline":
        flops, nbytes = mod.work(model, [500] * 64)
        least = 5 * max(flops / 197e12, nbytes / 819e9)
        assert got == pytest.approx(100 * 2 * least / (2 * 3e-3))
    else:
        least = sum(max(f / 197e12, n / 819e9)
                    for f, n in mod.work(model, 64, 4))
        assert got == pytest.approx(100 * 2 * least / (2 * 2e-3))
