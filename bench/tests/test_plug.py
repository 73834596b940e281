"""A configuration brings its own weights builder and reference as files
(``weights.module`` and ``reference`` in its configuration file): the
harness loads what the file names, follows it through a whole run, and
holds a reference's undecided positions (``excluded_<format>``) to the
cell's limits.  The dense configuration reads as it did before the names
were introduced: the same weights to the bit and the same gaps."""
import dataclasses
import json
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, SMOKE, smoke_conf, smoke_traffic

import bench.run as R
from bench import check, weights
from bench.reference import dense

GLM = "chatglm3-6b-3l"


def _build_before(model: dict, twin_scale: float, key) -> dict:
    """The dense builder as it stood before configurations named their
    module (a frozen copy): the same key splits, in the same order."""
    d, L = model["d_model"], model["n_layers"]
    h, hk = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // h
    ff, vp = model["d_ff"], weights.padded_vocab(model)
    f32 = jnp.float32
    ks = iter(jax.random.split(key, 16))

    def mat(shape):
        return jax.random.normal(next(ks), shape, f32) * shape[-2] ** -0.5

    def twins(base, delta):
        return jnp.stack([base, base + twin_scale * delta], -1).reshape(
            base.shape[0], -1)

    def gain(shape):
        return jax.random.uniform(next(ks), shape, f32, 0.8, 1.2)

    return {
        "embed": {"table": jax.random.normal(next(ks), (vp, d), f32)},
        "layers": {
            "attn": {"wq": mat((L, d, h * dh)), "wk": mat((L, d, hk * dh)),
                     "wv": mat((L, d, hk * dh)), "wo": mat((L, h * dh, d))},
            "mlp": {"w_gate": mat((L, d, ff)), "w_up": mat((L, d, ff)),
                    "w_down": mat((L, ff, d))},
            "ln1": {"w": gain((L, d))},
            "ln2": {"w": gain((L, d))},
        },
        "ln_final": {"w": gain((d,))},
        "lm_head": {"w": twins(mat((d, vp // 2)), mat((d, vp // 2)))},
    }


def test_make_params_by_the_named_module_is_the_dense_build_bit_for_bit():
    conf = smoke_conf(GLM)
    seed = 2**33 + 21
    got = weights.make_params(conf, seed)
    want = jax.jit(lambda k: _build_before(conf["model"], 3e-5, k))(
        weights.jax_key(seed))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint32),
                              np.asarray(b).view(np.uint32))


def _picked(model: dict, n: int, seed: int) -> list:
    """Finished requests of fixed prompts and served tokens."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, model["vocab"], rng.integers(4, 20))
        served = rng.integers(0, model["vocab"], rng.integers(3, 12))
        out.append(SimpleNamespace(
            req=SimpleNamespace(prompt=prompt.astype(np.int32), mode="M23",
                                idx=i, max_new=len(served)),
            sreq=SimpleNamespace(out=[int(x) for x in served], state="done")))
    return out


def _widest_before(params, model, picked, precisions):
    """``check.widest_gaps`` as it stood before it took the reference
    (a frozen copy), on the dense reference."""
    out = {}
    head_t = np.ascontiguousarray(np.asarray(params["lm_head"]["w"]).T)
    for t in picked:
        tokens, cand, sl = check._padded(t.req.prompt, t.sreq.out)
        for prec in precisions:
            g, _ = dense.gaps(params, head_t, tokens, cand, model, prec)
            w = float(np.max(g[sl]))
            key = f"gap_{t.req.mode}" + ("" if prec == "highest"
                                         else f"@{prec}")
            out[key] = max(out.get(key, 0.0), w)
    return out


def test_widest_gaps_on_the_dense_reference_read_as_before():
    conf = smoke_conf(GLM)
    model = conf["model"]
    params = weights.make_params(conf, 2**33 + 22)
    picked = _picked(model, 4, 5)
    got = check.widest_gaps(dense, params, model, picked, ("highest", "high"))
    want = _widest_before(params, model, picked, ("highest", "high"))
    assert got == want
    assert set(got) == {"gap_M23", "gap_M23@high"}   # no excluded_ share


class _Masked:
    """A reference that leaves the first ``n_off`` positions of every
    sequence undecided, with gaps of its own."""

    def __init__(self, n_off: int):
        self.n_off = n_off

    def gaps(self, params, head_t, tokens, cand, model, precision):
        g = np.arange(len(tokens), dtype=np.float64)
        compared = np.arange(len(tokens)) >= self.n_off
        return g, np.asarray(cand), compared


def test_widest_gaps_counts_the_served_positions_a_reference_leaves_out():
    model = SMOKE[GLM]
    picked = _picked(model, 3, 6)
    params = {"lm_head": {"w": np.zeros((4, 4), np.float32)}}
    served = sum(len(t.sreq.out) for t in picked)
    # a sequence's served positions start at len(prompt) - 1: leaving out
    # the positions below the shortest prompt's last leaves out none
    n_off = min(len(t.req.prompt) for t in picked)
    nums = check.widest_gaps(_Masked(n_off - 1), params, model, picked)
    assert nums["excluded_M23"] == 0.0
    # one more leaves out the first served position of the shortest
    nums = check.widest_gaps(_Masked(n_off), params, model, picked)
    lost = sum(max(0, n_off - (len(t.req.prompt) - 1)) for t in picked)
    assert nums["excluded_M23"] == lost / served > 0
    # the widest gap is over compared positions: gap = position index, so
    # it is the last served position of the longest sequence
    assert nums["gap_M23"] == max(len(t.req.prompt) + len(t.sreq.out) - 2
                                  for t in picked)
    ok, checks = check.verdict(nums, {"gap_M23": 1e9})
    assert not ok and checks["excluded_M23"]["limit"] is None


@pytest.mark.parametrize("module", ["deepseek_v2_lite_16b", "mamba2_130m"])
def test_model_config_builds_nested_dims_from_a_dict(module):
    """A configuration file's ``model`` object carries the program's
    nested dimensions as plain objects; the harness builds the classes."""
    import importlib

    smoke = importlib.import_module(f"repro.configs.{module}").SMOKE
    model = json.loads(json.dumps(dataclasses.asdict(smoke)))
    name = model.pop("name")
    cfg = R.model_config({"name": name, "model": model})
    assert cfg == smoke
    for key in ("mla", "moe", "ssm"):
        assert type(getattr(cfg, key)) is type(getattr(smoke, key))


def test_model_config_builds_deepseek_v2_lite_dims_as_a_file_writes_them():
    from repro.configs.deepseek_v2_lite_16b import SMOKE as DS

    model = {"family": "moe", "n_layers": 2, "d_model": 64, "vocab": 256,
             "n_heads": 4, "n_kv_heads": 4, "d_ff": 96,
             "mla": {"d_model": 64, "n_heads": 4, "kv_lora": 32, "q_lora": 0,
                     "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16},
             "moe": {"d_model": 64, "n_experts": 4, "top_k": 2,
                     "expert_ff": 96, "n_shared": 2, "capacity_factor": 2.0},
             "first_k_dense": 1, "dense_ff": 128, "max_seq": 128,
             "attn_q_chunk": 16, "attn_kv_chunk": 16}
    cfg = R.model_config({"name": DS.name, "model": model})
    assert cfg.mla == DS.mla and cfg.moe == DS.moe
    assert cfg == DS


# --- a second configuration, by new files alone --------------------------

PROBE_WEIGHTS = '''"""Test builder: the dense one, recording that it was called."""
from bench import weights

CALLS = []


def build(model, twin_scale, key):
    CALLS.append(model["d_model"])
    return weights.build(model, twin_scale, key)
'''

PROBE_REFERENCE = '''"""Test reference: the dense one, recording each call; at ``highest``
it leaves one served position of each sequence undecided (the last whose
candidate is not token 0)."""
import numpy as np

from bench.reference import dense
from bench.reference.dense import decode_flops, prefill_flops  # noqa: F401

CALLS = []


def gaps(params, head_t, tokens, cand, model, precision="highest"):
    g, chosen = dense.gaps(params, head_t, tokens, cand, model, precision)
    compared = np.ones(len(g), bool)
    served = np.flatnonzero(np.asarray(cand))
    if precision == "highest" and len(served):
        compared[served[-1]] = False
    CALLS.append(precision)
    return g, chosen, compared
'''

TOY = "toy-m23-closed"


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with one more configuration and cell, added
    as files: a configuration naming the two modules above, the modules, a
    workload entry and a limits file."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "bench" / "probe").mkdir()
    (root / "bench" / "probe" / "weights.py").write_text(PROBE_WEIGHTS)
    (root / "bench" / "reference" / "probe.py").write_text(PROBE_REFERENCE)
    conf = dict(smoke_conf(GLM), name="toy")
    conf["weights"] = dict(conf["weights"], module="bench.probe.weights")
    conf["reference"] = "bench.reference.probe"
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(conf))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="toy",
                                 file="bench/configs/toy.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name=TOY,
                                   config="toy"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _toy_run(root, limits: dict, seed: int):
    (root / "bench" / "limits" / f"{TOY}.json").write_text(
        json.dumps(limits))
    cell = R.load_cell(TOY, root)
    cell = dataclasses.replace(cell, traffic=smoke_traffic(cell.traffic))
    return cell, R.run_cell(cell, seed, 2.0, False,
                            backend="pallas_interpret",
                            t_start=time.perf_counter())


def test_second_configuration_runs_its_named_modules(toy_root):
    from bench import plug

    limits = {"gap_M23": 2e-6, "wrong_length": 0.0}
    cell, res = _toy_run(toy_root, limits, 2**33 + 31)
    built = plug.load("bench.probe.weights", toy_root).CALLS
    ref = plug.load("bench.reference.probe", toy_root)
    assert cell.reference is ref
    assert built == [64]                 # the run's weights, built once
    assert ref.CALLS and set(ref.CALLS) == {"highest"}
    # served positions left out and no limit for them: not correct
    share = res["checks"]["excluded_M23"]
    assert share["limit"] is None and 0 < share["value"] < 0.5
    assert res["checks"]["gap_M23"]["value"] <= limits["gap_M23"]
    assert not res["correct"]
    # a limit at or above the share: correct
    _, res = _toy_run(toy_root, dict(limits, excluded_M23=0.5), 2**33 + 32)
    assert res["checks"]["excluded_M23"]["limit"] == 0.5
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("key", ["weights.module", "reference"])
def test_load_cell_refuses_a_configuration_without_a_module(toy_root, key):
    path = toy_root / "bench" / "configs" / "toy.json"
    conf = json.loads(path.read_text())
    broken = dict(conf, weights={k: v for k, v in conf["weights"].items()
                                 if k != "module"}) \
        if key == "weights.module" else \
        {k: v for k, v in conf.items() if k != "reference"}
    path.write_text(json.dumps(broken))
    try:
        with pytest.raises(SystemExit, match=f"no '{key}'"):
            R.load_cell(TOY, toy_root)
    finally:
        path.write_text(json.dumps(conf))
