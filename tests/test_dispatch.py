"""Dispatch-layer suite: backend parity per mode (ref / pallas_interpret /
sharded), mode-aware collective payloads, autotuner cache round-trips, and
registry routing.  DESIGN.md §5, §7."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    PrecisionMode, available_backends, mp_matmul, register_backend,
    unregister_backend, use_backend, get_default_backend,
)
from repro.core.dispatch import dispatch
from repro.core.modes import MODE_TABLE, STATIC_MODES
from repro.kernels import autotune, ref
from repro.launch.mesh import make_matmul_mesh

PARITY_BACKENDS = ("ref", "pallas_interpret", "sharded")


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _rel(out, gold):
    return float(np.linalg.norm(np.asarray(out, np.float64) - gold)
                 / max(np.linalg.norm(gold), 1e-30))


# ----------------------------------------------------------------- parity
@pytest.mark.parametrize("mode", STATIC_MODES)
def test_backend_parity_per_mode(mode):
    """Every backend must land within the mode's error budget of the f64
    golden product, and the backends must agree with each other to the same
    tolerance (acceptance criterion for the sharded path)."""
    rng = np.random.default_rng(0)
    a, b = _rand(rng, (96, 200)), _rand(rng, (200, 128))
    gold = ref.matmul_golden_f64(a, b)
    bound = float(MODE_TABLE[mode].rel_err_bound)
    outs = {}
    for backend in PARITY_BACKENDS:
        out = mp_matmul(a, b, mode, backend=backend)
        outs[backend] = np.asarray(out, np.float64)
        assert _rel(out, gold) < bound, (mode, backend)
    for backend in ("pallas_interpret", "sharded"):
        mutual = np.linalg.norm(outs[backend] - outs["ref"]) \
            / np.linalg.norm(outs["ref"])
        assert mutual < bound, (mode, backend, mutual)


def test_sharded_runs_on_multi_device_mesh():
    mesh = make_matmul_mesh()
    assert mesh.shape["data"] >= 2, \
        "sharded tests need >=2 fake devices (tests/conftest.py sets 8)"
    rng = np.random.default_rng(1)
    # K=200 is NOT divisible by the device count: exercises zero K-padding
    a, b = _rand(rng, (64, 200)), _rand(rng, (200, 64))
    out = mp_matmul(a, b, PrecisionMode.M16, backend="sharded")
    out_ref = mp_matmul(a, b, PrecisionMode.M16, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)


def test_sharded_gradients_flow():
    rng = np.random.default_rng(2)
    a, b = _rand(rng, (32, 64)), _rand(rng, (64, 32))

    def loss(a, b):
        return jnp.sum(mp_matmul(a, b, PrecisionMode.M16, backend="sharded",
                                 bwd_mode=PrecisionMode.M23) ** 2)

    ga, gb = jax.grad(loss, argnums=(0, 1))(a, b)
    ga_r, gb_r = jax.grad(
        lambda a, b: jnp.sum(mp_matmul(a, b, PrecisionMode.M16, backend="ref",
                                       bwd_mode=PrecisionMode.M23) ** 2),
        argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_r), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_r), rtol=2e-4,
                               atol=2e-4)


def test_sharded_falls_back_inside_shard_map():
    """mp_matmul(backend="sharded") inside an existing shard_map body (the
    MoE expert-parallel shape) must fall back to local compute instead of
    attempting an unsupported nested shard_map."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(7)
    a, b = _rand(rng, (16, 64)), _rand(rng, (64, 16))

    def body(a, b):
        return mp_matmul(a, b, PrecisionMode.M16, backend="sharded")

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(a, b)
    out_ref = mp_matmul(a, b, PrecisionMode.M16, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=1e-6, atol=1e-6)


def test_sharded_batched_and_dd_fall_back_cleanly():
    rng = np.random.default_rng(3)
    a3 = _rand(rng, (3, 16, 64))
    b3 = _rand(rng, (3, 64, 16))
    out = mp_matmul(a3, b3, PrecisionMode.M16, backend="sharded")
    out_ref = mp_matmul(a3, b3, PrecisionMode.M16, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=1e-6)


def test_sharded_collective_bytes_scale_with_mode():
    """The tentpole claim: the cross-device reduce ships n_orders×M×N fp32 —
    low modes cut communication bytes.  M23 (3 orders) must move ~3× the
    all-reduce bytes of M8 (1 order)."""
    from repro.analysis import hlo_parser

    rng = np.random.default_rng(4)
    a, b = _rand(rng, (64, 256)), _rand(rng, (256, 128))

    def coll_bytes(mode):
        txt = jax.jit(
            lambda a, b: mp_matmul(a, b, mode, backend="sharded")
        ).lower(a, b).compile().as_text()
        totals = hlo_parser.analyze_hlo(txt)
        return totals.coll_by_kind.get("all-reduce", 0.0)

    b8 = coll_bytes(PrecisionMode.M8)
    b23 = coll_bytes(PrecisionMode.M23)
    assert b8 > 0 and b23 > 0
    ratio = b23 / b8
    assert 2.0 < ratio <= 4.0, (b8, b23, ratio)


def test_partials_match_ref_combine():
    """mp_matmul_partials + combine_partials == the oracle (the sharded
    backend's local/remote split is algebraically a no-op)."""
    rng = np.random.default_rng(5)
    a, b = _rand(rng, (48, 96)), _rand(rng, (96, 32))
    for mode in STATIC_MODES:
        stacked = ref.mp_matmul_partials(a, b, mode)
        assert stacked.shape[0] == MODE_TABLE[mode].n_orders
        out = ref.combine_partials(stacked, mode)
        gold = ref.matmul_golden_f64(a, b)
        assert _rel(out, gold) < float(MODE_TABLE[mode].rel_err_bound)


# ----------------------------------------------------------------- autotuner
CANDS = [(32, 64, 128), (32, 128, 128)]


def test_autotune_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    blocks = autotune.autotune(64, 192, 128, PrecisionMode.M16,
                               interpret=True, iters=1, candidates=CANDS)
    assert tuple(blocks) in {tuple(c) for c in CANDS}
    path = os.path.join(str(tmp_path), f"{autotune.device_kind()}.json")
    assert os.path.exists(path), "winner must persist on disk"
    # a fresh process (simulated: drop the in-memory table) reuses the disk
    # table without sweeping — candidates=[] would raise if a sweep ran
    autotune.clear_memory_cache()
    again = autotune.autotune(64, 192, 128, PrecisionMode.M16,
                              interpret=True, iters=1, candidates=[])
    assert tuple(again) == tuple(blocks)
    assert autotune.lookup(64, 192, 128, PrecisionMode.M16) == tuple(blocks)
    autotune.clear_memory_cache()


def test_autotune_candidates_respect_vmem_budget():
    from repro.kernels.mp_matmul import vmem_bytes

    cands = autotune.candidate_blocks(4096, 4096, 4096, PrecisionMode.M52)
    assert cands, "M52 must keep at least one feasible tile"
    for (bm, bk, bn) in cands:
        assert vmem_bytes(PrecisionMode.M52, bm, bk, bn) \
            <= autotune.VMEM_BUDGET_BYTES
    # the M8 sweep space must be strictly larger: fewer limbs/accumulators
    assert len(autotune.candidate_blocks(4096, 4096, 4096, PrecisionMode.M8)) \
        > len(cands)


def test_tuned_blocks_reach_pallas_dispatch(tmp_path, monkeypatch):
    """dispatch() must read the autotune table for the pallas backend (pure
    lookup — no sweep without REPRO_MP_AUTOTUNE=1)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    rng = np.random.default_rng(6)
    a, b = _rand(rng, (64, 192)), _rand(rng, (192, 128))
    key = autotune.table_key(64, 192, 128, PrecisionMode.M16, jnp.float32)
    autotune.save_table({key: [32, 64, 128]})
    out = dispatch(a, b, PrecisionMode.M16, backend="pallas_interpret")
    out_ref = dispatch(a, b, PrecisionMode.M16, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=3e-6, atol=2e-5)
    autotune.clear_memory_cache()


# ----------------------------------------------------------------- registry
def test_registry_routing_and_errors():
    assert set(("ref", "pallas", "pallas_interpret", "sharded")) \
        <= set(available_backends())
    with pytest.raises(ValueError):
        dispatch(jnp.zeros((2, 2)), jnp.zeros((2, 2)), PrecisionMode.M8,
                 backend="nope")
    # built-ins are protected in both directions
    with pytest.raises(ValueError):
        register_backend("ref", lambda *a: None)
    with pytest.raises(ValueError):
        unregister_backend("sharded")
    calls = []

    def custom(a, b, fmt, out_dtype):
        calls.append(fmt)  # backends receive the resolved MPFormat
        return ref.mp_matmul_ref(a, b, fmt, out_dtype=out_dtype)

    register_backend("custom_test", custom)
    try:
        out = mp_matmul(jnp.ones((4, 8)), jnp.ones((8, 4)), PrecisionMode.M8,
                        backend="custom_test")
        assert [f.name for f in calls] == ["M8"]
        np.testing.assert_allclose(np.asarray(out), 8.0)
    finally:
        unregister_backend("custom_test")


def test_pallas_backend_needs_a_tpu():
    """backend="pallas" means compiled Mosaic: on a host without a TPU it
    raises instead of quietly running the interpreter, in dispatch and at
    the engine's first step alike (CPU callers ask for pallas_interpret)."""
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    a, b = jnp.ones((8, 128)), jnp.ones((128, 128))
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        dispatch(a, b, PrecisionMode.M16, backend="pallas")
    cfg = get_config("paper-mpfp-100m", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=48,
                      matmul_backend="pallas")
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        eng.generate([np.asarray([1, 2, 3], np.int32)], max_new=1)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The entry points' compile cache: $JAX_COMPILATION_CACHE_DIR when set
    (JAX reads it itself, so the helper leaves the config alone), else the
    fixed <repo>/.jax_cache."""
    from pathlib import Path

    from repro.launch.compile_cache import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo_cache = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    try:
        assert configure_compile_cache() == repo_cache
        assert jax.config.jax_compilation_cache_dir == repo_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_engine_pins_backend_end_to_end():
    """A ServeEngine built with matmul_backend="sharded" must decode through
    the multi-device path and produce the same tokens as the default engine
    (greedy argmax is insensitive to sub-ulp backend differences)."""
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    cfg = get_config("paper-mpfp-100m", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompt = [np.asarray([1, 2, 3], np.int32)]
    ref_toks = ServeEngine(cfg, params, max_batch=2, max_seq=48
                           ).generate(prompt, max_new=3)
    sh_toks = ServeEngine(cfg, params, max_batch=2, max_seq=48,
                          matmul_backend="sharded").generate(prompt, max_new=3)
    assert ref_toks == sh_toks


def test_use_backend_context_restores_default():
    before = get_default_backend()
    with use_backend("pallas_interpret"):
        assert get_default_backend() == "pallas_interpret"
    assert get_default_backend() == before
    with pytest.raises(ValueError):
        with use_backend("nope"):
            pass
