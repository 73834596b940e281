"""Core MPFP unit + property tests: modes, limbs, auto mode, policy, classify."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

# real hypothesis when installed (CI: requirements-dev.txt), deterministic
# fallback otherwise — this suite must never skip wholesale (it was one of
# the two perpetually-skipped tier-1 files)
from proptest_compat import given, settings, st

from repro.core import (
    MODE_TABLE, PrecisionMode, classify, decompose, exception_counts,
    mode_flops, mp_matmul, reconstruct, select_mode_index, spec,
    validate_mode_pair, PrecisionPolicy, get_policy, all_finite,
)
from repro.core.limbs import (
    dd_from_f64, dd_to_f64, residual_scale, round_to_limbs, significant_limbs,
)
from repro.core.auto import auto_report, mp_matmul_auto
from repro.kernels.ref import matmul_golden_f64, naive_multipass_ref


# ---------------------------------------------------------------- mode table
def test_mode_table_structure():
    # paper Table I: 6 modes; mode bits
    assert PrecisionMode.AUTO.mode_bits == "000"
    assert PrecisionMode.M8.mode_bits == "001"
    assert PrecisionMode.M52.mode_bits == "101"
    # Karatsuba economy: 2 limbs -> 3 products, not 4
    assert spec(PrecisionMode.M16).n_products == 3
    assert spec(PrecisionMode.M23).n_products == 6
    assert spec(PrecisionMode.M36).n_products == 15
    assert spec(PrecisionMode.M52).n_products == 28
    # products sorted by descending order (small-magnitude-first accumulation)
    prods = spec(PrecisionMode.M23).products
    orders = [i + j for i, j in prods]
    assert orders == sorted(orders, reverse=True)


def test_mode_select_error_signal():
    """Paper: operand mode mismatch -> error signal."""
    with pytest.raises(ValueError, match="mode-select error"):
        validate_mode_pair(PrecisionMode.M8, PrecisionMode.M16)
    assert validate_mode_pair(PrecisionMode.M16, PrecisionMode.M16) == PrecisionMode.M16


def test_auto_spec_resolution_is_rejected():
    with pytest.raises(ValueError):
        spec(PrecisionMode.AUTO)


def test_mode_flops_scale_with_products():
    f8 = mode_flops(PrecisionMode.M8, 128, 128, 128)
    f16 = mode_flops(PrecisionMode.M16, 128, 128, 128)
    assert f16 == 3 * f8


# ---------------------------------------------------------------- limbs
@settings(max_examples=30, deadline=None)
@given(st.integers(1, 3), st.integers(0, 2**20))
def test_limb_roundtrip_property(n_limbs, seed):
    """Property: reconstruct(decompose(x,k)) is the round-to-8k-bit value; for
    k=3 it is (near-)exact for fp32 inputs."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((16, 16)) * 10.0 ** rng.integers(-3, 4),
                    jnp.float32)
    limbs = decompose(x, n_limbs)
    assert limbs.shape == (n_limbs, 16, 16) and limbs.dtype == jnp.bfloat16
    recon = reconstruct(limbs)
    rel = float(jnp.max(jnp.abs(recon - x)) / jnp.max(jnp.abs(x)))
    assert rel <= 2.0 ** (-8 * n_limbs + 2)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**20))
def test_dd_roundtrip_property(seed):
    rng = np.random.default_rng(seed)
    x64 = rng.standard_normal((8, 8))
    d = dd_from_f64(x64)
    back = dd_to_f64(d)
    assert np.max(np.abs(back - x64)) <= 2.0 ** -45 * np.max(np.abs(x64))


def test_round_to_bf16_matches_convert_bitwise():
    """The integer-op bf16 rounding the limb cascade uses (it survives XLA's
    excess-precision rewrites on TPU) is the f32->bf16->f32 round trip, bit
    for bit: ties to even, signed zeros, subnormals, overflow to inf, NaN."""
    from repro.core.limbs import round_to_bf16

    rng = np.random.default_rng(0)
    x = np.concatenate([
        (rng.standard_normal(4096)
         * 10.0 ** rng.integers(-40, 38, 4096)).astype(np.float32),
        np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 3.4028235e38,
                  -3.4028235e38, 1e-45, 2.3e-40, 1.0 + 2.0 ** -8,
                  1.0 + 3 * 2.0 ** -8], np.float32)])
    got = np.asarray(round_to_bf16(jnp.asarray(x)))
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_significant_limbs_detects_integers():
    ints = jnp.asarray(np.arange(-100, 100, dtype=np.float32).reshape(10, 20))
    assert int(significant_limbs(ints)) == 1
    floats = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                         jnp.float32)
    assert int(significant_limbs(floats)) >= 2
    assert float(residual_scale(ints, 1)) == 0.0


def test_round_to_limbs_is_idempotent():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    r1 = round_to_limbs(x, 2)
    r2 = round_to_limbs(r1, 2)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


# ---------------------------------------------------------------- auto mode
def test_auto_mode_selects_cheap_for_integers():
    rng = np.random.default_rng(1)
    ai = jnp.asarray(rng.integers(-50, 50, (32, 32)), jnp.float32)
    bi = jnp.asarray(rng.integers(-50, 50, (32, 32)), jnp.float32)
    rep = auto_report(ai, bi)
    assert rep["selected_mode"] == PrecisionMode.M8
    # integer products are exact in mode M8 (fits 8-bit mantissa x MXU fp32 acc)
    out = mp_matmul_auto(ai, bi)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ai) @ np.asarray(bi))


def test_auto_mode_escalates_for_full_mantissa():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    idx = int(select_mode_index(a, b))
    assert idx >= 1  # at least M16 for full-mantissa data


def test_auto_mode_consensus_takes_wider_operand():
    rng = np.random.default_rng(3)
    ints = jnp.asarray(rng.integers(-50, 50, (32, 32)), jnp.float32)
    floats = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    idx_mixed = int(select_mode_index(ints, floats))
    idx_ints = int(select_mode_index(ints, ints))
    assert idx_mixed > idx_ints


def test_auto_mode_under_jit():
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
    out = jax.jit(lambda a, b: mp_matmul(a, b, PrecisionMode.AUTO))(a, b)
    gold = matmul_golden_f64(a, b)
    rel = np.linalg.norm(np.asarray(out, np.float64) - gold) / np.linalg.norm(gold)
    assert rel < 2.0 ** -12


# ---------------------------------------------------------------- accuracy
@pytest.mark.parametrize("mode", [PrecisionMode.M8, PrecisionMode.M16,
                                  PrecisionMode.M23])
def test_mode_error_within_budget(mode):
    rng = np.random.default_rng(6)
    K = 384
    a = jnp.asarray(rng.standard_normal((128, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, 128)), jnp.float32)
    gold = matmul_golden_f64(a, b)
    out = mp_matmul(a, b, mode)
    rel = np.linalg.norm(np.asarray(out, np.float64) - gold) / np.linalg.norm(gold)
    assert rel < MODE_TABLE[mode].rel_err_bound, (mode, rel)


def test_karatsuba_order_cut_vs_naive_multipass():
    """The order cut (drop ll) must not cost accuracy at M16: the dropped
    product is below the kept-terms' rounding floor (Karatsuba economy)."""
    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
    gold = matmul_golden_f64(a, b)
    gn = np.linalg.norm(gold)
    cut = mp_matmul(a, b, PrecisionMode.M16)
    naive = naive_multipass_ref(a, b, PrecisionMode.M16)
    err_cut = np.linalg.norm(np.asarray(cut, np.float64) - gold) / gn
    err_naive = np.linalg.norm(np.asarray(naive, np.float64) - gold) / gn
    assert err_cut < 1.5 * err_naive + 2.0 ** -20  # no meaningful accuracy loss
    # ... while doing 3/4 of the multiplies (asserted in test_mode_table)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**20), st.sampled_from([-8, 0, 8]))
def test_scale_invariance_property(seed, log_scale):
    """bf16 limbs share fp32's exponent range -> mode error is scale-free."""
    rng = np.random.default_rng(seed)
    scale = float(2.0 ** log_scale)
    a = jnp.asarray(rng.standard_normal((32, 64)) * scale, jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 32)) * scale, jnp.float32)
    gold = matmul_golden_f64(a, b)
    out = mp_matmul(a, b, PrecisionMode.M16)
    rel = np.linalg.norm(np.asarray(out, np.float64) - gold) / np.linalg.norm(gold)
    assert rel < MODE_TABLE[PrecisionMode.M16].rel_err_bound


# ---------------------------------------------------------------- classify
def test_exception_signals():
    x = jnp.asarray([0.0, np.inf, -np.inf, np.nan, 1e-40, 1.0], jnp.float32)
    c = exception_counts(x)
    assert int(c["zero"]) == 1
    assert int(c["infinity"]) == 2
    assert int(c["nan"]) == 1
    assert int(c["denormal"]) == 1
    s = classify(x)
    assert bool(s.denormal[4]) and not bool(s.denormal[5])


def test_all_finite_tree():
    good = {"a": jnp.ones((3,)), "b": {"c": jnp.zeros((2,))}}
    bad = {"a": jnp.asarray([1.0, np.nan])}
    assert bool(all_finite(good))
    assert not bool(all_finite(bad))


# ---------------------------------------------------------------- policy
def test_policy_recipes():
    p = get_policy("train_default")
    assert p.mode("moe_router").name == "M23"
    fast = get_policy("train_fast")
    assert fast.mode("ffn").name == "M8"
    auto = get_policy("auto")
    assert auto.mode("ffn") == PrecisionMode.AUTO
    assert isinstance(p, PrecisionPolicy)


def test_grad_through_modes():
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    for mode in (PrecisionMode.M8, PrecisionMode.M16, PrecisionMode.M23):
        g = jax.grad(lambda a, b: jnp.sum(mp_matmul(a, b, mode) ** 2))(a, b)
        g_ref = jax.grad(lambda a, b: jnp.sum((a @ b) ** 2))(a, b)
        rel = float(jnp.linalg.norm(g - g_ref) / jnp.linalg.norm(g_ref))
        assert rel < 4 * float(MODE_TABLE[mode].rel_err_bound), (mode, rel)


def test_bwd_mode_override():
    """Backward can run at higher precision than forward (production recipe)."""
    rng = np.random.default_rng(10)
    a = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    g_hi = jax.grad(lambda a, b: jnp.sum(
        mp_matmul(a, b, PrecisionMode.M8, bwd_mode=PrecisionMode.M23) ** 2))(a, b)
    g_ref = jax.grad(lambda a, b: jnp.sum((a @ b) ** 2))(a, b)
    # fwd error feeds g, but the matmuls of the bwd itself are near-fp32
    rel = float(jnp.linalg.norm(g_hi - g_ref) / jnp.linalg.norm(g_ref))
    assert rel < 2.0 ** -5
