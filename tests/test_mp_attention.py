"""Fused multi-precision flash attention (kernels/mp_attention.py and its
routing): chunking-invariance property tests (chunk-scan AND fused kernel vs
the unchunked oracle, builtin modes + a registered custom format, ref +
pallas_interpret, ragged + divisible lengths, causal + bidirectional), the
attn_qk/attn_pv policy op classes, the decode-path policy fix, the bounded
paged gather, the paged kernel vs its fallback, the mp_attention VJP, and
autotune-table coexistence of attention keys with v1/v2 matmul keys."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from proptest_compat import given, settings, st

from repro.core import dispatch
from repro.core import formats as formats_lib
from repro.core.mpmatmul import mp_attention, mp_matmul
from repro.core.policy import PrecisionPolicy
from repro.kernels import autotune, ref
from repro.kernels import mp_attention as attn_kern
from repro.models import attention as attn_models

CUSTOM = "M20ATT"


@pytest.fixture(scope="module", autouse=True)
def _custom_format():
    fmt = formats_lib.register_format(CUSTOM, mantissa_bits=20, n_limbs=3,
                                      max_order=1)
    yield fmt
    formats_lib.unregister_format(CUSTOM)


def _qkv(seed, B=2, S=32, T=None, H=2, Dh=16):
    rng = np.random.default_rng(seed)
    T = S if T is None else T
    q = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
    return q, k, v


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _bound(mode_qk, mode_pv):
    return max(formats_lib.resolve(mode_qk).rel_err_bound,
               formats_lib.resolve(mode_pv).rel_err_bound)


# =========================================================================
# chunking invariance: chunk-scan and fused kernel vs the unchunked oracle
# (module-level: the hypothesis fallback wraps properties as zero-arg tests)
# =========================================================================
@settings(max_examples=24, deadline=None)
@given(
    mode=st.sampled_from(["M8", "M16", "M23", CUSTOM]),
    s=st.sampled_from([17, 32, 33, 64]),
    causal=st.booleans(),
    backend=st.sampled_from(["ref", "pallas_interpret"]),
    seed=st.integers(0, 2**16),
)
def test_fused_matches_unchunked_oracle(mode, s, causal, backend, seed):
    """The fused path (small blocks, either backend) agrees with the
    unchunked oracle at the same formats within the registry bound (x4
    tensor-norm dispersion allowance, the repo-wide convention)."""
    q, k, v = _qkv(seed, S=s)
    oracle = ref.mp_attention_ref(q, k, v, mode, "M23", causal=causal)
    fused = dispatch.dispatch_attention(
        q, k, v, mode, "M23", causal=causal, backend=backend,
        block_q=16, block_kv=16 if backend == "ref" else None)
    assert _rel(fused, oracle) < 4.0 * _bound(mode, "M23")


@settings(max_examples=12, deadline=None)
@given(
    mode=st.sampled_from(["M16", "M23", CUSTOM]),
    s=st.sampled_from([17, 33, 32]),
    causal=st.booleans(),
    seed=st.integers(0, 2**16),
)
def test_chunk_scan_matches_unchunked_oracle(mode, s, causal, seed):
    """The legacy chunk-scan (per-chunk mp_matmul launches) agrees with the
    same oracle — so fused vs chunk-scan stay interchangeable."""
    q, k, v = _qkv(seed, S=s)
    pol = PrecisionPolicy({"attn_qk": mode, "attn_pv": mode})
    chunked = attn_models.chunked_attention(q, k, v, pol, causal=causal,
                                            q_chunk=16, kv_chunk=16)
    oracle = ref.mp_attention_ref(q, k, v, mode, mode, causal=causal)
    assert _rel(chunked, oracle) < 4.0 * _bound(mode, mode)


class TestChunkingInvariance:
    def test_kernel_matches_ref_same_blocking(self):
        """With identical (block_q, block_kv) the kernel and the blocked jnp
        oracle share the exact online-softmax core — reassociation-level
        agreement only (the kernel zero-pads the head dim to lane width)."""
        q, k, v = _qkv(7, S=64)
        for mode_qk, mode_pv in (("M8", "M8"), ("M16", "M8"),
                                 (CUSTOM, "M23")):
            a = ref.mp_attention_ref(q, k, v, mode_qk, mode_pv,
                                     causal=True, block_q=16, block_kv=128)
            b = attn_kern.mp_attention_pallas(
                q, k, v, mode_qk, mode_pv, causal=True, interpret=True,
                block_q=16, block_kv=128)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

    def test_independent_qk_pv_formats(self):
        """attn_qk and attn_pv really resolve independently: degrading only
        the PV side moves the result, and matches a per-side oracle."""
        q, k, v = _qkv(3, S=32)
        hi = ref.mp_attention_ref(q, k, v, "M23", "M23")
        lo_pv = ref.mp_attention_ref(q, k, v, "M23", "M8")
        assert _rel(lo_pv, hi) > 1e-5  # PV quantization is visible
        assert _rel(lo_pv, hi) < 4.0 * _bound("M8", "M8")

    def test_q_offset_matches_suffix_of_full(self):
        """A q block at offset behaves like the suffix rows of the full
        causal computation (the prefill-at-cache-offset contract)."""
        q, k, v = _qkv(11, S=32)
        full = ref.mp_attention_ref(q, k, v, "M23", causal=True)
        tail = ref.mp_attention_ref(q[:, 24:], k, v, "M23", causal=True,
                                    q_offset=24)
        np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, 24:]),
                                   rtol=1e-6, atol=1e-6)


# =========================================================================
# mp_attention public op: VJP decomposition
# =========================================================================
class TestMpAttentionVJP:
    def test_grads_close_to_chunk_scan_autodiff(self):
        q, k, v = _qkv(5, S=32, H=2, Dh=16)
        pol = PrecisionPolicy.full_fp32()

        def fused(q, k, v):
            return jnp.sum(mp_attention(q, k, v, "M23", "M23") ** 2)

        def chunk(q, k, v):
            return jnp.sum(attn_models.chunked_attention(
                q, k, v, pol, q_chunk=16, kv_chunk=16) ** 2)

        gf = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
        gc = jax.grad(chunk, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gc):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)

    def test_backward_formats_are_independent(self):
        """dgrad/wgrad run at their own formats: degrading only wgrad_qk
        moves dK but leaves dV untouched (it flows through wgrad_pv)."""
        q, k, v = _qkv(6, S=16)

        def loss(k_, v_, **bw):
            return jnp.sum(mp_attention(q, k_, v_, "M23", "M23", **bw))

        dk_hi, dv_hi = jax.grad(loss, argnums=(0, 1))(k, v)
        dk_lo, dv_lo = jax.grad(
            lambda k_, v_: loss(k_, v_, wgrad_qk_mode="M8"),
            argnums=(0, 1))(k, v)
        assert float(jnp.max(jnp.abs(dk_hi - dk_lo))) > 0
        np.testing.assert_array_equal(np.asarray(dv_hi), np.asarray(dv_lo))

    def test_auto_format_raises(self):
        q, k, v = _qkv(0, S=8)
        with pytest.raises(ValueError, match="AUTO"):
            mp_attention(q, k, v, "AUTO")

    def test_auto_policy_falls_back_to_chunk_scan(self):
        """models routing: an AUTO attn policy takes the chunk-scan path
        (bit-identical to calling it directly)."""
        q, k, v = _qkv(2, S=16)
        pol = PrecisionPolicy.auto()
        a = attn_models._self_attention(q, k, v, pol, causal=True,
                                        q_chunk=16, kv_chunk=16)
        b = attn_models.chunked_attention(q, k, v, pol, causal=True,
                                          q_chunk=16, kv_chunk=16)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# =========================================================================
# policy op classes
# =========================================================================
class TestAttnOpClasses:
    def test_aliases_preserve_legacy_policies(self):
        pol = PrecisionPolicy.serve_default()  # attn_logits=M16, attn_out=M8
        assert pol.mode("attn_qk").name == "M16"
        assert pol.mode("attn_pv").name == "M8"

    def test_alias_beats_generic_glob(self):
        pol = PrecisionPolicy({"attn_logits": "M23", "*": "M8"})
        assert pol.mode("attn_qk").name == "M23"

    def test_exact_new_class_rule_wins(self):
        pol = PrecisionPolicy({"attn_qk": "M8", "attn_logits": "M23"})
        assert pol.mode("attn_qk").name == "M8"
        assert pol.mode("attn_logits").name == "M23"

    def test_new_class_glob_resolves(self):
        pol = PrecisionPolicy({"attn_q*": CUSTOM})
        assert pol.mode("attn_qk").name == CUSTOM
        assert pol.mode("attn_pv").name == "M16"  # default tier

    def test_backward_overrides_flow_through_alias(self):
        pol = PrecisionPolicy({"attn_logits": {"fwd": "M16", "wgrad": "M23"}})
        assert pol.wgrad("attn_qk").name == "M23"

    def test_json_round_trip_with_new_classes(self):
        pol = PrecisionPolicy({"attn_qk": CUSTOM, "attn_pv": "M8"})
        back = PrecisionPolicy.from_json(pol.to_json())
        assert back.mode("attn_qk").name == CUSTOM
        assert back.mode("attn_pv").name == "M8"


# =========================================================================
# decode paths: policy obedience + paged routing
# =========================================================================
class TestDecodePaths:
    def test_decode_einsums_obey_policy(self):
        """The masked-decode path quantizes at the resolved formats: M8
        differs from M23, and M8 equals the explicit mp_matmul composition."""
        q, k, v = _qkv(8, B=2, S=1, T=24, H=2, Dh=16)
        lengths = jnp.asarray([13, 7], jnp.int32)
        lo = dispatch.masked_decode_attention(q, k, v, lengths, "M8", "M8")
        hi = dispatch.masked_decode_attention(q, k, v, lengths, "M23", "M23")
        assert float(jnp.max(jnp.abs(lo - hi))) > 1e-5

        scale = 1.0 / np.sqrt(16)
        qh = q.transpose(0, 2, 1, 3) * scale
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        logits = mp_matmul(qh, jnp.swapaxes(kh, -1, -2), "M8", backend="ref")
        mask = jnp.arange(24)[None, None, None, :] < lengths.reshape(-1, 1, 1, 1)
        logits = jnp.where(mask, logits, ref.ATTN_NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        manual = mp_matmul(p, vh, "M8", backend="ref").transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(lo), np.asarray(manual),
                                   rtol=1e-6, atol=1e-6)

    def test_decode_auto_policy_routes(self):
        q, k, v = _qkv(9, B=1, S=1, T=16, H=2, Dh=16)
        out = dispatch.masked_decode_attention(
            q, k, v, jnp.asarray([9], jnp.int32), "AUTO", "AUTO")
        assert out.shape == q.shape and bool(jnp.all(jnp.isfinite(out)))

    def test_paged_kernel_matches_gather_fallback(self):
        rng = np.random.default_rng(4)
        B, H, Dh, hk, n_blocks, bs, W = 4, 4, 16, 2, 12, 8, 4
        q = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((n_blocks, bs, hk, Dh)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((n_blocks, bs, hk, Dh)),
                         jnp.float32)
        table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0],
                             [0, 0, 0, 0], [6, 7, 8, 9]], jnp.int32)
        lengths = jnp.asarray([19, 9, 0, 30], jnp.int32)
        for mode_qk, mode_pv in (("M16", "M8"), ("M23", "M23"),
                                 (CUSTOM, CUSTOM)):
            kern = dispatch.dispatch_paged_attention(
                q, kp, vp, table, lengths, mode_qk, mode_pv,
                backend="pallas_interpret")
            fall = dispatch.dispatch_paged_attention(
                q, kp, vp, table, lengths, mode_qk, mode_pv, backend="ref")
            active = np.asarray(lengths) > 0
            assert _rel(np.asarray(kern)[active], np.asarray(fall)[active]) \
                < 4.0 * _bound(mode_qk, mode_pv) + 1e-5
            # inactive slots flush exact zeros from the kernel
            np.testing.assert_array_equal(np.asarray(kern)[~active], 0.0)

    def test_paged_auto_takes_einsum_fallback(self):
        """AUTO formats analyze operands — the paged route must not hit the
        static-format kernel even on a Pallas backend."""
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((1, 1, 2, 16)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((4, 8, 2, 16)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((4, 8, 2, 16)), jnp.float32)
        table = jnp.asarray([[1, 2]], jnp.int32)
        lengths = jnp.asarray([11], jnp.int32)
        out = dispatch.dispatch_paged_attention(
            q, kp, vp, table, lengths, "AUTO", "AUTO",
            backend="pallas_interpret")
        assert out.shape == q.shape and bool(jnp.all(jnp.isfinite(out)))


# =========================================================================
# bounded paged gather (scheduler-side table slicing)
# =========================================================================
class TestBoundedGather:
    def test_decode_tables_sliced_to_used_blocks(self):
        from repro.configs.registry import get_config
        from repro.models import transformer as T
        from repro.serve.engine import ServeEngine
        from repro.serve.scheduler import ContinuousScheduler, ScheduledRequest

        cfg = get_config("paper-mpfp-100m", smoke=True)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, max_batch=2, max_seq=64,
                          policy=PrecisionPolicy.serve_default())
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=4)
        assert sched.pool.max_blocks_per_seq == 16

        widths = []
        orig = eng.paged_steps_for

        def spy(policy):
            prefill_fn, decode_fn = orig(policy)

            def decode_spy(params, pk, pv, table, lengths, tokens):
                widths.append(table.shape[1])
                return decode_fn(params, pk, pv, table, lengths, tokens)

            return prefill_fn, decode_spy

        eng.paged_steps_for = spy
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
        done = sched.run([ScheduledRequest(rid=0, prompt=prompt, max_new=3)])
        assert len(done[0].out) == 3
        # 5 prompt + 3 new = 8 tokens -> 2 blocks of 4; pow2 bucket = 2,
        # NOT the trash-padded max_blocks_per_seq = 16
        assert widths and set(widths) == {2}

    def test_table_width_pow2_bucketing(self):
        class _R:
            def __init__(self, n):
                self.blocks = list(range(n))

        from repro.serve.kv_cache import PagedKVPool
        from repro.serve.primitives import table_width

        pool = PagedKVPool(1, 32, 4, (2, 8), (2, 8), max_blocks_per_seq=16)
        assert table_width(pool, [_R(1)]) == 1
        assert table_width(pool, [_R(3), _R(5)]) == 8
        assert table_width(pool, [_R(16)]) == 16  # clamped to capacity


# =========================================================================
# autotune: attention keys coexist with v1/v2 matmul keys
# =========================================================================
class TestAttnAutotune:
    def test_keys_coexist_and_round_trip(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_DIR", str(tmp_path))
        autotune.clear_memory_cache()
        v1_key = autotune.table_key(128, 256, 512, "M16", jnp.float32)
        # single-matmul keys stay byte-identical to v1
        assert v1_key == "M16|128x256x512|float32"
        fused_key = autotune.table_key(128, 256, 512, "M16", jnp.float32,
                                       n_out=2, epilogue="swiglu")
        assert fused_key == "M16|128x256x512|float32|out2|swiglu"
        attn_key = autotune.attention_table_key(8, 512, 512, 64, "M16", "M8",
                                                causal=True)
        assert attn_key.startswith("attn|M16/M8|")
        table = {v1_key: [64, 128, 128], fused_key: [64, 256, 128],
                 attn_key: [64, 128]}
        autotune.save_table(table)
        autotune.clear_memory_cache()
        assert autotune.lookup(128, 256, 512, "M16") == (64, 128, 128)
        assert autotune.lookup(128, 256, 512, "M16", n_out=2,
                               epilogue="swiglu") == (64, 256, 128)
        assert autotune.lookup_attention(8, 512, 512, 64, "M16", "M8",
                                         causal=True) == (64, 128)
        # same shape, different variant bits -> distinct cells
        assert autotune.lookup_attention(8, 512, 512, 64, "M16", "M8",
                                         causal=False) is None
        assert autotune.lookup_attention(8, 512, 512, 64, "M16", "M8",
                                         causal=True, paged=True) is None

    def test_old_cache_file_loads_unchanged(self, tmp_path, monkeypatch):
        """A v1/v2 table (matmul keys only) loads as-is; adding an attention
        key preserves every existing entry byte-for-byte."""
        import json

        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_DIR", str(tmp_path))
        autotune.clear_memory_cache()
        old = {"M16|128x256x512|float32": [64, 128, 128],
               "M52|8x128x128|float32": [8, 128, 128]}
        path = autotune._cache_path()
        import os
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(old, f)
        table = autotune.load_table()
        assert {k: list(v) for k, v in table.items()} == old
        table[autotune.attention_table_key(4, 64, 64, 32, "M8", "M8",
                                           causal=True)] = [32, 128]
        autotune.save_table(table)
        autotune.clear_memory_cache()
        loaded = autotune.load_table()
        for k, want in old.items():
            assert loaded[k] == want

    def test_autotune_attention_sweep_persists(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_DIR", str(tmp_path))
        autotune.clear_memory_cache()
        got = autotune.autotune_attention(
            2, 32, 32, 16, "M8", "M8", causal=True, interpret=True, iters=1,
            candidates=[(16, 128), (32, 128)])
        assert got in ((16, 128), (32, 128))
        autotune.clear_memory_cache()
        assert autotune.lookup_attention(2, 32, 32, 16, "M8", "M8",
                                         causal=True) == got

    def test_vmem_model_sanity(self):
        base = attn_kern.attn_vmem_bytes("M16", "M8", 128, 128, 128)
        assert attn_kern.attn_vmem_bytes("M52", "M8", 128, 128, 128) > base
        assert attn_kern.attn_vmem_bytes("M16", "M8", 256, 128, 128) > base
        assert attn_kern.attn_vmem_bytes("M16", "M8", 128, 256, 128) > base
        cands = autotune.attention_candidate_blocks(512, 512, 128,
                                                    "M23", "M23")
        assert cands
        for bq, bkv in cands:
            assert attn_kern.attn_vmem_bytes(
                "M23", "M23", bq, bkv, 128) <= autotune.VMEM_BUDGET_BYTES


# =========================================================================
# public surface
# =========================================================================
class TestPublicAPI:
    def test_mp_facade_exports_attention(self):
        import repro.mp as mp

        assert mp.mp_attention is mp_attention
