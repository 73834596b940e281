"""Continuous-batching scheduler tests: token parity vs the static path,
join/evict bit-stability, paged free-list invariants, and mixed per-request
precision modes (ref + pallas_interpret backends)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core import formats as formats_lib
from repro.core.context import resolve_request_policy
from repro.core.policy import PrecisionPolicy
from repro.models import transformer as T
from repro.models.attention import chunked_attention
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import (
    TRASH_BLOCK, BlockPoolExhausted, PagedKVPool)
from repro.serve.scheduler import ContinuousScheduler, ScheduledRequest

CFG = get_config("paper-mpfp-100m", smoke=True)
KV = (CFG.n_kv_heads, CFG.resolved_head_dim)   # a pool entry: K and V alike


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, backend=None, policy=None, max_batch=4):
    return ServeEngine(CFG, params, max_batch=max_batch, max_seq=64,
                       policy=policy or PrecisionPolicy.serve_default(),
                       matmul_backend=backend)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, size=s).astype(np.int32)
            for s in sizes]


# =========================================================================
# paged pool free-list invariants
# =========================================================================
class TestPagedPool:
    def _pool(self, n_blocks=8):
        return PagedKVPool(2, n_blocks, 4, KV, KV,
                            max_blocks_per_seq=4)

    def test_never_double_allocates(self):
        pool = self._pool()
        seen = set()
        for _ in range(3):
            got = pool.alloc(2)
            assert not (set(got) & seen)
            assert TRASH_BLOCK not in got
            seen |= set(got)
        assert pool.n_live == 6 and pool.n_free == 1

    def test_exhaustion_raises_and_eviction_reclaims(self):
        pool = self._pool()
        a = pool.alloc(4)
        b = pool.alloc(3)
        with pytest.raises(BlockPoolExhausted):
            pool.alloc(1)
        pool.free(b)  # eviction reclaim
        c = pool.alloc(3)
        assert set(c) == set(b)  # LIFO reuse of the freed blocks
        assert pool.n_free == 0 and pool.n_live == 7
        pool.free(a + c)
        assert pool.n_free == 7 and pool.n_live == 0

    def test_double_free_and_trash_free_raise(self):
        pool = self._pool()
        got = pool.alloc(1)
        pool.free(got)
        with pytest.raises(ValueError):
            pool.free(got)
        with pytest.raises(ValueError):
            pool.free([TRASH_BLOCK])

    def test_over_reservation_raises(self):
        pool = self._pool()
        with pytest.raises(BlockPoolExhausted):
            pool.alloc(5)  # > max_blocks_per_seq

    def test_table_row_trash_padding(self):
        pool = self._pool()
        blocks = pool.alloc(2)
        row = pool.table_row(blocks)
        assert list(row[:2]) == blocks
        assert all(row[2:] == TRASH_BLOCK)


# =========================================================================
# token parity vs the static path
# =========================================================================
class TestParity:
    def test_equal_length_batch_matches_static(self, params):
        """Identical arrival batch, equal prompt lengths: scheduled tokens ==
        the static generate() batch token-for-token (same padding-free
        semantics, same decode compute)."""
        eng = _engine(params)
        prompts = _prompts(0, [6, 6, 6, 6])
        static = eng.generate(prompts, max_new=6)
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        done = sched.run([ScheduledRequest(rid=i, prompt=p, max_new=6)
                          for i, p in enumerate(prompts)])
        got = {r.rid: r.out for r in done}
        for i in range(4):
            assert got[i] == static[i], i

    def test_mixed_length_batch_matches_solo_runs(self, params):
        """Mixed lengths: the static batch left-pads (pad tokens join the
        causal prefix), so the reference is per-request solo generate() —
        the padding-free semantics the scheduler preserves for every
        request simultaneously."""
        eng = _engine(params)
        prompts = _prompts(1, [5, 3, 9, 2])
        solo = [eng.generate([p], max_new=5)[0] for p in prompts]
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        done = sched.run([ScheduledRequest(rid=i, prompt=p, max_new=5)
                          for i, p in enumerate(prompts)])
        got = {r.rid: r.out for r in done}
        for i in range(4):
            assert got[i] == solo[i], i

    def test_join_evict_mid_stream_bit_identical(self, params):
        """A short request joining mid-stream and evicting before the others
        finish must not perturb the survivors' token streams."""
        eng = _engine(params)
        long_prompts = _prompts(2, [4, 7])
        short = _prompts(3, [3])[0]

        alone = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        base = alone.run([ScheduledRequest(rid=i, prompt=p, max_new=8)
                          for i, p in enumerate(long_prompts)])
        base_out = {r.rid: r.out for r in base}

        mixed = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        reqs = [ScheduledRequest(rid=i, prompt=p, max_new=8)
                for i, p in enumerate(long_prompts)]
        # joins at step 2, finishes (and evicts) at most by step 5
        reqs.append(ScheduledRequest(rid=99, prompt=short, max_new=2,
                                     arrival=2))
        done = mixed.run(reqs)
        got = {r.rid: r.out for r in done}
        assert len(got[99]) == 2
        for i in range(2):
            assert got[i] == base_out[i], f"survivor {i} perturbed"

    def test_slot_reuse_after_eviction(self, params):
        """More requests than slots: later arrivals must wait for eviction,
        reuse freed blocks, and still match their solo runs."""
        eng = _engine(params, max_batch=2)
        prompts = _prompts(4, [4, 6, 3, 5, 7])
        solo = [eng.generate([p], max_new=4)[0] for p in prompts]
        # pool sized so at most 2 requests fit: forces block recycling
        sched = ContinuousScheduler(eng, n_blocks=5, block_size=8)
        done = sched.run([ScheduledRequest(rid=i, prompt=p, max_new=4)
                          for i, p in enumerate(prompts)])
        got = {r.rid: r.out for r in done}
        for i in range(5):
            assert got[i] == solo[i], i
        assert sched.pool.n_live == 0
        assert sched.pool.n_free == sched.pool.n_blocks - 1

    def test_prefill_pad_past_table_capacity_is_harmless(self, params):
        """Prompt whose power-of-two prefill bucket exceeds the block-table
        capacity: the padded tail's writes must redirect to trash, NOT clamp
        into the row's last real block (which holds live prompt K/V).

        prompt=10, max_new=2, block_size=4, max_blocks_per_seq=3: capacity
        12 < bucket 16, and positions 12..15 share a table column with live
        positions 8..9 if clamped."""
        eng = _engine(params)
        p = _prompts(11, [10])[0]
        solo = eng.generate([p], max_new=2)[0]
        sched = ContinuousScheduler(eng, n_blocks=16, block_size=4,
                                    max_blocks_per_seq=3)
        done = sched.run([ScheduledRequest(rid=0, prompt=p, max_new=2)])
        assert done[0].out == solo

    def test_ragged_prompt_lengths_admitted(self, params):
        """Prompt lengths that are not multiples of the attention chunk
        (smoke q_chunk=16) — exercises the chunked_attention pad-and-mask
        path end to end (the seed asserted on these)."""
        eng = _engine(params)
        prompts = _prompts(5, [17, 33])
        solo = [eng.generate([p], max_new=3)[0] for p in prompts]
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        done = sched.run([ScheduledRequest(rid=i, prompt=p, max_new=3)
                          for i, p in enumerate(prompts)])
        got = {r.rid: r.out for r in done}
        for i in range(2):
            assert got[i] == solo[i], i


# =========================================================================
# per-request precision modes
# =========================================================================
class TestMixedModes:
    @pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
    def test_mixed_mode_batch_matches_per_mode_solo(self, params, backend):
        """M8 + M23 + a registered custom format decoding concurrently from
        one engine: each request's tokens equal its per-mode solo run."""
        fmt = formats_lib.register_format(
            "M12QOS", mantissa_bits=12, n_limbs=2, max_order=1)
        modes = ["M8", "M23", fmt.name]
        prompts = _prompts(6, [5, 4, 6])
        solo = []
        for p, m in zip(prompts, modes):
            e = _engine(params, backend=backend,
                        policy=PrecisionPolicy.serve_default().overlay(m))
            solo.append(e.generate([p], max_new=4)[0])

        eng = _engine(params, backend=backend)
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        done = sched.run([
            ScheduledRequest(rid=i, prompt=p, max_new=4, mode=m)
            for i, (p, m) in enumerate(zip(prompts, modes))])
        got = {r.rid: r.out for r in done}
        for i in range(3):
            assert got[i] == solo[i], (i, modes[i])

    def test_full_policy_override_wins_over_mode(self, params):
        pol = PrecisionPolicy.full_fp32()
        resolved = resolve_request_policy(mode="M8", policy=pol.to_json())
        assert resolved == pol

    def test_mode_overlay_covers_whole_network(self):
        base = PrecisionPolicy.serve_default()
        ov = base.overlay("M23")
        for cls in ("qkv", "ffn", "attn_logits", "lm_head", "anything"):
            assert ov.mode(cls).name == "M23"

    def test_auto_mode_request_schedules(self, params):
        """AUTO per-request policy: pre-limbing is skipped, scheduling still
        works and matches the solo AUTO run."""
        eng = _engine(params)
        auto = PrecisionPolicy.auto()
        p = _prompts(7, [4])[0]
        e_solo = _engine(params, policy=auto)
        solo = e_solo.generate([p], max_new=3)[0]
        sched = ContinuousScheduler(eng, n_blocks=16, block_size=8)
        done = sched.run([ScheduledRequest(rid=0, prompt=p, max_new=3,
                                           policy=auto)])
        assert done[0].out == solo


# =========================================================================
# scheduler robustness
# =========================================================================
class TestSchedulerInvariants:
    def test_unsatisfiable_request_raises(self, params):
        eng = _engine(params)
        sched = ContinuousScheduler(eng, n_blocks=3, block_size=4,
                                    max_blocks_per_seq=2)
        req = ScheduledRequest(rid=0, prompt=_prompts(8, [20])[0], max_new=8)
        with pytest.raises(BlockPoolExhausted):
            sched.run([req])

    def test_eos_token_evicts_early(self, params):
        """EOS cuts generation short; blocks return to the pool."""
        eng = _engine(params)
        p = _prompts(9, [5])[0]
        ref_out = eng.generate([p], max_new=8)[0]
        eos = ref_out[2]  # force an early stop at the 3rd token
        sched = ContinuousScheduler(eng, n_blocks=16, block_size=8)
        done = sched.run([ScheduledRequest(rid=0, prompt=p, max_new=8,
                                           eos_token=eos)])
        assert done[0].out == ref_out[:3]
        assert sched.pool.n_live == 0

    def test_non_dense_family_rejected(self, params):
        ssm_cfg = get_config("mamba2-130m", smoke=True)
        with pytest.raises(NotImplementedError):
            ContinuousScheduler(
                ServeEngine(ssm_cfg, {}, max_batch=2, max_seq=32),
                n_blocks=4, block_size=4)

    def test_stats_account_for_everything(self, params):
        eng = _engine(params)
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        reqs = [ScheduledRequest(rid=i, prompt=p, max_new=3, arrival=i)
                for i, p in enumerate(_prompts(10, [3, 4, 5]))]
        done = sched.run(reqs)
        s = sched.stats()
        assert s["completed"] == 3
        assert s["useful_tokens"] == sum(len(r.out) for r in done) == 9
        assert s["blocks_live"] == 0
        done_steps = [r.done_step for r in done]
        assert done_steps == sorted(done_steps)  # monotone completions

    def test_flood_past_pool_capacity_is_graceful(self, params):
        """Admission under transient exhaustion queues (FIFO) instead of
        raising — the pool can satisfy each request alone, just not all at
        once — and every reserved block comes back (no leak).  Regression:
        _admit used to raise BlockPoolExhausted the moment the free list
        could not cover the queue head."""
        eng = _engine(params)
        # 4 allocatable blocks = 2 concurrent requests; flood with 9 at once
        sched = ContinuousScheduler(eng, n_blocks=5, block_size=8)
        reqs = [ScheduledRequest(rid=i, prompt=p, max_new=4)
                for i, p in enumerate(_prompts(11, [3, 5, 7] * 3))]
        done = sched.run(reqs)
        assert len(done) == 9
        assert all(len(r.out) == r.max_new for r in done)
        assert sched.pool.n_live == 0, "block leak after flood"
        assert sched.pool.n_free == sched.pool.n_blocks - 1
        assert sched.n_active == 0 and sched.n_queued == 0

    def test_stats_latency_percentiles(self, params):
        """stats() surfaces TTFT/TPOT/ITL p50+p95 (ms) and queue-wait
        percentiles (virtual steps) pooled over completed requests."""
        eng = _engine(params)
        sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
        sched.run([ScheduledRequest(rid=i, prompt=p, max_new=4, arrival=i)
                   for i, p in enumerate(_prompts(12, [3, 4, 5]))])
        s = sched.stats()
        for k in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
                  "itl_p50_ms", "itl_p95_ms", "queue_wait_p50_steps",
                  "queue_wait_p95_steps"):
            assert k in s and s[k] >= 0.0
        assert s["ttft_p95_ms"] >= s["ttft_p50_ms"]
        # queue wait is measured in scheduler steps: admitted minus arrival
        assert s["queue_wait_p95_steps"] < sched.steps


# =========================================================================
# the pool through a step: donated, written in place, read by global id
# =========================================================================
# one of each family the paged path serves: dense GQA, and an MoE stack with
# latent attention whose leading dense layer runs the pool's first layer
FAMILIES = {"dense": CFG, "moe": get_config("deepseek-v2-lite-16b",
                                            smoke=True)}


def _spy_paged_steps(eng, seen):
    """Record (pool_k in, pool_v in, pool_k out, pool_v out) of every paged
    step the engine launches."""
    def spy(fn):
        def call(params, pk, pv, *rest):
            out = fn(params, pk, pv, *rest)
            seen.append((pk, pv) + tuple(out[-2:]))
            return out
        return call

    paged, mixed = eng.paged_steps_for, eng.mixed_decode_step_for
    eng.paged_steps_for = lambda policy: tuple(map(spy, paged(policy)))
    eng.mixed_decode_step_for = lambda env: spy(mixed(env))


class TestDonatedPool:
    @pytest.fixture(scope="class")
    def family_params(self):
        return {name: T.init_params(cfg, jax.random.PRNGKey(1))
                for name, cfg in FAMILIES.items()}

    def _serve(self, family, params, modes, *, backend=None, seen=None):
        """A join mid-stream and an eviction over a few ticks; returns the
        tokens and the scheduler (``seen`` records the paged steps)."""
        cfg = FAMILIES[family]
        eng = ServeEngine(cfg, params[family], max_batch=4, max_seq=64,
                          policy=PrecisionPolicy.serve_default(),
                          matmul_backend=backend)
        if seen is not None:
            _spy_paged_steps(eng, seen)
        sched = ContinuousScheduler(eng, n_blocks=24, block_size=4)
        rng = np.random.default_rng(8)
        reqs = [ScheduledRequest(rid=i, prompt=rng.integers(
                    0, cfg.vocab, size=n).astype(np.int32), max_new=m,
                    mode=mode, arrival=a)
                for i, (n, m, a, mode) in enumerate(
                    zip((5, 9, 3), (6, 3, 4), (0, 0, 2), modes))]
        done = sched.run(reqs)
        return {r.rid: r.out for r in done}, sched

    @pytest.mark.parametrize("family,backend", [("dense", "pallas_interpret"),
                                                ("moe", None)])
    def test_ticks_match_slice_and_stack(self, family_params, family,
                                         backend, slice_and_stack):
        """The scheduler's tokens and the whole pool after the last tick,
        bit for bit, against the per-layer slice-and-stack path."""
        modes = [None] * 3
        got, sched = self._serve(family, family_params, modes,
                                 backend=backend)
        with slice_and_stack():
            want, ref = self._serve(family, family_params, modes,
                                    backend=backend)
        assert got == want
        np.testing.assert_array_equal(np.asarray(sched.pool.k),
                                      np.asarray(ref.pool.k))
        np.testing.assert_array_equal(np.asarray(sched.pool.v),
                                      np.asarray(ref.pool.v))

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_step_deletes_the_pool_it_was_handed(self, family_params,
                                                 family):
        """After each paged step the arrays handed in are deleted (donated)
        and the pool holds the step's outputs."""
        cfg = FAMILIES[family]
        eng = ServeEngine(cfg, family_params[family], max_batch=2,
                          max_seq=32, policy=PrecisionPolicy.serve_default())
        seen = []
        _spy_paged_steps(eng, seen)
        sched = ContinuousScheduler(eng, n_blocks=8, block_size=4)
        sched.submit(ScheduledRequest(
            rid=0, prompt=np.arange(5, dtype=np.int32), max_new=3))
        for n in (2, 3):     # prefill + decode, then a decode
            sched.step()
            assert len(seen) == n
            pk, pv, k, v = seen[-1]
            assert pk.is_deleted() and pv.is_deleted()
            assert sched.pool.k is k and sched.pool.v is v

    def test_pool_steps_donated_counts_each_launch(self, family_params):
        """``pool_steps_donated`` counts every paged launch once — each
        prefill and each decode launch — and the scheduler's stats()
        report it."""
        seen = []
        _, sched = self._serve("dense", family_params, [None] * 3, seen=seen)
        n = sched.engine.cache_stats()["pool_steps_donated"]
        assert n == len(seen) == sched.prefills + sched.decode_launches
        assert sched.prefills == 3 and sched.decode_launches > 0
        assert sched.stats()["pool_steps_donated"] == n

    def test_mixed_lane_decode_matches_slice_and_stack(self, family_params,
                                                       slice_and_stack):
        """A mixed-format batch (one partitioned-lane launch a tick) takes
        the pool donated and matches the slice-and-stack path bit for
        bit."""
        modes = ["M8", "M23", "M16"]
        seen = []
        got, sched = self._serve("dense", family_params, modes, seen=seen)
        assert sched.stats()["launches_per_tick"] == 1.0
        assert sched.engine._mixed_step_cache
        assert all(pk.is_deleted() and pv.is_deleted()
                   for pk, pv, _, _ in seen)
        assert sched.engine.pool_steps_donated == len(seen)
        with slice_and_stack():
            want, ref = self._serve("dense", family_params, modes)
        assert got == want
        np.testing.assert_array_equal(np.asarray(sched.pool.k),
                                      np.asarray(ref.pool.k))
        np.testing.assert_array_equal(np.asarray(sched.pool.v),
                                      np.asarray(ref.pool.v))


# =========================================================================
# chunked_attention ragged fix (unit level)
# =========================================================================
class TestRaggedChunkedAttention:
    @pytest.mark.parametrize("s", [33, 17, 40, 100])
    def test_ragged_matches_unchunked(self, s):
        """Pad-and-mask chunking must agree with the single-chunk result
        (q_chunk >= S exercises the historical path as the oracle)."""
        rng = np.random.default_rng(s)
        B, H, Dh = 2, 2, 8
        q = jnp.asarray(rng.standard_normal((B, s, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, s, H, Dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, s, H, Dh)), jnp.float32)
        pol = PrecisionPolicy.full_fp32()
        ref_out = chunked_attention(q, k, v, pol, q_chunk=1024, kv_chunk=1024)
        ragged = chunked_attention(q, k, v, pol, q_chunk=16, kv_chunk=16)
        np.testing.assert_allclose(np.asarray(ragged), np.asarray(ref_out),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_divisible_shapes_bit_stable(self, causal):
        """Historically-accepted divisible shapes keep their exact chunking:
        results are bit-identical to the pre-fix chunk layout (no padding,
        no extra masking)."""
        rng = np.random.default_rng(0)
        B, S, H, Dh = 1, 32, 2, 8
        q = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
        pol = PrecisionPolicy.full_fp32()
        a = chunked_attention(q, k, v, pol, causal=causal,
                              q_chunk=16, kv_chunk=16)
        b = chunked_attention(q, k, v, pol, causal=causal,
                              q_chunk=16, kv_chunk=16)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
