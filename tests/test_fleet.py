"""Fleet serving tests: prefill->decode KV handoff bit-parity vs the
single-engine scheduler, cross-pool block transfer, router placement
policies, graceful degradation (backoff / downgrade / caps), and the
sequence-parallel decode-attention path the sharded backend routes to."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.dispatch import dispatch_attention, masked_decode_attention
from repro.core.policy import PrecisionPolicy
from repro.dist.attention import sp_decode_attention
from repro.models import transformer as T
from repro.serve.engine import ServeEngine
from repro.serve.fleet import (
    DOWNGRADE_CHAIN,
    DecodeEngine,
    FleetRouter,
    KVHandoff,
    PrefillEngine,
    deliver,
    make_fleet,
)
from repro.serve.kv_cache import BlockPoolExhausted, PagedKVPool
from repro.serve.scheduler import ContinuousScheduler, ScheduledRequest

CFG = get_config("paper-mpfp-100m", smoke=True)
KV = (CFG.n_kv_heads, CFG.resolved_head_dim)   # a pool entry: K and V alike


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, backend=None, max_batch=4):
    return ServeEngine(CFG, params, max_batch=max_batch, max_seq=64,
                       policy=PrecisionPolicy.serve_default(),
                       matmul_backend=backend)


def _reqs(seed=0, n=6, max_new=6, modes=("M8", "M16", "M23")):
    rng = np.random.default_rng(seed)
    return [ScheduledRequest(
        rid=i,
        prompt=rng.integers(0, CFG.vocab,
                            size=int(rng.integers(2, 9))).astype(np.int32),
        max_new=int(rng.integers(2, max_new + 1)),
        mode=modes[i % len(modes)] if modes else None,
        arrival=i // 2)
        for i in range(n)]


def _outs(done):
    return {r.rid: r.out for r in done}


# =========================================================================
# KV handoff: fleet tokens must be bit-identical to the single-engine
# scheduler — decode inherits prefill's paged blocks, never recomputes
# =========================================================================
class TestHandoffParity:
    @pytest.mark.parametrize("policy", ["round_robin", "least_kv",
                                        "mode_affinity"])
    def test_fleet_matches_scheduler_mixed_modes(self, params, policy):
        eng = _engine(params)
        sched = ContinuousScheduler(eng, n_blocks=33, block_size=8)
        want = _outs(sched.run(_reqs()))

        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        router = FleetRouter(cells, policy=policy)
        got = _outs(router.run(_reqs()))
        assert got == want  # bit-identical token streams

    def test_fleet_matches_scheduler_pallas_interpret(self, params):
        eng = _engine(params, backend="pallas_interpret")
        sched = ContinuousScheduler(eng, n_blocks=33, block_size=8)
        want = _outs(sched.run(_reqs(n=3, max_new=4)))

        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        got = _outs(FleetRouter(cells).run(_reqs(n=3, max_new=4)))
        assert got == want

    def test_interleaved_cell_matches_scheduler(self, params):
        """disaggregate=False reproduces the single-engine discipline."""
        eng = _engine(params)
        sched = ContinuousScheduler(eng, n_blocks=33, block_size=8)
        want = _outs(sched.run(_reqs(seed=3)))
        cells = make_fleet(eng, 1, n_blocks=33, block_size=8,
                           disaggregate=False)
        got = _outs(FleetRouter(cells).run(_reqs(seed=3)))
        assert got == want

    def test_instant_completion_releases_blocks(self, params):
        """max_new=1 finishes inside prefill: no handoff, blocks freed."""
        eng = _engine(params)
        cells = make_fleet(eng, 1, n_blocks=17, block_size=8)
        router = FleetRouter(cells)
        done = router.run([ScheduledRequest(
            rid=0, prompt=np.arange(4, dtype=np.int32), max_new=1)])
        assert len(done) == 1 and len(done[0].out) == 1
        assert cells[0].pool.n_live == 0
        assert router.stats()["pending_handoffs"] == 0


# =========================================================================
# cross-pool block transfer
# =========================================================================
class TestCrossPoolHandoff:
    def _pool(self, n_blocks=8):
        return PagedKVPool(2, n_blocks, 4, KV, KV,
                            max_blocks_per_seq=4)

    def test_transfer_blocks_bit_identical(self):
        src, dst = self._pool(), self._pool()
        sb = src.alloc(3)
        rng = np.random.default_rng(0)
        src.k = src.k.at[:, sb].set(
            jnp.asarray(rng.standard_normal(src.k[:, sb].shape), jnp.float32))
        src.v = src.v.at[:, sb].set(
            jnp.asarray(rng.standard_normal(src.v[:, sb].shape), jnp.float32))
        db = dst.alloc(3)
        src.transfer_blocks(dst, sb, db)
        assert jnp.array_equal(dst.k[:, db], src.k[:, sb])
        assert jnp.array_equal(dst.v[:, db], src.v[:, sb])

    def test_handoff_after_donated_prefill_decodes_in_the_new_pool(
            self, params):
        """A request prefilled into one pool by a donated step hands its
        blocks to a foreign pool and decodes there, donated again: the
        blocks arrive bit for bit, the pool the prefill was handed is
        deleted, and the tokens are the single scheduler's."""
        eng = _engine(params)
        prompt = np.arange(3, 9, dtype=np.int32)
        want = ContinuousScheduler(eng, n_blocks=8, block_size=4).run(
            [ScheduledRequest(rid=0, prompt=prompt, max_new=4)])[0].out
        src, dst = (PagedKVPool(CFG.n_layers, 8, 4, KV, KV,
                                max_blocks_per_seq=4) for _ in range(2))
        dst.alloc(3)    # the request's blocks land at other ids there
        prefill, decode = PrefillEngine(eng, src), DecodeEngine(eng, dst)
        req = ScheduledRequest(rid=0, prompt=prompt, max_new=4)
        assert prefill.try_admit(req)
        handed = src.k
        (handoff,), _ = prefill.step()
        assert handed.is_deleted()
        sb, sk, sv = list(req.blocks), src.k[:, req.blocks], src.v[:, req.blocks]
        assert decode.accept(handoff) and req.blocks != sb
        assert jnp.array_equal(dst.k[:, req.blocks], sk)
        assert jnp.array_equal(dst.v[:, req.blocks], sv)
        while decode.n_active:
            decode.step()
        assert req.out == want

    def test_transfer_rejects_geometry_mismatch(self):
        src = self._pool()
        odd = PagedKVPool(2, 8, 2, KV, KV,
                           max_blocks_per_seq=4)
        with pytest.raises(ValueError):
            src.transfer_blocks(odd, [1], [1])

    def test_deliver_foreign_pool_moves_blocks(self):
        src, dst = self._pool(), self._pool()
        req = ScheduledRequest(rid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new=4)
        req.blocks = src.alloc(2)
        src.k = src.k.at[:, req.blocks].set(7.0)
        h = KVHandoff(req=req, src_pool=src, src_cell=0)
        assert deliver(h, dst)
        assert src.n_live == 0 and dst.n_live == 2  # free list moved too
        assert h.src_pool is dst
        assert bool(jnp.all(dst.k[:, req.blocks] == 7.0))

    def test_deliver_same_pool_is_zero_copy(self):
        pool = self._pool()
        req = ScheduledRequest(rid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new=4)
        req.blocks = pool.alloc(2)
        before = list(req.blocks)
        assert deliver(KVHandoff(req=req, src_pool=pool), pool)
        assert req.blocks == before and pool.n_live == 2

    def test_deliver_fails_gracefully_when_dst_full(self):
        src, dst = self._pool(), self._pool()
        req = ScheduledRequest(rid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new=4)
        req.blocks = src.alloc(2)
        dst.alloc(4)
        dst.alloc(3)  # exhaust dst (7 allocatable + trash)
        assert not deliver(KVHandoff(req=req, src_pool=src), dst)
        assert src.n_live == 2  # handoff untouched, blocks still in src

    def test_deliver_injected_transfer_fail_is_side_effect_free(self):
        """An injected handoff_transfer_fail fires before any allocation:
        the handoff stays valid against its source pool and the next
        attempt (event spent) succeeds — the park-and-retry contract."""
        from repro.serve.faults import FaultEvent, FaultInjector, FaultPlan

        src, dst = self._pool(), self._pool()
        req = ScheduledRequest(rid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new=4)
        req.blocks = src.alloc(2)
        inj = FaultInjector(FaultPlan(events=[
            FaultEvent("handoff_transfer_fail")]))
        h = KVHandoff(req=req, src_pool=src, src_cell=0)
        assert not deliver(h, dst, injector=inj, dst_cell=1)
        assert src.n_live == 2 and dst.n_live == 0  # nothing moved
        assert h.src_pool is src
        assert deliver(h, dst, injector=inj, dst_cell=1)  # one-shot fault
        assert dst.n_live == 2

    def test_injected_block_corrupt_lands_nan_in_destination(self):
        """pool_block_corrupt poisons the first transferred block — the
        payload the decode guardrail must catch downstream."""
        from repro.serve.faults import FaultEvent, FaultInjector, FaultPlan

        src, dst = self._pool(), self._pool()
        sb, db = src.alloc(2), dst.alloc(2)
        dst.fault_injector = FaultInjector(FaultPlan(events=[
            FaultEvent("pool_block_corrupt")]))
        src.transfer_blocks(dst, sb, db)
        assert bool(jnp.all(jnp.isnan(dst.k[:, db[0]])))
        assert not bool(jnp.any(jnp.isnan(dst.k[:, db[1]])))


# =========================================================================
# pool negative paths: the free list must fail loudly, never corrupt
# =========================================================================
class TestPoolNegativePaths:
    def _pool(self, n_blocks=8, max_per_seq=4):
        return PagedKVPool(2, n_blocks, 4, KV, KV,
                           max_blocks_per_seq=max_per_seq)

    def test_double_free_and_foreign_free_rejected(self):
        pool = self._pool()
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            pool.free(blocks)  # already returned
        with pytest.raises(ValueError, match="double free|foreign"):
            pool.free([5])  # never allocated
        with pytest.raises(ValueError, match="trash"):
            pool.free([0])
        assert pool.n_free == 7 and pool.n_live == 0  # accounting intact

    def test_transfer_rejects_block_count_mismatch(self):
        src, dst = self._pool(), self._pool()
        sb, db = src.alloc(2), dst.alloc(3)
        with pytest.raises(ValueError, match="count mismatch"):
            src.transfer_blocks(dst, sb, db)

    def test_try_alloc_respects_per_seq_cap_and_exhaustion(self):
        pool = self._pool(n_blocks=8, max_per_seq=4)
        assert pool.try_alloc(5) is None      # over max_blocks_per_seq
        assert pool.try_alloc(4) is not None  # 3 free left
        assert pool.try_alloc(4) is None      # exhausted, all-or-nothing
        assert pool.n_free == 3               # failed attempts took nothing
        with pytest.raises(BlockPoolExhausted, match="free list has 3"):
            pool.alloc(4)

    def test_try_alloc_thread_hammering_never_double_allocates(self):
        """Many threads racing try_alloc/free: no block may ever be handed
        to two owners, and the free list must balance when the dust
        settles — the lock-guarded accounting the fleet's shared-pool
        engines rely on."""
        import threading

        pool = self._pool(n_blocks=33, max_per_seq=8)
        seen_twice, lock = [], threading.Lock()
        held: set = set()

        def hammer(seed):
            rng = np.random.default_rng(seed)
            for _ in range(200):
                got = pool.try_alloc(int(rng.integers(1, 5)))
                if got is None:
                    continue
                with lock:
                    dup = [b for b in got if b in held]
                    seen_twice.extend(dup)
                    held.update(got)
                with lock:
                    held.difference_update(got)
                pool.free(got)

        threads = [threading.Thread(target=hammer, args=(s,))
                   for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not seen_twice  # no block ever had two owners
        assert pool.n_free == 32 and pool.n_live == 0


# =========================================================================
# router placement policies
# =========================================================================
class TestRouterPolicies:
    def test_mode_affinity_pins_modes_to_home_cells(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        router = FleetRouter(cells, policy="mode_affinity")
        done = router.run(_reqs(n=8, modes=("M8", "M23")))
        homes = {}
        for r in done:
            homes.setdefault(r.mode, set()).add(r.engine_id)
        assert homes["M8"] != homes["M23"]  # distinct home cells
        assert all(len(v) == 1 for v in homes.values())  # never spilled

    def test_round_robin_spreads_across_cells(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        FleetRouter(cells, policy="round_robin").run(_reqs(n=6, modes=None))
        assert all(c.prefill.prefills > 0 for c in cells)

    def test_least_kv_avoids_pressured_cell(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        hot = [b for n in (8, 8, 4) for b in cells[0].pool.alloc(n)]
        router = FleetRouter(cells, policy="least_kv")
        done = router.run([ScheduledRequest(
            rid=0, prompt=np.arange(4, dtype=np.int32), max_new=2)])
        assert done[0].engine_id == 1
        cells[0].pool.free(hot)

    def test_unknown_policy_rejected(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 1, n_blocks=17, block_size=8)
        with pytest.raises(ValueError, match="unknown router policy"):
            FleetRouter(cells, policy="best_effort")

    def test_completion_fanout_by_submitter(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        router = FleetRouter(cells)
        reqs = _reqs(n=4, modes=None)
        for r in reqs:
            r.submitter = "alice" if r.rid % 2 == 0 else "bob"
        router.run(reqs)
        assert sorted(r.rid for r in router.drain("alice")) == [0, 2]
        assert sorted(r.rid for r in router.drain("bob")) == [1, 3]
        assert router.drain("alice") == []  # drained


# =========================================================================
# graceful degradation: backoff, caps, downgrade
# =========================================================================
class TestGracefulDegradation:
    def test_flood_requeues_and_completes_without_leak(self, params):
        """More concurrent requests than the pools can hold: admission must
        back off and retry (never raise), and every block must come home."""
        eng = _engine(params)
        # 4 allocatable blocks/cell = 2 concurrent requests/cell, flooded
        # with 10 simultaneous arrivals
        cells = make_fleet(eng, 2, n_blocks=5, block_size=8)
        router = FleetRouter(cells)
        reqs = _reqs(n=10, max_new=4, modes=None)
        for r in reqs:
            r.arrival = 0
        done = router.run(reqs)
        stats = router.stats()
        assert stats["completed"] == 10
        assert stats["requeues"] > 0  # pressure actually happened
        assert stats["blocks_live"] == 0 and stats["pending_handoffs"] == 0
        assert all(len(r.out) == r.max_new or r.out[-1] == r.eos_token
                   for r in done)

    def test_admission_caps_bound_inflight_per_mode(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 2, n_blocks=33, block_size=8)
        router = FleetRouter(cells, admission_caps={"M8": 1})
        done = router.run(_reqs(n=4, max_new=4, modes=("M8",)))
        assert len(done) == 4  # capped, not starved
        assert router.stats()["requeues"] > 0
        assert router._inflight["M8"] == 0  # accounting drained

    def test_downgrade_after_sustained_pressure(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 1, n_blocks=17, block_size=8)
        router = FleetRouter(cells, downgrade_after=2)
        hold = cells[0].pool.alloc(8) + cells[0].pool.alloc(8)  # starve
        req = ScheduledRequest(rid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new=2, mode="M23")
        router.submit(req)
        for _ in range(8):  # enough ticks for requeues to pass the threshold
            router.step()
        assert req.requeues >= 2
        assert req.downgraded_from == "M23"
        assert req.mode in DOWNGRADE_CHAIN.values()
        cells[0].pool.free(hold)
        for _ in range(200):
            router.step()
            if router.completed:
                break
        assert router.completed and router.completed[0].rid == 0
        assert router.stats()["downgrades"] >= 1

    def test_never_satisfiable_request_still_raises(self, params):
        """Graceful degradation covers transient pressure; a request that can
        NEVER fit (bigger than the whole pool) fails loudly at submit."""
        eng = _engine(params)
        cells = make_fleet(eng, 2, n_blocks=3, block_size=4,
                           max_blocks_per_seq=2)
        router = FleetRouter(cells)
        with pytest.raises(BlockPoolExhausted):
            router.submit(ScheduledRequest(
                rid=0, prompt=np.arange(20, dtype=np.int32), max_new=8))

    def test_fleet_stats_have_latency_percentiles(self, params):
        eng = _engine(params)
        cells = make_fleet(eng, 1, n_blocks=17, block_size=8)
        router = FleetRouter(cells)
        router.run(_reqs(n=3, max_new=4, modes=None))
        stats = router.stats()
        for k in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
                  "itl_p95_ms", "queue_wait_p95_steps"):
            assert k in stats and stats[k] >= 0.0


# =========================================================================
# sequence-parallel decode attention (the sharded backend's decode path)
# =========================================================================
TOLS = {"M8": 5e-3, "M16": 1e-4, "M23": 1e-5}


def _qkv(seed=0, B=2, T=21, H=4, Dh=8):
    # T=21 is not a multiple of the device count: exercises the zero-pad
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
    return q, k, v


class TestSequenceParallelDecode:
    @pytest.mark.parametrize("mode", ["M8", "M16", "M23"])
    def test_matches_single_device_einsum(self, mode):
        q, k, v = _qkv()
        ln = jnp.asarray([21, 13], jnp.int32)
        want = masked_decode_attention(q, k, v, ln, mode, backend="ref")
        got = sp_decode_attention(q, k, v, ln, mode)
        np.testing.assert_allclose(got, want, rtol=TOLS[mode],
                                   atol=TOLS[mode])

    def test_masked_rows_flush_exact_zero(self):
        q, k, v = _qkv(seed=1)
        ln = jnp.asarray([15, 0], jnp.int32)  # slot 1 inactive
        out = sp_decode_attention(q, k, v, ln, "M16")
        assert bool(jnp.all(out[1] == 0.0))

    def test_under_jit(self):
        q, k, v = _qkv(seed=2)
        ln = jnp.asarray([21, 7], jnp.int32)
        want = sp_decode_attention(q, k, v, ln, "M16")
        got = jax.jit(lambda *a: sp_decode_attention(*a, "M16"))(q, k, v, ln)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_masked_decode_attention_sharded_backend_routes(self):
        q, k, v = _qkv(seed=3)
        ln = jnp.asarray([21, 9], jnp.int32)
        want = masked_decode_attention(q, k, v, ln, "M16", backend="ref")
        got = masked_decode_attention(q, k, v, ln, "M16", backend="sharded")
        np.testing.assert_allclose(got, want, rtol=TOLS["M16"],
                                   atol=TOLS["M16"])

    def test_dispatch_attention_sharded_decode_shape(self):
        """S==1 through dispatch_attention 'sharded' runs sequence-parallel
        (previously it dropped to the single-device blocked oracle)."""
        q, k, v = _qkv(seed=4)
        T_ = k.shape[1]
        want = dispatch_attention(q, k, v, "M16", causal=True,
                                  q_offset=T_ - 1, backend="ref")
        got = dispatch_attention(q, k, v, "M16", causal=True,
                                 q_offset=T_ - 1, backend="sharded")
        np.testing.assert_allclose(got, want, rtol=TOLS["M16"],
                                   atol=TOLS["M16"])

    def test_auto_format_falls_back(self):
        q, k, v = _qkv(seed=5)
        ln = jnp.asarray([21, 9], jnp.int32)
        want = masked_decode_attention(q, k, v, ln, "AUTO", backend="ref")
        got = sp_decode_attention(q, k, v, ln, "AUTO")
        np.testing.assert_allclose(got, want, rtol=0, atol=0)
