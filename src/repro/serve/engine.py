"""Serving engine: batched request prefill + decode with per-slot KV caches.

Continuous-batching-lite: a fixed pool of ``max_batch`` slots; requests attach
to free slots, prefill fills the slot's cache region, decode advances every
active slot in one jit'd step.  Precision: decode runs the ``serve_default``
policy (paper mode 2 with mode-3 logits) or AUTO — the run-time
reconfigurability the paper targets at 'portable devices' maps to serving's
latency/quality dial here.

Run-time reconfiguration endpoint: :meth:`ServeEngine.set_policy` accepts a
``PrecisionPolicy`` (object, JSON string, or parsed payload — the wire format
of ``PrecisionPolicy.to_json``, which embeds any custom format definitions)
and swaps the precision of all subsequent prefill/decode steps.  Step
functions are cached per policy, so flipping between a small set of policies
re-traces once per policy, then swaps are free.

Weight pre-limbing: decode is matmul-bound at tiny M (one token per slot),
so the per-step VPU limb cascade over every *weight* dominates the paper's
"truncate before multiply" cost.  The engine decomposes the dense-path
weights ONCE per (policy, params) — with ``limbs.prelimb_weight`` at the
policy's maximum limb count — and runs decode steps against
:class:`~repro.core.limbs.PrelimbedWeight` operands, which dispatch routes
through ``mp_matmul_prelimbed_weights`` (the kernel's ``prelimbed_b``
variant): B-limb extraction leaves the decode loop entirely.  The stacks
are laid out once in the shape the kernel reads — one buffer per layer,
padded to the kernel's blocks — so a decode step copies no weight bytes
(:func:`prelimb_dense_params`).  Prefill keeps
the raw weights (it wants the fused multi-output projection kernel, which
re-extracts limbs it shares across a whole group).  AUTO policies skip
pre-limbing — the controller analyzes raw operand values.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import context as context_lib
from repro.core.formats import is_auto
from repro.core.lanes import LaneCtx, LaneEnvelope, lane_scope
from repro.core.limbs import PrelimbedWeight, prelimb_weight
from repro.core.policy import PrecisionPolicy
from repro.models import transformer as T
from repro.models.mla import head_matrices
from repro.serve.kv_cache import PagedKVCache, PagedKVPool
from repro.train.trainer import make_prefill_step, make_serve_step

# op classes whose weights sit on the decode path (the pre-limb set); a
# model with routed experts adds _MOE_CLASSES
_PRELIMB_CLASSES = ("qkv", "attn_out", "ffn", "lm_head")
_MOE_CLASSES = ("moe_router", "moe_expert")

# params-tree groups -> weight leaves read as whole matrices (one limb stack
# each); anything reshaped, LoRA-patched or scanned inside a layer — SSM,
# the hybrid shared block — is deliberately absent
_PRELIMB_LEAVES = {"mlp": ("w_gate", "w_up", "w_down"),
                   "attn": ("wq", "wk", "wv", "wo"),
                   "mla": ("w_q", "w_dq", "w_uq", "w_dkv", "w_kr", "w_o"),
                   "moe": ("router",),
                   "shared": ("w_gate", "w_up", "w_down")}
# (E, K, N) stacks of held experts: one limb stack per expert
_EXPERT_LEAVES = {"moe": ("w_gate", "w_up", "w_down")}
# MLA's latent up-projections: one limb stack per head, as the absorbed
# decode reads them (models/mla.head_matrices)
_HEAD_LEAVES = {"mla": ("w_uk", "w_uv")}


def _policy_prelimb_limbs(policy: PrecisionPolicy,
                          classes=_PRELIMB_CLASSES) -> Optional[int]:
    """Max limb count any decode-path forward format needs, or None when an
    AUTO rule makes pre-limbing unusable (AUTO analyzes raw values)."""
    n = 1
    for c in classes:
        mode = policy.mode(c)
        if is_auto(mode):
            return None
        n = max(n, mode.n_limbs)
    return n


def _prelimb_padded(w, n_limbs: int) -> PrelimbedWeight:
    """One weight's limb stack, stored at the kernel's padded shape."""
    from repro.kernels.ops import prelimbed_storage_shape  # deferred: pallas

    return prelimb_weight(w, n_limbs,
                          padded=prelimbed_storage_shape(*w.shape[-2:]))


def _leaf_kind(group, key) -> Optional[str]:
    if key in _EXPERT_LEAVES.get(group, ()):
        return "experts"
    if key in _HEAD_LEAVES.get(group, ()):
        return "heads"
    if key in _PRELIMB_LEAVES.get(group, ()):
        return "matrix"
    return None


def _walk(tree, fn, group=None):
    """``fn(group, key, leaf)`` over a nested dict of arrays."""
    return {k: (_walk(v, fn, k) if isinstance(v, dict) else fn(group, k, v))
            for k, v in tree.items()}


def _prelimb_stack(blocks: dict, n_limbs: int, mla=None):
    """Prelimb one layer-stacked params group into a list of per-layer
    trees, each limb stack its own (n_limbs, K', N') buffer, prelimbed
    layer by layer so the stacked and split stacks are never held at once.
    Expert stacks become a list of per-expert stacks, MLA's latent
    up-projections a list of per-head ones (``mla`` gives the heads).

    A stack is split only where every leaf left raw is a per-layer vector
    (norm gains); otherwise (SSM or hybrid layers, whose raw matrices a
    split would copy) it is returned unchanged."""
    kinds = []
    _walk(blocks, lambda g, k, x: kinds.append((_leaf_kind(g, k), x.ndim)))
    if not any(kind for kind, _ in kinds) or any(
            kind is None and nd > 2 for kind, nd in kinds):
        return blocks

    def one(i):
        def leaf(group, key, x):
            x = x[i]
            kind = _leaf_kind(group, key)
            if kind == "experts":
                return [_prelimb_padded(w, n_limbs) for w in x]
            if kind == "heads":
                return [_prelimb_padded(w, n_limbs)
                        for w in head_matrices(x, mla, key)]
            return _prelimb_padded(x, n_limbs) if kind else x

        return _walk(blocks, leaf)

    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    return [one(i) for i in range(n)]


def prelimb_dense_params(params, n_limbs: int, mla=None):
    """Decompose the decode-path weight matrices of a transformer params
    tree into :class:`PrelimbedWeight` limb stacks (one-time, per policy),
    with the jnp cascade ``limbs.prelimb_weight`` on every backend.

    Each stack is stored zero-padded to the kernel's block multiples
    (``ops.prelimbed_storage_shape``), so the decode step pads no weight.
    A layer stack comes back as a list of per-layer trees
    (``_prelimb_stack``; ``mla``, the model's ``MLADims``, splits MLA's
    latent up-projections per head): the forward then walks the layers in
    Python and each kernel reads its layer's (expert's, head's) stack in
    place, where a ``lax.scan`` would copy every layer's slice out of the
    stacked weights on every decode step.  Non-dict / absent groups pass
    through untouched.
    """
    out = dict(params)
    for stack_key in ("layers", "dense_layers"):
        if isinstance(out.get(stack_key), dict):
            out[stack_key] = _prelimb_stack(out[stack_key], n_limbs, mla)
    for head in ("lm_head", "ctc_head"):
        if isinstance(out.get(head), dict) and "w" in out[head]:
            out[head] = {**out[head],
                         "w": _prelimb_padded(out[head]["w"], n_limbs)}
    return out


def prelimb_pad_bytes(tree) -> int:
    """Bytes of zeros the padded limb stacks of ``tree`` store."""
    total = 0
    for w in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, PrelimbedWeight)):
        if isinstance(w, PrelimbedWeight):
            logical = int(np.prod(w.limbs.shape[:-2])) * w.kn[0] * w.kn[1]
            total += (w.limbs.size - logical) * w.limbs.dtype.itemsize
    return total


def expert_limb_bytes(tree) -> int:
    """Bytes of the held experts' limb stacks in a prelimbed tree."""
    total = 0
    for stack in (tree.get("layers"), tree.get("dense_layers")):
        for layer in stack if isinstance(stack, list) else ():
            moe = layer.get("moe", {})
            for key in _EXPERT_LEAVES["moe"]:
                total += sum(w.limbs.nbytes for w in moe.get(key, ())
                             if isinstance(w, PrelimbedWeight))
    return total


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _paged_cache(pool_k, pool_v, table, lengths) -> T.ModelCache:
    """The model's cache over the paged pool: the whole pool with the slots'
    block table and lengths, which the layers thread from the first to the
    last (``transformer._thread_pool``)."""
    return T.ModelCache(attn=PagedKVCache(pool_k, pool_v, table, lengths))


def make_paged_prefill_step(cfg: ModelConfig, policy: PrecisionPolicy,
                            mesh=None):
    """Prefill one (micro-batch of) fresh request(s) into the paged pool.

    ``table`` (B, max_blocks) / ``lengths`` (B,) are the host scheduler's
    slot state (lengths are 0: paged prefill targets fresh slots only);
    ``last_idx`` is the true prompt length minus one — prompts are padded to
    a shape bucket, the padded tail writes land past the reservation (trash
    or rewritten-before-read positions, serve/kv_cache.py) and the returned
    logits row is the real last token's.

    Returns ``(last_logits, guard_stat, pool_k, pool_v)`` — ``guard_stat``
    is the per-slot max |logit| scalar the numerical guardrail polices
    (``jnp.max`` propagates NaN, so non-finite logits surface as a
    non-finite stat); computing it inside the step keeps the check free of
    extra launches.  The pools are the ones handed in, updated in place:
    the engine jits every paged step with them donated
    (``POOL_ARGNUMS``).
    """
    def step(params, pool_k, pool_v, table, lengths, tokens, last_idx):
        cache = _paged_cache(pool_k, pool_v, table, lengths)
        logits, _, new_cache = T.forward(params, {"tokens": tokens}, cfg,
                                         policy, cache=cache, mesh=mesh)
        last = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1, axis=1)
        stat = jnp.max(jnp.abs(last[:, 0, :]), axis=-1)
        return last, stat, new_cache.attn.k, new_cache.attn.v

    return step


def make_paged_decode_step(cfg: ModelConfig, policy: PrecisionPolicy,
                           mesh=None):
    """One decode step over a compacted micro-batch of active slots.

    The active-slot mask is carried by the (table, lengths) pair itself:
    padded/inactive rows are (all-trash row, length 0), so their reads mask
    to nothing and their writes land in the trash block — no in-kernel
    branching.  Returns ``(logits (B, 1, V), guard_stat (B,), new pool k,
    new pool v)``: ``guard_stat`` is the per-slot max |logit| the numerical
    guardrail polices — folded into the step so the finite check costs one
    scalar per slot and no extra launch.
    """
    def step(params, pool_k, pool_v, table, lengths, tokens):
        cache = _paged_cache(pool_k, pool_v, table, lengths)
        logits, _, new_cache = T.forward(params, {"tokens": tokens}, cfg,
                                         policy, cache=cache, mesh=mesh)
        stat = jnp.max(jnp.abs(logits[:, -1, :]), axis=-1)
        return logits, stat, new_cache.attn.k, new_cache.attn.v

    return step


def make_mixed_decode_step(cfg: ModelConfig, envelope: LaneEnvelope,
                           mesh=None):
    """One partitioned-lane decode step: a heterogeneous micro-batch whose
    slots run at different (non-AUTO) formats inside ONE launch.

    ``envelope`` is the static per-op-class (n_limbs, max_order) ceiling —
    it keys the trace, so any batch that fits under it shares the compiled
    step regardless of which formats sit in which lane.  ``lane_n`` /
    ``lane_ord`` are (C, B) int32 *traced* inputs (C =
    ``lanes.DECODE_OP_CLASSES``): changing a slot's format between ticks is
    a new input value, not a new trace.  The lane context rides a
    contextvar installed around the traced body (the ``pin_backend``
    pattern), so the model code needs no signature changes — projection and
    attention call sites pick it up via ``lanes.current_lanes()``.

    The policy passed to the model is only a carrier for the non-lane ops
    (all format-independent at S == 1); every format-sensitive contraction
    reads the lane tables instead.  Same (logits, guard_stat, pools) return
    contract as :func:`make_paged_decode_step`.
    """
    carrier = PrecisionPolicy.serve_default()

    def step(params, pool_k, pool_v, table, lengths, tokens, lane_n,
             lane_ord):
        cache = _paged_cache(pool_k, pool_v, table, lengths)
        ctx = LaneCtx(envelope, lane_n.astype(jnp.int32),
                      lane_ord.astype(jnp.int32))
        with lane_scope(ctx):
            logits, _, new_cache = T.forward(params, {"tokens": tokens}, cfg,
                                             carrier, cache=cache, mesh=mesh)
        stat = jnp.max(jnp.abs(logits[:, -1, :]), axis=-1)
        return logits, stat, new_cache.attn.k, new_cache.attn.v

    return step


def paged_model_error(cfg: ModelConfig) -> Optional[str]:
    """Why the paged serving path cannot run ``cfg``, or None: it serves
    dense GQA stacks, and MoE stacks with latent attention (MLA)."""
    if cfg.family == "dense" and cfg.mla is None:
        return None
    if cfg.family == "moe" and cfg.mla is not None:
        return None
    return (f"paged serving runs dense GQA models and MoE models with "
            f"latent attention (family={cfg.family!r}, "
            f"mla={cfg.mla is not None})")


def pool_entry_shapes(cfg: ModelConfig):
    """Each token's (k, v) entry in one layer of the paged pool: the GQA
    heads' keys and values, or MLA's latent ``c_kv`` and rope key."""
    if cfg.mla is not None:
        return (cfg.mla.kv_lora,), (cfg.mla.qk_rope_dim,)
    kv = (cfg.n_kv_heads, cfg.resolved_head_dim)
    return kv, kv


# the arguments of every paged step that hold the pool (``pool_k``,
# ``pool_v``): the engine jits the steps with them donated, so each step
# updates the pool in place
POOL_ARGNUMS = (1, 2)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 512,
                 policy: Optional[PrecisionPolicy] = None, mesh=None,
                 greedy: bool = True, matmul_backend: Optional[str] = None,
                 prelimb_weights: bool = True):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.mesh = mesh
        self.greedy = greedy
        # backend routing is a trace-time decision (core/dispatch.py): the
        # wrapper pins it around the traced body so one engine can run ref on
        # CPU CI, the autotuned Pallas kernel on a TPU slice, or the sharded
        # path on a multi-device host without touching the model code
        self.matmul_backend = matmul_backend
        self.prelimb_weights = prelimb_weights
        self._step_cache: Dict[PrecisionPolicy, Tuple] = {}
        self._paged_step_cache: Dict[PrecisionPolicy, Tuple] = {}
        # LaneEnvelope -> (mixed_decode_step,): the partitioned-lane decode
        # trace cache.  Keyed by the static envelope, NOT the format mix —
        # a mode joining mid-stream re-uses the batch-max trace as long as
        # it fits under the envelope (no re-trace, no eviction of live
        # per-policy entries; asserted by the serve soak)
        self._mixed_step_cache: Dict[LaneEnvelope, Tuple] = {}
        # observability: traces actually executed vs. step/prelimb cache
        # reuse — the scheduler folds these into its stats() so tests and
        # the soak gate can assert "no stray trace on a mid-stream join"
        self.trace_events = 0
        self.step_cache_hits = 0
        self.step_cache_misses = 0
        self.prelimb_cache_hits = 0
        self.prelimb_cache_misses = 0
        self.pool_steps_donated = 0
        # (n_limbs, id(params)) -> prelimbed tree: the id guards against a
        # live params swap (eng.params = reloaded) silently leaving decode on
        # stale limb stacks while prefill uses the new weights
        self._prelimb_cache: Dict[Tuple[int, int], dict] = {}
        self.policy = (policy
                       or context_lib.current_context().policy
                       or PrecisionPolicy.serve_default())
        self._prefill, self._decode = self._steps_for(self.policy)
        self._decode_params_for(self.policy)  # eager decompose (cold-start)
        # NOTE: no engine-owned KV pool here — generate() and the throughput
        # probe each build their own cache (a resident pool would only double
        # cache memory; the v2 engine allocated one and never used it)
        self._slots: List[Optional[Request]] = [None] * max_batch

    @property
    def _decode_params(self):
        """Decode-step params, resolved lazily so a live ``eng.params`` swap
        (checkpoint reload) can never leave decode on stale limb stacks."""
        return self._decode_params_for(self.policy)

    # distinct policies whose jit'd steps stay resident; per-request swapping
    # across more than this re-traces in LRU fashion instead of leaking
    # compiled executables without bound
    MAX_POLICY_CACHE = 8

    def _counted_trace(self, fn, name: str):
        """Bump ``trace_events`` each time jax (re)traces ``fn`` — the body
        runs once per trace, so the counter is a trace spy, not a call
        counter (compiled executions never re-enter the Python body).  The
        wrapper carries ``name``, which ``jax.jit`` turns into the program's
        name (``jit_<name>``) on the device trace."""
        def counted(*args, **kwargs):
            self.trace_events += 1
            return fn(*args, **kwargs)

        counted.__name__ = counted.__qualname__ = name
        return counted

    def _cached_steps(self, cache: Dict, key, factories: Tuple,
                      donate_argnums: Tuple = ()) -> Tuple:
        """Shared LRU discipline for every jit'd step cache (keyed by policy
        or lane envelope): touch on hit, evict oldest past MAX_POLICY_CACHE,
        trace (with the engine's backend pinned) on miss.  Each step is
        named after its factory: ``make_paged_decode_step`` gives
        ``paged_decode`` (program ``jit_paged_decode``)."""
        if key in cache:
            cache[key] = cache.pop(key)  # LRU touch
            self.step_cache_hits += 1
        else:
            from repro.core.dispatch import pin_backend

            self.step_cache_misses += 1
            while len(cache) >= self.MAX_POLICY_CACHE:
                cache.pop(next(iter(cache)))
            cache[key] = tuple(
                jax.jit(self._counted_trace(
                    pin_backend(make(self.cfg, key, self.mesh),
                                self.matmul_backend),
                    make.__name__.removeprefix("make_").removesuffix("_step")),
                    donate_argnums=donate_argnums)
                for make in factories)
        return cache[key]

    def _steps_for(self, policy: PrecisionPolicy) -> Tuple:
        """jit'd (prefill, decode) pair for one policy (LRU-cached: swapping
        among a working set of policies re-traces once each, then is free)."""
        return self._cached_steps(self._step_cache, policy,
                                  (make_prefill_step, make_serve_step))

    def paged_steps_for(self, policy: PrecisionPolicy) -> Tuple:
        """jit'd (paged_prefill, paged_decode) pair for one policy.

        The continuous scheduler resolves a policy *per request* and buckets
        compatible requests per decode micro-batch; this cache is what makes
        a working set of per-request modes free after the first trace (same
        LRU discipline as :meth:`_steps_for`).  Paged serving runs the
        models :func:`paged_model_error` admits."""
        err = paged_model_error(self.cfg)
        if err is not None:
            raise NotImplementedError(err)
        return self._cached_steps(
            self._paged_step_cache, policy,
            (make_paged_prefill_step, make_paged_decode_step),
            donate_argnums=POOL_ARGNUMS)

    def mixed_decode_step_for(self, envelope: LaneEnvelope):
        """jit'd partitioned-lane decode step for one static lane envelope.

        The envelope — not the format mix — keys the trace, so every batch
        that fits under it (any per-slot assignment of formats at or below
        the per-class ceilings) shares one compiled executable.  A mode
        joining mid-stream therefore reuses the batch-max trace instead of
        minting (and possibly evicting) per-policy entries.  Dense GQA
        models only: latent attention and routed experts have no lane
        kernels, so a batch of mixed formats on them cannot share one
        launch."""
        if self.cfg.family != "dense" or self.cfg.mla is not None:
            raise NotImplementedError(
                f"mixed-format decode lanes run dense GQA models only; "
                f"latent attention and routed experts have no lane kernels "
                f"(family={self.cfg.family!r}, "
                f"mla={self.cfg.mla is not None})")
        return self._cached_steps(self._mixed_step_cache, envelope,
                                  (make_mixed_decode_step,),
                                  donate_argnums=POOL_ARGNUMS)[0]

    def launch_paged(self, step, pool: PagedKVPool, params, *args) -> list:
        """Launch one paged step (of :meth:`paged_steps_for` or
        :meth:`mixed_decode_step_for`) over ``pool``, which it takes
        donated, and adopt the pools it returns at once; returns the step's
        other outputs (logits, guard stat)."""
        *out, k, v = step(params, pool.k, pool.v, *args)
        pool.update(k, v)
        self.pool_steps_donated += 1
        return out

    def set_policy(self, policy: Union[PrecisionPolicy, str, bytes, dict]
                   ) -> PrecisionPolicy:
        """Hot-swap the precision policy for all subsequent steps (the
        serving control-plane endpoint for the paper's run-time mode dial).

        Accepts a ``PrecisionPolicy`` or its JSON wire form
        (``PrecisionPolicy.to_json``; embedded custom formats are registered
        on the fly).  Safe mid-stream: the KV cache layout is policy-
        independent, so in-flight generations continue at the new precision.
        Returns the active policy."""
        if not isinstance(policy, PrecisionPolicy):
            policy = PrecisionPolicy.from_json(policy)
        self.policy = policy
        self._prefill, self._decode = self._steps_for(policy)
        self._decode_params_for(policy)  # warm the prelimb cache eagerly
        return policy

    def _decode_params_for(self, policy: PrecisionPolicy):
        """Decode-step params: dense-path weights as pre-extracted limb
        stacks, decomposed ONCE per (policy limb count, params) and cached.
        Falls back to the raw params under AUTO policies or when pre-limbing
        is disabled."""
        classes = _PRELIMB_CLASSES + (_MOE_CLASSES if self.cfg.moe else ())
        return self._decode_params_for_limbs(
            _policy_prelimb_limbs(policy, classes))

    def _decode_params_for_limbs(self, n: Optional[int]):
        """Pre-limbed decode params at an explicit limb depth — the entry
        the mixed path uses with the *batch-max envelope* depth, so a
        heterogeneous batch shares the homogeneous cache entry of its
        deepest member (``decompose`` is depth-stable: the first k limbs of
        a deeper stack are bit-identical to the k-limb stack, which is what
        lets shallower lanes mask into the shared stack).  The key is
        (n_limbs, id(params)): a mode joining mid-stream under the same
        envelope is a pure cache hit — counted, so the soak can assert no
        live entry was evicted or re-decomposed."""
        if not self.prelimb_weights or n is None:
            return self.params
        key = (n, id(self.params))
        if key in self._prelimb_cache:
            self.prelimb_cache_hits += 1
        else:
            self.prelimb_cache_misses += 1
            stale = [k for k in self._prelimb_cache if k[1] != id(self.params)]
            for k in stale:
                del self._prelimb_cache[k]
            self._prelimb_cache[key] = prelimb_dense_params(
                self.params, n, mla=self.cfg.mla)
        return self._prelimb_cache[key]

    def cache_stats(self) -> Dict[str, int]:
        """Trace/cache observability counters (merged into scheduler
        ``stats()``): ``trace_events`` counts jit traces actually executed;
        the hit/miss pairs cover the jit'd-step LRU and the prelimbed-weight
        cache; ``prelimb_pad_bytes`` is the memory the cached limb stacks
        spend on zero padding to the kernel's blocks;
        ``expert_limb_bytes`` the cached limb stacks of the held experts,
        and ``experts_held`` how many experts each MoE layer holds (0 for
        a model without routed experts); ``pool_steps_donated`` counts the
        paged steps launched with the pool donated (:meth:`launch_paged`)."""
        moe = self.cfg.moe
        return {
            "trace_events": self.trace_events,
            "step_cache_hits": self.step_cache_hits,
            "step_cache_misses": self.step_cache_misses,
            "prelimb_cache_hits": self.prelimb_cache_hits,
            "prelimb_cache_misses": self.prelimb_cache_misses,
            "prelimb_pad_bytes": sum(prelimb_pad_bytes(t) for t
                                     in self._prelimb_cache.values()),
            "expert_limb_bytes": sum(expert_limb_bytes(t) for t
                                     in self._prelimb_cache.values()),
            "experts_held": moe.held if moe is not None else 0,
            "pool_steps_donated": self.pool_steps_donated,
        }

    # -- single-request path (prefill writes the whole pool cache; simple and
    #    jit-stable: one prefill per unique prompt length bucket) -----------
    def generate(self, prompts: List[np.ndarray], max_new: int = 16
                 ) -> List[List[int]]:
        """Batched greedy generation: pads prompts to one bucket, prefills the
        pool, then runs ``max_new`` fused decode steps."""
        B = len(prompts)
        assert B <= self.max_batch
        L = max(len(p) for p in prompts)
        toks = np.zeros((self.max_batch, L), np.int32)
        for i, p in enumerate(prompts):
            toks[i, L - len(p):] = p  # left-pad (simplest aligned decoding)
        cache = T.make_cache(self.cfg, self.max_batch, self.max_seq,
                             dtype=jnp.float32)
        logits, cache = self._prefill(self.params,
                                      {"tokens": jnp.asarray(toks)}, cache)
        outs = [[] for _ in range(self.max_batch)]
        cur = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        for _ in range(max_new):
            for i in range(B):
                outs[i].append(int(cur[i, 0]))
            logits, cache = self._decode(self._decode_params, cache, cur)
            cur = jnp.argmax(logits[:, -1, :], axis=-1
                             ).astype(jnp.int32)[:, None]
        return [outs[i] for i in range(B)]

    def decode_throughput_probe(self, steps: int = 8) -> Dict[str, float]:
        """Timing probe used by benchmarks (tokens/s at the pool batch)."""
        import time
        cache = T.make_cache(self.cfg, self.max_batch, self.max_seq,
                             dtype=jnp.float32)
        tok = jnp.zeros((self.max_batch, 1), jnp.int32)
        logits, cache = self._decode(self._decode_params, cache, tok)  # compile
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(steps):
            logits, cache = self._decode(self._decode_params, cache, tok)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        return {"tokens_per_s": self.max_batch * steps / dt,
                "ms_per_step": dt / steps * 1e3}
