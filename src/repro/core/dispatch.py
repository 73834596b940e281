"""Unified backend dispatch for ``mp_matmul`` — one routing layer for every
realization of the paper's reconfigurable multiplier (DESIGN.md §5).

Every ``mp_matmul`` call funnels through :func:`dispatch`, which routes to a
registered backend:

  ref               pure-jnp limb matmuls (XLA fuses; oracle + dry-run)
  pallas            fused Pallas kernel compiled by Mosaic, block sizes from
                    the autotune table; TPU only (raises elsewhere)
  pallas_interpret  same kernel, interpreter mode (CPU validation)
  sharded           shard_map data-parallel path: the contraction (K) dim
                    shards over a 1-D device mesh, each device accumulates
                    its limb-order partials locally, ONE psum reduces the
                    (n_orders, M, N) stack, and the compensated cross-order
                    combine runs after the reduce

The sharded backend's collective placement is mode-aware by construction:
the reduce payload is ``n_orders × M × N`` fp32 — 1× for M8 up to 7× for M52
— instead of ``n_products`` separate reduces (up to 28×).  Low modes cut
communication bytes, not just MXU passes.  Reducing *per-order* partials
(rather than locally combining to one buffer) keeps the numerics
partition-invariant: the Neumaier combine sees the same per-order totals a
single device would, so shard count never changes which rounding the result
absorbs beyond fp32 psum reassociation.

Backends are keyed by :class:`repro.core.formats.MPFormat` (run-time
registered formats route identically to the paper's built-ins), and the
default backend / autotune flag / default mesh come from the active
:class:`repro.core.context.PrecisionContext` — there is no module-level
mutable backend state.  The v1 global-flavored helpers below
(``set_default_backend``, ``use_backend``) are deprecated shims over the
context.

The custom VJP lives one level up (core/mpmatmul.py) and treats every backend
uniformly — backward passes re-enter ``dispatch`` at their bwd formats.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import context as context_lib
from repro.core.formats import FormatLike, MPFormat, is_auto, resolve
from repro.core.limbs import DD, PrelimbedWeight
from repro.kernels import ref as ref_backend

Operand = Union[jax.Array, DD, PrelimbedWeight]

BACKENDS = ("ref", "pallas", "pallas_interpret", "sharded")


# ---------------------------------------------------------------------------
# default-backend plumbing — deprecated shims over the PrecisionContext
# ---------------------------------------------------------------------------
def set_default_backend(name: str) -> None:
    """Deprecated: use ``mp.configure(backend=...)``.  Mutates the process-
    default context (kept so v1 launchers keep working)."""
    warnings.warn("set_default_backend is deprecated; use "
                  "repro.mp.configure(backend=...)", DeprecationWarning,
                  stacklevel=2)
    context_lib.configure(backend=name)


def get_default_backend() -> str:
    """The active context's backend (scoped override, else process default)."""
    return context_lib.current_context().backend


@contextlib.contextmanager
def use_backend(name: str):
    """Deprecated: use ``with mp.context(backend=...)`` (trace-time: wrap the
    jit call, not the step)."""
    warnings.warn("use_backend is deprecated; use "
                  "repro.mp.context(backend=...)", DeprecationWarning,
                  stacklevel=3)
    with context_lib.context(backend=name) as ctx:
        yield ctx


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
def _run_ref(a: Operand, b: Operand, fmt: MPFormat, out_dtype):
    return ref_backend.mp_matmul_ref(a, b, fmt, out_dtype=out_dtype)


def _tuned_blocks(a: Operand, b: Operand, fmt: MPFormat, interpret: bool
                  ) -> Tuple[Optional[int], Optional[int], Optional[int]]:
    """Autotune-table lookup for the shape ops.mp_matmul_pallas will run.

    Mirrors the ops layer's batch folding: an a-batched × 2-D b contraction
    folds the batch into M.  Sweeps happen only when the active context's
    ``autotune`` flag is set (env shim: REPRO_MP_AUTOTUNE=1) — otherwise this
    is a pure table read (cold processes never stall)."""
    if isinstance(a, DD) or isinstance(b, DD):
        return None, None, None
    if b.ndim != 2:
        return None, None, None
    from repro.kernels import autotune

    M = 1
    for d in a.shape[:-1]:
        M *= d
    K, N = b.shape
    if context_lib.autotune_enabled():
        bm, bk, bn = autotune.autotune(M, K, N, fmt, dtype=jnp.float32,
                                       interpret=interpret)
        return bm, bk, bn
    blocks = autotune.lookup(M, K, N, fmt)
    return blocks if blocks is not None else (None, None, None)


def kernel_interpret(backend: str) -> bool:
    """The ``interpret`` flag for a Pallas backend name.

    ``pallas`` is compiled Mosaic and needs a TPU: on any other host it
    raises instead of quietly running the interpreter, so a run that asked
    for the kernels either runs them on the chip or fails.
    ``pallas_interpret`` runs the same kernels in the interpreter (CPU
    validation)."""
    if backend == "pallas_interpret":
        return True
    if backend != "pallas":
        raise ValueError(f"{backend!r} is not a Pallas backend")
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"backend 'pallas' compiles Mosaic kernels for a TPU, but JAX's "
            f"backend here is {jax.default_backend()!r}; use "
            f"backend='pallas_interpret' to run the kernels in the "
            f"interpreter")
    return False


def _run_pallas(a: Operand, b: Operand, fmt: MPFormat, out_dtype,
                *, backend: str):
    from repro.kernels import ops as pallas_backend  # deferred: imports pallas

    interpret = kernel_interpret(backend)
    bm, bk, bn = _tuned_blocks(a, b, fmt, interpret)
    return pallas_backend.mp_matmul_pallas(
        a, b, fmt, out_dtype=out_dtype, interpret=interpret,
        bm=bm, bk=bk, bn=bn)


def _sharded_2d(a: jax.Array, b: jax.Array, fmt: MPFormat, out_dtype,
                mesh, axis: str) -> jax.Array:
    n = mesh.shape[axis]
    K = a.shape[1]
    pad = (-K) % n
    if pad:
        # zero K-padding is exact: limbs of 0 are 0, contributing nothing to
        # any order's partial sum
        a = jnp.pad(a, [(0, 0), (0, pad)])
        b = jnp.pad(b, [(0, pad), (0, 0)])

    def local(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        partials = ref_backend.mp_matmul_partials(a_loc, b_loc, fmt)
        return jax.lax.psum(partials, axis)  # (n_orders, M, N), ONE collective

    partials = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, None, None),
        check_vma=False,
    )(a, b)
    return ref_backend.combine_partials(partials, fmt, out_dtype=out_dtype)


def _bound_axis_names() -> Tuple:
    """Mesh axis names bound by an enclosing shard_map/xmap/named-vmap scope.

    Nested shard_map is unsupported — a sharded-backend matmul inside e.g.
    the MoE expert-parallel body must fall back to local compute (the outer
    scope already owns the devices)."""
    from jax._src import core as _core  # no public accessor

    return tuple(_core.unsafe_get_axis_names())


def _run_sharded(a: Operand, b: Operand, fmt: MPFormat, out_dtype,
                 *, mesh=None, axis: str = "data"):
    """K-sharded multi-device path; falls back to ref where sharding the
    contraction cannot help (DD operands, both-batched einsums, 1 device)
    or cannot work (already inside a shard_map scope).  The mesh comes from
    the call, else the active context, else the default 1-D matmul mesh."""
    if isinstance(a, (DD, PrelimbedWeight)) or isinstance(b, (DD, PrelimbedWeight)) \
            or b.ndim != 2:
        return _run_ref(a, b, fmt, out_dtype)
    if _bound_axis_names():
        return _run_ref(a, b, fmt, out_dtype)
    if mesh is None:
        mesh = context_lib.current_context().mesh
    if mesh is None:
        from repro.launch import mesh as mesh_lib  # deferred: device init

        mesh = mesh_lib.make_matmul_mesh(axis=axis)
    if axis not in mesh.shape:
        if len(mesh.shape) == 1:
            # a 1-D mesh under any axis name IS a matmul mesh: use its axis
            # rather than silently degrading to single-device compute
            axis = next(iter(mesh.shape))
        else:
            raise ValueError(
                f"sharded backend needs a 1-D mesh or an axis named "
                f"{axis!r}; the configured mesh has axes "
                f"{tuple(mesh.shape)}")
    if mesh.shape[axis] == 1:
        return _run_ref(a, b, fmt, out_dtype)
    lead = a.shape[:-1]
    out = _sharded_2d(a.reshape(-1, a.shape[-1]), b, fmt, out_dtype,
                      mesh, axis)
    return out.reshape(tuple(lead) + (b.shape[-1],))


_REGISTRY: Dict[str, Callable] = {
    "ref": lambda a, b, fmt, out_dtype: _run_ref(a, b, fmt, out_dtype),
    "pallas": lambda a, b, fmt, out_dtype: _run_pallas(
        a, b, fmt, out_dtype, backend="pallas"),
    "pallas_interpret": lambda a, b, fmt, out_dtype: _run_pallas(
        a, b, fmt, out_dtype, backend="pallas_interpret"),
    "sharded": lambda a, b, fmt, out_dtype: _run_sharded(
        a, b, fmt, out_dtype),
}


def register_backend(name: str, fn: Callable) -> None:
    """Extension point: fn(a, b, fmt: MPFormat, out_dtype) -> (..., M, N).

    Built-in names are reserved — overwriting "ref" would silently reroute
    every oracle comparison in the process with no way back."""
    if name in BACKENDS:
        raise ValueError(f"cannot override built-in backend {name!r}")
    _REGISTRY[name] = fn


def unregister_backend(name: str) -> None:
    if name in BACKENDS:
        raise ValueError(f"cannot unregister built-in backend {name!r}")
    _REGISTRY.pop(name, None)


def pin_backend(fn: Callable, backend: Optional[str]) -> Callable:
    """Wrap ``fn`` so its (re)traces run under ``mp.context(backend=...)``.

    The backend is read at *trace* time, so the context must be live while
    tracing — wrapping the jit-decorated callable's body (this) works;
    wrapping the ``jax.jit(...)`` construction does not.  ``backend`` of
    None/"" returns ``fn`` unchanged."""
    if not backend:
        return fn

    def wrapped(*args, **kwargs):
        with context_lib.context(backend=backend):
            return fn(*args, **kwargs)

    return wrapped


def available_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def dispatch(
    a: Operand,
    b: Operand,
    mode: FormatLike,
    *,
    backend: Optional[str] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Route one static-format matmul to a backend (the single funnel every
    forward/backward limb contraction passes through).  ``mode`` may be an
    MPFormat, a registered format name, or a legacy PrecisionMode."""
    name = backend or context_lib.current_context().backend
    fn = _REGISTRY.get(name)
    if fn is None:
        raise ValueError(f"unknown backend {name!r}; have {available_backends()}")
    return fn(a, b, resolve(mode), out_dtype)


# ---------------------------------------------------------------------------
# fused multi-output projections (operand-shared A)
# ---------------------------------------------------------------------------
def _tuned_blocks_fused(x, ws, fmt: MPFormat, interpret: bool,
                        gate: str, has_bias: bool, has_res: bool):
    """Autotune-table lookup for the multi-output fused-projection kernel.

    Mirrors the ops layer's shape handling: equal-width weights stack
    (n_out > 1), unequal widths concatenate along N (n_out = 1, N = ΣN)."""
    from repro.kernels import mp_matmul as kern  # deferred: imports pallas
    from repro.kernels import autotune

    M = 1
    for d in x.shape[:-1]:
        M *= d
    K = x.shape[-1]
    Ns = [w.shape[-1] for w in ws]
    if len(set(Ns)) == 1 and len(ws) > 1:
        n_out, N = len(ws), Ns[0]
    else:
        n_out, N = 1, sum(Ns)
    desc = kern.epilogue_desc(gate, has_bias, has_res)
    if context_lib.autotune_enabled():
        return autotune.autotune(M, K, N, fmt, dtype=jnp.float32,
                                 interpret=interpret, n_out=n_out,
                                 epilogue=desc)
    blocks = autotune.lookup(M, K, N, fmt, n_out=n_out, epilogue=desc)
    return blocks if blocks is not None else (None, None, None)


def dispatch_fused(
    x: jax.Array,
    ws,
    mode: FormatLike,
    *,
    gate: str = "none",
    biases=None,
    residual=None,
    backend: Optional[str] = None,
    out_dtype=jnp.float32,
):
    """Route one fused projection group (one A operand, ``n_out`` weights,
    epilogue lattice) to a backend.

    ref/sharded run the XLA realization that still shares the one-time A limb
    decomposition (``kernels/ref.mp_fused_proj_ref``); pallas variants run
    the multi-output kernel.  Backends registered via
    :func:`register_backend` see per-branch ``dispatch`` calls with the
    epilogue applied outside (they only advertise the binary contract).
    """
    name = backend or context_lib.current_context().backend
    fmt = resolve(mode)
    ws = tuple(ws)
    if name in ("ref", "sharded"):
        # sharded: K-sharding each branch would psum n_out× per group; the
        # XLA path shares the A decomposition and lets GSPMD place the
        # collectives — the fused win without bespoke shard_map plumbing.
        return ref_backend.mp_fused_proj_ref(
            x, ws, fmt, gate=gate, biases=biases, residual=residual,
            out_dtype=out_dtype)
    if name in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as pallas_backend  # deferred: pallas

        interpret = kernel_interpret(name)
        bm, bk, bn = _tuned_blocks_fused(
            x, ws, fmt, interpret, gate, biases is not None,
            residual is not None)
        return pallas_backend.mp_fused_proj_pallas(
            x, ws, fmt, gate=gate, biases=biases, residual=residual,
            out_dtype=out_dtype, interpret=interpret, bm=bm, bk=bk, bn=bn)
    if name not in _REGISTRY:
        raise ValueError(f"unknown backend {name!r}; have {available_backends()}")
    raws = [dispatch(x, w, fmt, backend=name, out_dtype=jnp.float32)
            for w in ws]
    return ref_backend.apply_epilogue(raws, gate=gate, biases=biases,
                                      residual=residual, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# fused multi-precision attention (QK^T and P·V at independent formats)
# ---------------------------------------------------------------------------
def _attn_blocks(B_H: int, S: int, T: int, Dh: int, fmt_qk: MPFormat,
                 fmt_pv: MPFormat, causal: bool, interpret: bool):
    """Autotune-table lookup for the fused flash-attention kernel — same
    discipline as :func:`_tuned_blocks`: sweep only when the context's
    autotune flag is set, otherwise a pure table read."""
    from repro.kernels import autotune

    if context_lib.autotune_enabled():
        return autotune.autotune_attention(
            B_H, S, T, Dh, fmt_qk, fmt_pv, causal=causal,
            interpret=interpret)
    blocks = autotune.lookup_attention(B_H, S, T, Dh, fmt_qk, fmt_pv,
                                       causal=causal)
    return blocks if blocks is not None else (None, None)


def dispatch_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mode_qk: FormatLike,
    mode_pv: Optional[FormatLike] = None,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    backend: Optional[str] = None,
    out_dtype=jnp.float32,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jax.Array:
    """Route one fused attention call (q (B, S, H, Dh), k/v (B, T, H, Dh)
    with H already GQA-repeated) to a backend.

    pallas / pallas_interpret run the flash kernel (kernels/mp_attention.py,
    block sizes from the autotune table).  sharded runs decode shapes
    (S == 1) sequence-parallel over the cache dim
    (dist/attention.sp_decode_attention); its prefill/training shapes — and
    every other backend: ref (K-sharding the head-dim contraction cannot
    help) and registered extension backends (which only advertise the binary
    matmul contract) — run the blocked jnp oracle, which shares the kernel's
    online-softmax core.  Sequence-parallel *training* shapes never reach
    this route: models/attention.py keeps them on the chunk-scan path."""
    name = backend or context_lib.current_context().backend
    fmt_qk = resolve(mode_qk)
    fmt_pv = resolve(mode_pv if mode_pv is not None else mode_qk)
    if name in ("pallas", "pallas_interpret"):
        from repro.kernels import mp_attention as attn_kernels

        interpret = kernel_interpret(name)
        B, S, H, Dh = q.shape
        bq, bkv = block_q, block_kv
        if bq is None and bkv is None:
            bq, bkv = _attn_blocks(B * H, S, k.shape[1], Dh, fmt_qk, fmt_pv,
                                   causal, interpret)
        return attn_kernels.mp_attention_pallas(
            q, k, v, fmt_qk, fmt_pv, causal=causal, scale=scale,
            q_offset=q_offset, out_dtype=out_dtype, interpret=interpret,
            block_q=bq, block_kv=bkv)
    if name not in _REGISTRY:
        raise ValueError(f"unknown backend {name!r}; have {available_backends()}")
    if name == "sharded" and q.shape[1] == 1 \
            and not (is_auto(fmt_qk) or is_auto(fmt_pv)) \
            and not _bound_axis_names():
        from repro.dist import attention as dist_attn  # lazy: imports us back

        # decode shape (S == 1): one query row against the cache prefix is
        # exactly the sequence-parallel decode contraction — a causal step
        # at q_offset sees positions [0, q_offset], a non-causal probe sees
        # all T.  (Prefill/training shapes stay on the oracle below:
        # models/attention.py keeps sequence-parallel training on the
        # chunk-scan path.)
        T = k.shape[1]
        ln = min(q_offset + 1, T) if causal else T
        out = dist_attn.sp_decode_attention(
            q, k, v, jnp.int32(ln), fmt_qk, fmt_pv, scale=scale)
        return out.astype(out_dtype)
    return ref_backend.mp_attention_ref(
        q, k, v, fmt_qk, fmt_pv, causal=causal, scale=scale,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        out_dtype=out_dtype)


def masked_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths,
    mode_qk: FormatLike,
    mode_pv: Optional[FormatLike] = None,
    *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Policy-obeying decode-attention einsum path: q (B, 1, H, Dh) against
    k/v (B, T, H, Dh) (H already repeated), masked by ``lengths`` (scalar or
    per-slot (B,)).  Both contractions route through ``mp_matmul`` at the
    resolved ``attn_qk`` / ``attn_pv`` formats — including AUTO — so the
    docstring claim "both attention einsums run through mp_matmul" holds on
    every backend; the ops stay plain batched matmuls, so GSPMD can still
    shard the cache sequence dim (sequence-parallel decode) exactly like the
    v1 einsums.  q is scaled *before* the contraction so the limb cascade
    decomposes the same operand the fused kernels do.

    The sharded backend gets a real multi-device realization: the cache
    sequence dim is the contraction of both einsums, so K-sharding them
    *jointly* — sequence-parallel decode with an online-softmax combine
    (dist/attention.py) — is the layout that helps; sharding each einsum
    independently cannot (the softmax between them needs full rows)."""
    from repro.core.mpmatmul import (  # lazy: mpmatmul imports us
        mp_einsum_qk,
        mp_matmul,
    )

    name = backend or context_lib.current_context().backend
    if name == "sharded" and not _bound_axis_names() \
            and not (is_auto(mode_qk)
                     or is_auto(mode_pv if mode_pv is not None else mode_qk)):
        from repro.dist import attention as dist_attn  # lazy: imports us back

        # falls back to this function (backend="ref") on a 1-device mesh
        return dist_attn.sp_decode_attention(
            q, k, v, lengths, mode_qk, mode_pv, scale=scale)

    B, S1, H, Dh = q.shape
    T = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))
    mode_pv = mode_pv if mode_pv is not None else mode_qk
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32) * scale  # (B, H, 1, Dh)
    kh = k.transpose(0, 2, 1, 3).astype(jnp.float32)          # (B, H, T, Dh)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    logits = mp_einsum_qk(qh, kh, mode_qk, backend=backend)    # (B, H, 1, T)
    ln = lengths.reshape(-1, 1, 1, 1) if getattr(lengths, "ndim", 0) \
        else lengths
    mask = jnp.arange(T)[None, None, None, :] < ln
    logits = jnp.where(mask, logits, ref_backend.ATTN_NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    # re-zero masked probabilities: bit-identical for rows with any valid
    # position (their masked entries already underflowed to exact 0), and
    # fully-masked rows (length-0 inactive slots) flush exact zeros instead
    # of a mean over trash — matching the paged kernel's invariant
    p = jnp.where(mask, p, 0.0)
    out = mp_matmul(p, vh, mode_pv, backend=backend)           # (B, H, 1, Dh)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# partitioned-lane mixed-format decode (one launch, per-slot formats)
# ---------------------------------------------------------------------------
def _lane_cols(lane, ndim: int) -> jax.Array:
    """Reshape a per-slot (B,) lane array to broadcast over a (B, ..., N)
    operand — lane values apply row-wise (every position/head of a slot
    shares that slot's format)."""
    lane = jnp.asarray(lane, jnp.int32).reshape(-1)
    return lane.reshape((lane.shape[0],) + (1,) * (ndim - 1))


def dispatch_mixed_matmul(
    a: jax.Array,
    b: Operand,
    env: FormatLike,
    lane_n: jax.Array,
    lane_ord: jax.Array,
    *,
    backend: Optional[str] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Route one partitioned-lane matmul: ``a`` (B, ..., K) whose slots run
    at per-lane ``(n_limbs, max_order)`` ≤ the static ``env`` envelope,
    against one 2-D weight (raw or pre-limbed).  ``lane_n`` / ``lane_ord``
    are per-slot (B,) int32 traced arrays.

    pallas backends run the lane-masked pre-limbed kernel
    (``ops.mp_mixed_matmul_pallas``); every other backend runs the masked
    ref oracle.  Both realizations share ``kernels/ref.lane_keep`` and the
    per-lane accumulation-discipline select, so the kept product set is
    defined exactly once.  Inference-only (decode never differentiates).
    """
    name = backend or context_lib.current_context().backend
    env = resolve(env)
    if name in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as pallas_backend  # deferred: pallas

        interpret = kernel_interpret(name)
        return pallas_backend.mp_mixed_matmul_pallas(
            a, b, env, lane_n, lane_ord, out_dtype=out_dtype,
            interpret=interpret)
    # ref / sharded / extension backends: the masked oracle.  (sharded: a
    # decode micro-batch's M dim is a handful of rows; K-sharding the
    # lane-masked cascade would pay a per-order psum for no MXU win, so the
    # mixed path makes the same local-compute call the homogeneous decode
    # projections do.)
    return ref_backend.masked_matmul_ref(
        a, b, env, _lane_cols(lane_n, a.ndim), _lane_cols(lane_ord, a.ndim),
        out_dtype=out_dtype)


def mixed_fused_proj(
    x: jax.Array,
    ws,
    env: FormatLike,
    lane_n: jax.Array,
    lane_ord: jax.Array,
    *,
    epilogue: str = "none",
    biases=None,
    residual=None,
    backend: Optional[str] = None,
    out_dtype=jnp.float32,
):
    """Partitioned-lane projection group: per-branch mixed matmuls plus the
    shared epilogue — the lane analogue of ``mpmatmul._sequential_fused``.
    Decode projections hit pre-limbed weights, so per-branch calls ARE the
    homogeneous decode discipline already (no A-sharing kernel to mirror);
    the epilogue math is byte-for-byte the homogeneous helper."""
    raws = [dispatch_mixed_matmul(x, w, env, lane_n, lane_ord,
                                  backend=backend, out_dtype=jnp.float32)
            for w in ws]
    return ref_backend.apply_epilogue(raws, gate=epilogue, biases=biases,
                                      residual=residual, out_dtype=out_dtype)


def mixed_masked_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths,
    env_qk: FormatLike,
    env_pv: FormatLike,
    lane_qk_n: jax.Array,
    lane_qk_ord: jax.Array,
    lane_pv_n: jax.Array,
    lane_pv_ord: jax.Array,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Lane-masked realization of :func:`masked_decode_attention`: q
    (B, 1, H, Dh) against k/v (B, T, H, Dh) (H already repeated), each slot
    running both attention einsums at its own format under the static
    envelopes.  Same mask/softmax/re-zero bookkeeping as the homogeneous
    path; the contractions go through the masked ref helpers so the kept
    product set matches the Pallas mixed paged kernel limb for limb."""
    B, S1, H, Dh = q.shape
    T = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32) * scale  # (B, H, 1, Dh)
    kh = k.transpose(0, 2, 1, 3).astype(jnp.float32)          # (B, H, T, Dh)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    # QK through masked_matmul_ref on the PRE-transposed k — mirroring the
    # homogeneous path's mp_einsum_qk (decompose-after-swapaxes), because
    # XLA's contraction order differs at the ulp between A@B and A@Bᵀ
    # layouts; the NT-form helper (masked_attn_qk_logits) is for the Pallas
    # kernels, whose homogeneous twin uses the NT form on VMEM tiles
    logits = ref_backend.masked_matmul_ref(
        qh, jnp.swapaxes(kh, -1, -2), resolve(env_qk),
        _lane_cols(lane_qk_n, 4), _lane_cols(lane_qk_ord, 4))  # (B, H, 1, T)
    ln = lengths.reshape(-1, 1, 1, 1) if getattr(lengths, "ndim", 0) \
        else lengths
    mask = jnp.arange(T)[None, None, None, :] < ln
    logits = jnp.where(mask, logits, ref_backend.ATTN_NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = ref_backend.masked_attn_pv(
        p, vh, resolve(env_pv), _lane_cols(lane_pv_n, 4),
        _lane_cols(lane_pv_ord, 4))                            # (B, H, 1, Dh)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def dispatch_mixed_paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    env_qk: FormatLike,
    env_pv: FormatLike,
    lane_qk_n: jax.Array,
    lane_qk_ord: jax.Array,
    lane_pv_n: jax.Array,
    lane_pv_ord: jax.Array,
    *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Route one partitioned-lane paged-decode attention step: q
    (B, 1, H, Dh) against the block pool through per-slot block tables,
    with per-slot QK / PV formats under the static envelopes.

    pallas / pallas_interpret run the mixed paged kernel — the lane table
    rides the scalar-prefetch channel next to the block table, so one
    launch serves every format in the batch.  Every other backend falls
    back to the bounded gather + lane-masked einsum path.  AUTO never
    reaches here: ``lanes.lanes_eligible`` keeps AUTO policies on the
    per-policy bucket path."""
    name = backend or context_lib.current_context().backend
    B, S1, H, Dh = q.shape
    n_blocks, bs, hk, _ = k_pool.shape
    n_rep = H // hk
    if name in ("pallas", "pallas_interpret"):
        from repro.kernels import mp_attention as attn_kernels

        interpret = kernel_interpret(name)
        out = attn_kernels.mp_mixed_paged_attention_pallas(
            q.reshape(B, H, Dh), k_pool, v_pool, block_table, lengths,
            env_qk, env_pv, lane_qk_n, lane_qk_ord, lane_pv_n, lane_pv_ord,
            scale=scale, interpret=interpret)
        return out.reshape(B, S1, H, Dh).astype(q.dtype)
    W = block_table.shape[1]
    kk = k_pool[block_table].reshape(B, W * bs, hk, Dh)
    vv = v_pool[block_table].reshape(B, W * bs, hk, Dh)
    if n_rep > 1:
        kk = jnp.repeat(kk, n_rep, axis=2)
        vv = jnp.repeat(vv, n_rep, axis=2)
    return mixed_masked_decode_attention(
        q, kk, vv, lengths, env_qk, env_pv, lane_qk_n, lane_qk_ord,
        lane_pv_n, lane_pv_ord, scale=scale)


def dispatch_paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    mode_qk: FormatLike,
    mode_pv: Optional[FormatLike] = None,
    *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Route one paged-decode attention step: q (B, 1, H, Dh) against the
    block pool (n_blocks, bs, Hkv, Dh) through the slot block tables.

    pallas / pallas_interpret run the paged flash kernel — K/V blocks are
    DMA'd through the scalar-prefetched block table, so the contiguous
    ``pool[table]`` gather never materializes in HBM.  Every other backend
    falls back to the gather + policy-obeying einsum path (the gather is
    bounded by the table width the scheduler passes, sliced to the bucket's
    used-block count); under the sharded backend that einsum path runs
    sequence-parallel across the mesh (masked_decode_attention routes to
    dist/attention.sp_decode_attention), so a fleet decode engine can span
    devices.  AUTO formats analyze raw operand values, so they always take
    the single-device einsum fallback."""
    name = backend or context_lib.current_context().backend
    B, S1, H, Dh = q.shape
    n_blocks, bs, hk, _ = k_pool.shape
    n_rep = H // hk
    is_auto_fmt = is_auto(mode_qk) or is_auto(
        mode_pv if mode_pv is not None else mode_qk)
    if name in ("pallas", "pallas_interpret") and not is_auto_fmt:
        from repro.kernels import mp_attention as attn_kernels

        interpret = kernel_interpret(name)
        out = attn_kernels.mp_paged_attention_pallas(
            q.reshape(B, H, Dh), k_pool, v_pool, block_table, lengths,
            mode_qk, mode_pv, scale=scale, interpret=interpret)
        return out.reshape(B, S1, H, Dh).astype(q.dtype)
    W = block_table.shape[1]
    kk = k_pool[block_table].reshape(B, W * bs, hk, Dh)
    vv = v_pool[block_table].reshape(B, W * bs, hk, Dh)
    if n_rep > 1:
        kk = jnp.repeat(kk, n_rep, axis=2)
        vv = jnp.repeat(vv, n_rep, axis=2)
    return masked_decode_attention(q, kk, vv, lengths, mode_qk, mode_pv,
                                   scale=scale, backend=name)


def masked_latent_attention(
    q_lat: jax.Array,
    q_rope: jax.Array,
    c_kv: jax.Array,
    k_rope: jax.Array,
    lengths,
    mode_qk: FormatLike,
    mode_pv: Optional[FormatLike] = None,
    *,
    scale: float,
    backend: Optional[str] = None,
) -> jax.Array:
    """Latent attention (MLA's absorbed decode) as policy-obeying einsums:
    q_lat (B, H, Dc) and q_rope (B, H, Dr) against the latent cache c_kv
    (B, T, Dc) and rope keys k_rope (B, T, Dr), masked by ``lengths``
    (scalar or (B,)).  Logits ``scale · (q_lat · c + q_rope · r)`` at the
    ``attn_qk`` format (queries scaled before the contraction, as the
    kernels do), context ``p · c`` at ``attn_pv``.  Returns (B, H, Dc)."""
    from repro.core.mpmatmul import mp_matmul  # lazy: mpmatmul imports us

    mode_pv = mode_pv if mode_pv is not None else mode_qk
    T = c_kv.shape[1]
    c = c_kv.astype(jnp.float32)
    logits = (mp_matmul(q_lat.astype(jnp.float32) * scale,
                        jnp.swapaxes(c, -1, -2), mode_qk, backend=backend)
              + mp_matmul(q_rope.astype(jnp.float32) * scale,
                          jnp.swapaxes(k_rope.astype(jnp.float32), -1, -2),
                          mode_qk, backend=backend))          # (B, H, T)
    ln = lengths.reshape(-1, 1, 1) if getattr(lengths, "ndim", 0) \
        else lengths
    mask = jnp.arange(T)[None, None, :] < ln
    logits = jnp.where(mask, logits, ref_backend.ATTN_NEG_INF)
    p = jnp.where(mask, jax.nn.softmax(logits, axis=-1), 0.0)
    return mp_matmul(p, c, mode_pv, backend=backend)


def dispatch_latent_paged_attention(
    q_lat: jax.Array,
    q_rope: jax.Array,
    c_pool: jax.Array,
    r_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    mode_qk: FormatLike,
    mode_pv: Optional[FormatLike] = None,
    *,
    scale: float,
    backend: Optional[str] = None,
) -> jax.Array:
    """Route one paged latent-attention step: q_lat (B, H, Dc), q_rope (B,
    H, Dr) against the latent pool c_pool (n_blocks, bs, Dc) / r_pool
    (n_blocks, bs, Dr) through the slot block tables -> context (B, H, Dc).

    pallas / pallas_interpret run the latent paged kernel (each pool block
    DMA'd once for every head); other backends, and AUTO formats, gather
    the table's columns and run :func:`masked_latent_attention`."""
    name = backend or context_lib.current_context().backend
    if name in ("pallas", "pallas_interpret") and not (
            is_auto(mode_qk) or is_auto(mode_pv if mode_pv is not None
                                        else mode_qk)):
        from repro.kernels import mp_attention as attn_kernels

        return attn_kernels.mp_latent_paged_attention_pallas(
            q_lat, q_rope, c_pool, r_pool, block_table, lengths, mode_qk,
            mode_pv, scale=scale, interpret=kernel_interpret(name))
    B = block_table.shape[0]
    c = c_pool[block_table].reshape((B, -1) + c_pool.shape[2:])
    r = r_pool[block_table].reshape((B, -1) + r_pool.shape[2:])
    return masked_latent_attention(q_lat, q_rope, c, r, lengths, mode_qk,
                                   mode_pv, scale=scale, backend=name)
