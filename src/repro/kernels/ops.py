"""jit'd wrappers around the Pallas multi-precision matmul kernels.

Handles: shape padding to block multiples, leading-batch flattening/vmap,
block-size selection, DD operands (pre-limbed path), and the CPU interpret
switch so the same call sites run on TPU (compiled) and CPU (validated).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import limbs as limbs_lib
from repro.core.limbs import DD, PrelimbedWeight
from repro.core.formats import FormatLike, resolve
from repro.kernels import mp_matmul as kern

Operand = Union[jax.Array, DD, PrelimbedWeight]

# default TPU-aligned tile sizes (fp32: multiples of (8,128); MXU: 128)
DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_blocks(M: int, K: int, N: int,
                 bm: Optional[int], bk: Optional[int], bn: Optional[int]
                 ) -> Tuple[int, int, int]:
    """Clamp default blocks to the (padded) problem, keeping TPU alignment."""
    bm = bm or min(DEFAULT_BM, _round_up(M, 8))
    bn = bn or min(DEFAULT_BN, _round_up(N, 128))
    bk = bk or min(DEFAULT_BK, _round_up(K, 128))
    return bm, bk, bn


def _clamp_vmem(mode, bm: int, bk: int, bn: int, out_dtype, *,
                n_out: int = 1, variant: str = "fused",
                epilogue: str = "none") -> Tuple[int, int, int]:
    """Shrink blocks until the *variant's* true VMEM footprint fits the
    autotune budget (kernels.mp_matmul.vmem_bytes) — the feasibility filter
    for paths that pick blocks without a sweep (prelimbed serving kernels,
    DD operands, untuned fused groups).  Tuned blocks already fit, so this
    is a no-op for them; bk halves first (K steps are free reloads), then
    bm, preserving (8, 128) tile alignment."""
    from repro.kernels import autotune  # deferred: autotune imports ops

    budget = autotune.VMEM_BUDGET_BYTES

    def fits(bm_, bk_, bn_):
        return kern.vmem_bytes(mode, bm_, bk_, bn_, out_dtype, n_out=n_out,
                               variant=variant, epilogue=epilogue) <= budget

    while not fits(bm, bk, bn) and bk > 128:
        bk = max(128, bk // 2)
    while not fits(bm, bk, bn) and bm > 8:
        bm = max(8, bm // 2)
    return bm, bk, bn


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[-2], cols - x.shape[-1]
    if pr == 0 and pc == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)]
    return jnp.pad(x, pad)


def _matmul2d(a: jax.Array, b: jax.Array, mode: FormatLike, out_dtype,
              interpret: bool, bm, bk, bn) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bk, bn = _pick_blocks(M, K, N, bm, bk, bn)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    ap = _pad2(a, Mp, Kp)
    bp = _pad2(b, Kp, Np)
    call = kern.build_fused_call(
        Mp, Kp, Np, mode, bm=bm, bk=bk, bn=bn, out_dtype=out_dtype,
        interpret=interpret,
    )
    out = call(ap, bp)
    return out[:M, :N]


def _matmul2d_dd(a: Operand, b: Operand, mode: FormatLike, out_dtype,
                 interpret: bool, bm, bk, bn) -> jax.Array:
    """DD-capable path: pre-limb both operands outside the kernel."""
    s = resolve(mode)
    al = (limbs_lib.decompose_dd(a, s.n_limbs) if isinstance(a, DD)
          else limbs_lib.decompose(a, s.n_limbs))
    bl = (limbs_lib.decompose_dd(b, s.n_limbs) if isinstance(b, DD)
          else limbs_lib.decompose(b, s.n_limbs))
    M, K = al.shape[1:]
    K2, N = bl.shape[1:]
    assert K == K2
    bm, bk, bn = _pick_blocks(M, K, N, bm, bk, bn)
    bm, bk, bn = _clamp_vmem(mode, bm, bk, bn, out_dtype,
                             variant="prelimbed_both")
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    al = jnp.pad(al, [(0, 0), (0, Mp - M), (0, Kp - K)])
    bl = jnp.pad(bl, [(0, 0), (0, Kp - K), (0, Np - N)])
    call = kern.build_prelimbed_call(
        Mp, Kp, Np, mode, bm=bm, bk=bk, bn=bn, out_dtype=out_dtype,
        interpret=interpret, both=True,
    )
    return call(al, bl)[:M, :N]


def mp_matmul_pallas(
    a: Operand,
    b: Operand,
    mode: FormatLike = "M16",
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
) -> jax.Array:
    """Pallas-backed mp_matmul: a (..., M, K) @ b (..., K, N) -> (..., M, N).

    Leading batch dims are handled by flattening (when only ``a`` is batched,
    the batch folds into M — one big matmul, best MXU utilization) or vmap
    (when both are batched)."""
    mode = resolve(mode)
    if isinstance(b, PrelimbedWeight) and not isinstance(a, (DD, PrelimbedWeight)):
        assert b.ndim == 2, "prelimbed weights must be 2-D per matmul"
        return mp_matmul_prelimbed_weights(
            a, b, mode, out_dtype=out_dtype, interpret=interpret,
            bm=bm, bk=bk, bn=bn)
    if isinstance(a, DD) or isinstance(b, DD):
        assert (a.hi.ndim if isinstance(a, DD) else a.ndim) == 2, (
            "DD path supports 2D operands")
        return _matmul2d_dd(a, b, mode, out_dtype, interpret, bm, bk, bn)

    f = functools.partial(
        _matmul2d, mode=mode, out_dtype=out_dtype, interpret=interpret,
        bm=bm, bk=bk, bn=bn,
    )
    if a.ndim == 2 and b.ndim == 2:
        return f(a, b)
    if b.ndim == 2:
        lead = a.shape[:-1]
        out = f(a.reshape(-1, a.shape[-1]), b)
        return out.reshape(lead + (b.shape[-1],))
    # both batched: broadcast leading dims, then vmap the 2D kernel
    lead = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a = jnp.broadcast_to(a, lead + a.shape[-2:]).reshape((-1,) + a.shape[-2:])
    b = jnp.broadcast_to(b, lead + b.shape[-2:]).reshape((-1,) + b.shape[-2:])
    out = jax.vmap(f)(a, b)
    return out.reshape(lead + out.shape[-2:])


def mp_fused_proj_pallas(
    x: jax.Array,
    ws,
    mode: FormatLike = "M16",
    *,
    gate: str = "none",
    biases=None,
    residual=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
):
    """Pallas-backed fused projection: x (..., K) against n_out weights.

    Equal-width weights run the multi-output kernel, each weight streaming
    as its OWN pallas operand (no host-side (n_out, K, N) stack copy).
    Unequal widths (GQA: wq wider than wk/wv) concatenate along N into ONE
    wide contraction — the A tile and its limbs are still read/extracted
    once — and the outputs are sliced back apart; only valid when no gate
    combine is requested (gate outputs must pair same-shaped operands, which
    always holds for SwiGLU gate/up).
    """
    mode = resolve(mode)
    ws = tuple(ws)
    Ns = [w.shape[-1] for w in ws]
    K = x.shape[-1]
    lead = x.shape[:-1]
    a = x.reshape(-1, K).astype(jnp.float32)
    M = a.shape[0]

    has_bias = biases is not None
    if has_bias:
        biases = tuple(b.astype(jnp.float32) for b in biases)
    has_res = residual is not None

    if len(set(Ns)) == 1:
        N = Ns[0]
        ws_eff = tuple(w.astype(jnp.float32) for w in ws)
        splits = None
    else:
        if gate != "none":
            raise ValueError("gate combine needs equal-width weights")
        N = sum(Ns)
        ws_eff = (jnp.concatenate([w.astype(jnp.float32) for w in ws],
                                  axis=-1),)                 # (K, ΣN)
        if has_bias:
            biases = (jnp.concatenate(biases, axis=-1),)
        splits = np.cumsum(Ns)[:-1]
    n_out = len(ws_eff)
    single_out = gate != "none" or (n_out == 1 and splits is None)

    desc = kern.epilogue_desc(gate, has_bias, has_res)
    bm_, bk_, bn_ = _pick_blocks(M, K, N, bm, bk, bn)
    bm_, bk_, bn_ = _clamp_vmem(mode, bm_, bk_, bn_, out_dtype,
                                n_out=n_out, epilogue=desc)
    Mp, Kp, Np = _round_up(M, bm_), _round_up(K, bk_), _round_up(N, bn_)
    operands = [_pad2(a, Mp, Kp)]
    operands += [_pad2(w, Kp, Np) for w in ws_eff]
    if has_bias:
        operands += [_pad2(b.reshape(1, N), 1, Np) for b in biases]
    if has_res:
        operands.append(_pad2(residual.reshape(-1, N).astype(jnp.float32),
                              Mp, Np))
    call = kern.build_fused_multi_call(
        Mp, Kp, Np, n_out, mode, bm=bm_, bk=bk_, bn=bn_, gate=gate,
        has_bias=has_bias, has_residual=has_res, out_dtype=out_dtype,
        interpret=interpret,
    )
    out = call(*operands)
    if gate != "none":
        return out[:M, :N].reshape(lead + (N,))
    out = out[:, :M, :N]
    if splits is not None:
        parts = jnp.split(out[0], splits, axis=-1)
        return tuple(p.reshape(lead + (p.shape[-1],)) for p in parts)
    if single_out:  # n_out == 1
        return out[0].reshape(lead + (N,))
    return tuple(out[t].reshape(lead + (N,)) for t in range(n_out))


def prelimbed_storage_shape(K: int, N: int) -> Tuple[int, int]:
    """The (K', N') a prelimbed (K, N) weight is stored at: the multiples
    of the wrapper's default K and N blocks, which do not depend on M or
    the mode (``_clamp_vmem`` only halves bk, to a divisor of K').  A limb
    stack stored at this shape reaches the kernel without a pad."""
    _, bk, bn = _pick_blocks(8, K, N, None, None, None)
    return _round_up(K, bk), _round_up(N, bn)


def _weight_limbs(w: Union[jax.Array, PrelimbedWeight], n_limbs: int
                  ) -> Tuple[jax.Array, int, int]:
    """A prelimbed weight's stored stack at ``n_limbs`` limbs (missing
    limbs zero, extra ones dropped) and its logical (K, N).  A bare
    (L, K, N) limb array is its own logical shape."""
    if isinstance(w, PrelimbedWeight):
        assert w.ndim == 2, "prelimbed weights must be 2-D per matmul"
        w_limbs, (K, N) = w.limbs, w.kn
    else:
        w_limbs, (K, N) = w, w.shape[1:]
    if w_limbs.shape[0] < n_limbs:
        w_limbs = jnp.concatenate([
            w_limbs,
            jnp.zeros((n_limbs - w_limbs.shape[0],) + w_limbs.shape[1:],
                      jnp.bfloat16)], axis=0)
    return w_limbs[:n_limbs], K, N


def mp_matmul_prelimbed_weights(
    x: jax.Array,
    w: Union[jax.Array, PrelimbedWeight],
    mode: FormatLike,
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
) -> jax.Array:
    """Serving fast path: weights decomposed once (``decompose_weights``),
    activations limbed on the fly inside the kernel.  x (..., K) @ W (K, N).

    ``w`` is a :class:`PrelimbedWeight` (its stack may be stored padded,
    ``prelimbed_storage_shape``: then only the activations are padded) or a
    bare (L, K, N) limb stack.  A mode needing more limbs than were stored
    computes at the stored precision: the missing limbs are zero by
    construction."""
    s = resolve(mode)
    w_limbs, K, N = _weight_limbs(w, s.n_limbs)
    lead = x.shape[:-1]
    a = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    M = a.shape[0]
    assert a.shape[1] == K, (a.shape, K)
    _, Ks, Ns = w_limbs.shape
    bm_, bk_, bn_ = _pick_blocks(M, Ks, Ns, bm, bk, bn)
    bm_, bk_, bn_ = _clamp_vmem(mode, bm_, bk_, bn_, out_dtype,
                                variant="prelimbed_b")
    Mp, Kp, Np = _round_up(M, bm_), _round_up(Ks, bk_), _round_up(Ns, bn_)
    a = _pad2(a, Mp, Kp)
    w_limbs = _pad2(w_limbs, Kp, Np)
    call = kern.build_prelimbed_call(
        Mp, Kp, Np, mode, bm=bm_, bk=bk_, bn=bn_, out_dtype=out_dtype,
        interpret=interpret, both=False,
    )
    out = call(a, w_limbs)[:M, :N]
    return out.reshape(lead + (N,))


def mp_mixed_matmul_pallas(
    x: jax.Array,
    w: Operand,
    env: FormatLike,
    lane_n: jax.Array,
    lane_ord: jax.Array,
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
) -> jax.Array:
    """Partitioned-lane matmul: x (..., K) @ W (K, N) with per-row formats.

    ``lane_n``/``lane_ord`` are (M,) int32 over the flattened leading dims
    of ``x`` (the decode micro-batch: x is (B, 1, K), so M == B).  ``env``
    is the batch-max envelope format the kernel is traced at.  ``w`` is a
    :class:`PrelimbedWeight` on the serving path; a raw weight is prelimbed
    on the fly at the envelope depth (same limb values the homogeneous
    kernel extracts in-kernel, so numerics are unchanged).  Blocks are
    selected with the envelope format — mixed and homogeneous launches see
    identical K tilings whenever the problem fits one K block (every
    serving decode shape); larger shapes may reassociate across K tiles
    like any block-size change.
    """
    s = resolve(env)
    if not isinstance(w, PrelimbedWeight):
        assert w.ndim == 2, "mixed matmul weights must be 2-D"
        w = decompose_weights(w.astype(jnp.float32), s.n_limbs,
                              interpret=interpret)
    w_limbs, K, N = _weight_limbs(w, s.n_limbs)
    lead = x.shape[:-1]
    a = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    M = a.shape[0]
    assert a.shape[1] == K, (a.shape, K)
    _, Ks, Ns = w_limbs.shape
    lane_n = jnp.broadcast_to(lane_n.reshape(-1), (M,)).astype(jnp.int32)
    lane_ord = jnp.broadcast_to(lane_ord.reshape(-1), (M,)).astype(jnp.int32)
    bm_, bk_, bn_ = _pick_blocks(M, Ks, Ns, bm, bk, bn)
    bm_, bk_, bn_ = _clamp_vmem(s, bm_, bk_, bn_, out_dtype,
                                variant="prelimbed_b")
    Mp, Kp, Np = _round_up(M, bm_), _round_up(Ks, bk_), _round_up(Ns, bn_)
    a = _pad2(a, Mp, Kp)
    w_limbs = _pad2(w_limbs, Kp, Np)
    # pad rows take the cheapest lane (1 limb, order 0); their outputs are
    # sliced off.  Lane values broadcast across a 128-wide lane dim so the
    # int32 operand tiles on TPU-aligned (·, 128) blocks.
    ln = jnp.concatenate([lane_n, jnp.ones((Mp - M,), jnp.int32)])
    lo = jnp.concatenate([lane_ord, jnp.zeros((Mp - M,), jnp.int32)])
    ln = jnp.broadcast_to(ln[:, None], (Mp, 128))
    lo = jnp.broadcast_to(lo[:, None], (Mp, 128))
    call = kern.build_mixed_prelimbed_call(
        Mp, Kp, Np, s, bm=bm_, bk=bk_, bn=bn_, out_dtype=out_dtype,
        interpret=interpret,
    )
    out = call(a, w_limbs, ln, lo)[:M, :N]
    return out.reshape(lead + (N,))


def decompose_weights(
    w: jax.Array, n_limbs: int, *, interpret: bool = False,
    br: int = 256, bc: int = 256,
) -> jax.Array:
    """Pre-limb a weight matrix with the Pallas decompose kernel."""
    R, C = w.shape
    brc = min(br, _round_up(R, 8))
    bcc = min(bc, _round_up(C, 128))
    Rp, Cp = _round_up(R, brc), _round_up(C, bcc)
    wp = _pad2(w.astype(jnp.float32), Rp, Cp)
    call = kern.build_decompose_call(Rp, Cp, n_limbs, br=brc, bc=bcc,
                                     interpret=interpret)
    return call(wp)[:, :R, :C]
