"""bf16 limb decomposition — the TPU analogue of the paper's operand truncation.

``decompose(x, k)`` splits an fp32 tensor into ``k`` bf16 limbs with
``x ~= sum_i limbs[i]`` where limb ``i`` carries mantissa bits ``[8i, 8(i+1))``.
Rounding the input to ``k`` limbs *is* the paper's "rounding of bits before
multiplication": narrower operands -> fewer MXU passes.

For >24-bit inputs (paper modes 5/6) fp32 cannot even *hold* the operand, so we
support a two-float ("double-double", DD) operand representation ``(hi, lo)``
with ``value = hi + lo`` giving ~49 usable mantissa bits.  ``decompose_dd``
extracts up to 7 limbs from it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class DD(NamedTuple):
    """Two-float operand: value = hi + lo, |lo| <= ulp(hi)/2."""

    hi: jax.Array  # fp32
    lo: jax.Array  # fp32

    @property
    def shape(self):
        return self.hi.shape

    @property
    def dtype(self):
        return self.hi.dtype


@jax.tree_util.register_pytree_node_class
class PrelimbedWeight:
    """A weight operand carried as its pre-extracted bf16 limb stack.

    ``limbs`` has shape (..., L, K', N'): the last three dims are the limb
    stack of one matrix whose logical shape is ``kn`` = (K, N), stored
    zero-padded to (K', N') >= (K, N) (the serving engine stores each
    stack at the kernel's block multiples, so a decode step pads nothing;
    zero limbs add nothing to any sum).  ``kn`` is static pytree metadata,
    not a leaf.  Leading dims, where a stack of per-layer weights was
    prelimbed whole, ride along; the serving engine instead keeps one
    stack per layer (``serve/engine.prelimb_dense_params``), since a scan
    would copy each layer's slice out of the stack on every step.
    Serving decomposes each weight ONCE per (policy, params) — decode steps
    then skip the per-step B-limb VPU cascade entirely (the kernel's
    ``prelimbed_b`` variant).  Inference-only, like :class:`DD`: no VJP
    routes through it.  A mode needing more limbs than stored computes at the
    stored precision (missing limbs are zero).
    """

    __slots__ = ("limbs", "kn")

    def __init__(self, limbs: jax.Array, kn=None):
        self.limbs = limbs  # (..., L, K', N') bf16
        self.kn = tuple(int(d) for d in (limbs.shape[-2:] if kn is None
                                         else kn))

    def tree_flatten(self):
        return (self.limbs,), self.kn

    @classmethod
    def tree_unflatten(cls, kn, children):
        obj = object.__new__(cls)
        obj.limbs, obj.kn = children[0], kn
        return obj

    def __repr__(self):
        return f"PrelimbedWeight(limbs={self.limbs!r}, kn={self.kn})"

    @property
    def shape(self):
        """Shape of the weight *value* the limb stack represents."""
        return tuple(self.limbs.shape[:-3]) + self.kn

    @property
    def ndim(self) -> int:
        return self.limbs.ndim - 1

    @property
    def n_limbs(self) -> int:
        return self.limbs.shape[-3]

    def logical_limbs(self) -> jax.Array:
        """The limb stack cut to the logical (K, N) (no op when unpadded)."""
        if tuple(self.limbs.shape[-2:]) == self.kn:
            return self.limbs
        return self.limbs[..., : self.kn[0], : self.kn[1]]


def prelimb_weight(w: jax.Array, n_limbs: int,
                   padded: Optional[Tuple[int, int]] = None
                   ) -> PrelimbedWeight:
    """Pure-jnp prelimb of a (..., K, N) weight -> (..., n_limbs, K, N)
    (what the serving engine runs once per policy; the Pallas decompose
    kernel, kernels/ops.decompose_weights, gives the same limbs).

    ``padded`` (K', N') stores the stack zero-padded to that shape: the
    weight is padded before it is decomposed, so no unpadded stack is ever
    held beside the padded one."""
    kn = w.shape[-2:]
    if padded is not None and tuple(padded) != tuple(kn):
        pad = [(0, 0)] * (w.ndim - 2) + [(0, padded[0] - kn[0]),
                                         (0, padded[1] - kn[1])]
        w = jnp.pad(w, pad)
    stacked = decompose(w, n_limbs)  # (L, ..., K, N)
    order = tuple(range(1, stacked.ndim - 2)) + (0, stacked.ndim - 2,
                                                 stacked.ndim - 1)
    return PrelimbedWeight(jnp.transpose(stacked, order), kn)


def dd_from_f64(x64: np.ndarray) -> DD:
    """Split a float64 numpy array into a DD pair (host-side helper)."""
    hi = x64.astype(np.float32)
    lo = (x64 - hi.astype(np.float64)).astype(np.float32)
    return DD(jnp.asarray(hi), jnp.asarray(lo))


def dd_to_f64(d: DD) -> np.ndarray:
    return np.asarray(d.hi, dtype=np.float64) + np.asarray(d.lo, dtype=np.float64)


def round_to_bf16(x: jax.Array) -> jax.Array:
    """fp32 -> the nearest bf16 value (round-to-nearest-even), kept in fp32.

    The same value as ``x.astype(bfloat16).astype(float32)``, but computed
    on the bit pattern: XLA on TPU may drop that convert pair inside a
    fusion (excess precision), and then every residual ``x - bf16(x)`` — so
    every limb past the first — comes out zero."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + (((u >> 16) & 1) + 0x7FFF)) & np.uint32(0xFFFF0000)
    return jnp.where(jnp.isnan(x), x,
                     jax.lax.bitcast_convert_type(u, jnp.float32))


def decompose(x: jax.Array, n_limbs: int) -> jax.Array:
    """fp32 -> stacked bf16 limbs, shape (n_limbs, *x.shape).

    Limb extraction is the round-to-nearest truncation cascade:
        l0 = bf16(x); l1 = bf16(x - l0); ...
    Each subtraction is exact in fp32 (the high bits cancel), so the residual
    after limb i is < 2^-8(i+1) relative.  fp32 holds < 25 mantissa bits, so
    limbs beyond 3 are ~0 for fp32 inputs (use DD inputs for modes 5/6).
    """
    x = x.astype(jnp.float32)
    limbs = []
    r = x
    for _ in range(n_limbs):
        hi = round_to_bf16(r)
        limbs.append(hi.astype(jnp.bfloat16))  # exact
        r = r - hi
    return jnp.stack(limbs)


def decompose_dd(x: DD, n_limbs: int) -> jax.Array:
    """DD -> stacked bf16 limbs, shape (n_limbs, *x.shape).

    The low word is folded in once the high word's residual has decayed to its
    magnitude (after 3 limbs ~ 2^-24 relative, matching |lo|).
    """
    limbs = []
    r = x.hi.astype(jnp.float32)
    for i in range(n_limbs):
        hi = round_to_bf16(r)
        limbs.append(hi.astype(jnp.bfloat16))
        r = r - hi
        if i == 2:  # residual of hi has decayed to lo's scale: fold lo in
            r = r + x.lo.astype(jnp.float32)
    return jnp.stack(limbs)


def reconstruct(limbs: jax.Array) -> jax.Array:
    """Sum limbs back to fp32 (ascending magnitude for accuracy)."""
    acc = jnp.zeros(limbs.shape[1:], jnp.float32)
    for i in range(limbs.shape[0] - 1, -1, -1):
        acc = acc + limbs[i].astype(jnp.float32)
    return acc


def round_to_limbs(x: jax.Array, n_limbs: int) -> jax.Array:
    """Round x to an 8*n_limbs-bit mantissa (the paper's pre-multiply rounding)."""
    return reconstruct(decompose(x, n_limbs))


def residual_scale(x: jax.Array, n_limbs: int) -> jax.Array:
    """max|x - round_to_limbs(x)| / max|x| — the tensor-level analogue of the
    paper's 'count zeros after the leading 1' operand analysis.

    Returns a scalar fp32.  0 means the tensor is exactly representable in
    ``n_limbs`` limbs (e.g. small integers in mode M8)."""
    x = x.astype(jnp.float32)
    r = x
    for _ in range(n_limbs):
        r = r - round_to_bf16(r)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), jnp.finfo(jnp.float32).tiny)
    return jnp.max(jnp.abs(r)) / scale


def significant_limbs(
    x: jax.Array, *, tol: float = 2.0**-13, max_limbs: int = 3
) -> jax.Array:
    """Number of limbs needed so the rounding residual is <= tol (relative).

    This is the AUTO-mode operand analyzer: a tensor of small integers (or any
    data with few significant mantissa bits — the paper's 'zeros after the
    leading 1') needs 1 limb; generic fp32 data needs 3.

    Returns an int32 scalar in [1, max_limbs]; traceable (jit/vmap-safe).
    """
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), jnp.finfo(jnp.float32).tiny)
    needed = jnp.int32(1)
    r = x
    for k in range(1, max_limbs):  # after k limbs, is the residual too big?
        r = r - round_to_bf16(r)
        too_big = jnp.max(jnp.abs(r)) > tol * scale
        # if the residual after k limbs is still too big, need at least k+1
        needed = jnp.maximum(needed, jnp.where(too_big, jnp.int32(k + 1), 1))
    return needed


def neumaier_sum(terms: Sequence[jax.Array]) -> jax.Array:
    """Compensated (Neumaier) summation of fp32 terms — the carry-save-adder
    analogue: per-term rounding errors are captured in a compensation register
    and applied once at the end."""
    if len(terms) == 1:
        return terms[0]
    s = terms[0]
    c = jnp.zeros_like(s)
    for t in terms[1:]:
        tmp = s + t
        # branchless Neumaier: compensation picks the larger-magnitude operand
        c = c + jnp.where(
            jnp.abs(s) >= jnp.abs(t), (s - tmp) + t, (t - tmp) + s
        )
        s = tmp
    return s + c
