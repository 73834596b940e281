"""Block-paged KV cache pool — serving memory as a shared free list.

The static serving path allocates one dense ``(B, S_max)`` KV region per
``generate()`` call and throws it away; a continuous-batching scheduler
admits and evicts requests *mid-stream*, so cache memory must be recycled at
a granularity finer than "the whole pool".  This module provides that
granularity: a fixed device-resident pool of fixed-size **blocks**
(``block_size`` token positions each, per layer), a host-side **free list**
that hands blocks to requests and reclaims them on eviction, and per-request
**block tables** mapping logical token positions to physical blocks — the
vLLM paged-attention memory model, sized for this repo's CPU/TPU test scale.

Layout (one pool array per K and V, each with an entry shape of its own):

    k, v: (n_layers, n_blocks, block_size) + entry shape

GQA attention stores ``(n_kv_heads, head_dim)`` in both; latent attention
(MLA) stores one head's latent ``c_kv`` (``(kv_lora,)``) in ``k`` and its
rotated rope key (``(qk_rope,)``) in ``v``.

Logical position ``p`` of a request lives at ``pool[layer, table[p // bs],
p % bs]`` where ``table`` is the request's block-table row.  Block 0 is the
reserved **trash block**: table rows point their unallocated tail (and
whole rows of inactive micro-batch slots) at it, so predicated writes need
no branching — garbage writes land in trash, never in another request's
blocks.  Two invariants make the scheme safe without any in-kernel masking:

  * reads are masked by per-slot ``length`` (positions >= length are never
    read), and
  * every position in ``[prompt_len, length)`` is rewritten by the decode
    step that produced it before any read — so prefill padding garbage in a
    request's own reserved tail is always overwritten before it is visible.

:class:`PagedKVCache` is the *jit-side* view (a pytree: the pool arrays,
block table, per-slot lengths and the layer being run) that the model's
layers thread from one to the next; each layer writes its new rows into the
pool in place and its kernel reads the pool where it lies, through global
block ids (layer ``l``'s block ``b`` is ``l * n_blocks + b``).  This module
has no model dependencies so ``models/attention.py`` can import it freely.
"""
from __future__ import annotations

import threading
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

# physical block 0 is never allocated: it is the write target for everything
# that must go nowhere (inactive slots, padded prefill tails past a request's
# reservation)
TRASH_BLOCK = 0


class PagedKVCache(NamedTuple):
    """Jit-side view of the whole paged pool at one layer.

    ``k``/``v`` hold every layer; ``layer`` is the one the current model
    layer reads and writes.  It addresses the pool through global block
    ids: :meth:`flat` merges the layer and block axes (two major axes, so a
    bitcast, not a copy) and :meth:`global_table` adds the layer's
    ``base`` (``layer * n_blocks``, also its trash block) to the host's
    block ids.  The view copies no pool bytes, so a step that takes the
    pool donated updates it in place.
    """

    k: jax.Array            # (n_layers, n_blocks, block_size) + k entry shape
    v: jax.Array            # (n_layers, n_blocks, block_size) + v entry shape
    block_table: jax.Array  # (B, max_blocks) int32 block ids within a layer
    length: jax.Array       # (B,) int32 valid prefix length per slot
    layer: Union[int, jax.Array] = 0  # pool layer of the current model layer

    @property
    def block_size(self) -> int:
        """Positions per block."""
        return self.k.shape[2]

    @property
    def base(self) -> Union[int, jax.Array]:
        """Global id of the layer's first block: its trash block."""
        return self.layer * self.k.shape[1] + TRASH_BLOCK

    def global_table(self) -> jax.Array:
        """The block table in global ids of the current layer."""
        return self.block_table + self.base

    def flat(self) -> Tuple[jax.Array, jax.Array]:
        """``(k, v)`` indexed by global block id: (n_layers * n_blocks,
        block_size) + entry shape."""
        return tuple(x.reshape((-1,) + x.shape[2:]) for x in (self.k, self.v))


class BlockPoolExhausted(RuntimeError):
    """Raised when an allocation asks for more blocks than the free list has."""


class PagedKVPool:
    """Device block pool + host free-list allocator.

    Each jit step takes the pool arrays donated, writes its new rows into
    them in place and returns them; the arrays handed in are deleted, so
    the caller adopts the returned ones at once (:meth:`update`) and nothing
    may keep a reference to a pool array across a step.  The free list is
    host state guarded by a lock, so fleet engines sharing one pool (a
    disaggregated prefill engine allocating while its decode engine frees
    evicted blocks) never race the accounting.  The device arrays have a
    single-writer discipline: exactly one engine step may be in flight per
    pool at a time (the fleet serializes steps per pool; cross-pool handoff
    copies blocks instead).  Allocation never hands out a block twice: a
    block is either in ``_free``, in ``_live`` (owned by exactly one
    request), or the trash block.
    """

    def __init__(self, n_layers: int, n_blocks: int, block_size: int,
                 k_shape: Sequence[int], v_shape: Sequence[int], *,
                 max_blocks_per_seq: int, dtype=jnp.float32):
        """``k_shape`` / ``v_shape``: each token's entry in one layer —
        ``(n_kv_heads, head_dim)`` for GQA, ``(kv_lora,)`` and
        ``(qk_rope,)`` for a latent pool."""
        if n_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved trash)")
        if block_size < 1 or max_blocks_per_seq < 1:
            raise ValueError("block_size and max_blocks_per_seq must be >= 1")
        self.n_layers = n_layers
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        lead = (n_layers, n_blocks, block_size)
        self.k = jnp.zeros(lead + tuple(k_shape), dtype)
        self.v = jnp.zeros(lead + tuple(v_shape), dtype)
        self._free: List[int] = list(range(1, n_blocks))  # LIFO reuse
        self._live: set = set()
        self._lock = threading.Lock()
        # chaos seam (serve/faults.py): when installed, transfer_blocks asks
        # it whether this transfer's payload lands corrupted — a poisoned
        # cross-cell handoff the decode guardrail must catch.  None in
        # production: the only cost is this attribute check per transfer.
        self.fault_injector = None

    # ---- free-list accounting ---------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return len(self._live)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` positions."""
        return max(1, -(-n_tokens // self.block_size))

    def try_alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` blocks off the free list, all-or-nothing, or return
        None — the graceful admission primitive.  Exhaustion is an expected
        serving condition (admission waits behind eviction reclaim), so the
        scheduler/fleet loops route through this instead of :meth:`alloc`;
        the lock makes check-and-take atomic under concurrent engines."""
        with self._lock:
            if n > self.max_blocks_per_seq or n > len(self._free):
                return None
            taken = [self._free.pop() for _ in range(n)]
            for b in taken:
                assert b not in self._live and b != TRASH_BLOCK  # never double
                self._live.add(b)
            return taken

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks off the free list (all-or-nothing); raises
        :class:`BlockPoolExhausted` when the reservation cannot be met."""
        taken = self.try_alloc(n)
        if taken is None:
            if n > self.max_blocks_per_seq:
                raise BlockPoolExhausted(
                    f"request needs {n} blocks > max_blocks_per_seq="
                    f"{self.max_blocks_per_seq}")
            raise BlockPoolExhausted(
                f"need {n} blocks, free list has {len(self._free)} "
                f"({len(self._live)} live)")
        return taken

    def free(self, blocks: Iterable[int]) -> None:
        """Return a request's blocks to the free list (eviction reclaim)."""
        with self._lock:
            for b in blocks:
                if b == TRASH_BLOCK:
                    raise ValueError("cannot free the trash block")
                if b not in self._live:
                    raise ValueError(f"double free / foreign block {b}")
                self._live.discard(b)
                self._free.append(b)

    def table_row(self, blocks: Sequence[int]) -> np.ndarray:
        """A request's block-table row: its blocks, trash-padded to width."""
        row = np.full((self.max_blocks_per_seq,), TRASH_BLOCK, np.int32)
        row[: len(blocks)] = np.asarray(blocks, np.int32)
        return row

    def trash_row(self) -> np.ndarray:
        """All-trash row for inactive / padded micro-batch slots."""
        return np.full((self.max_blocks_per_seq,), TRASH_BLOCK, np.int32)

    # ---- cross-pool KV handoff --------------------------------------------
    def transfer_blocks(self, dst: "PagedKVPool",
                        src_blocks: Sequence[int],
                        dst_blocks: Sequence[int]) -> None:
        """Copy block *contents* into another pool — the disaggregated
        prefill->decode KV handoff when the two engines do not share a pool.

        Block-granular and layout-preserving: ``dst.pool[:, dst_blocks] =
        src.pool[:, src_blocks]`` for K and V, one device gather + scatter
        per side, no recomputation and no per-token reshaping (the in-repo
        analogue of a NIC-side paged KV transfer).  The caller owns the
        free-list bookkeeping on both pools (``dst_blocks`` must already be
        allocated from ``dst``)."""
        if len(src_blocks) != len(dst_blocks):
            raise ValueError(
                f"block count mismatch: {len(src_blocks)} src vs "
                f"{len(dst_blocks)} dst")
        if (self.k.shape[2:] != dst.k.shape[2:]
                or self.v.shape[2:] != dst.v.shape[2:]
                or self.n_layers != dst.n_layers):
            raise ValueError(
                f"incompatible pool geometry: {self.k.shape} vs {dst.k.shape}")
        si = jnp.asarray(src_blocks, jnp.int32)
        di = jnp.asarray(dst_blocks, jnp.int32)
        dst.k = dst.k.at[:, di].set(self.k[:, si])
        dst.v = dst.v.at[:, di].set(self.v[:, si])
        inj = dst.fault_injector or self.fault_injector
        if inj is not None and inj.block_corrupt():
            # injected transport corruption: the first transferred block
            # arrives as NaN — the decode guardrail, not this layer, is
            # responsible for catching it downstream
            dst.k = dst.k.at[:, di[0]].set(jnp.nan)
            dst.v = dst.v.at[:, di[0]].set(jnp.nan)

    @property
    def nbytes(self) -> int:
        """Device bytes of the pool arrays."""
        return self.k.nbytes + self.v.nbytes

    # ---- jit-side pool hand-back ------------------------------------------
    def update(self, k: jax.Array, v: jax.Array) -> None:
        """Adopt the pool arrays a jit step returned in place of the ones it
        took donated."""
        if k.shape != self.k.shape or v.shape != self.v.shape:
            raise ValueError(
                f"pool shape changed: {k.shape} vs {self.k.shape}")
        self.k, self.v = k, v
