"""Paged KV cache: a fixed-size device block pool + a host-side allocator.

The serving-side answer to `models/generate.py`'s whole-batch cache
(ISSUE 6 tentpole, ROADMAP item 2): `generate()` gives every request its
own ``[L, B, max_len, H, Dh]`` cache sized for the worst case, so N
concurrent mixed-length streams pay N · max_len positions of HBM whether
they use them or not. Here sequences share ONE pool of fixed-size blocks
(vLLM's PagedAttention allocation scheme, mapped onto this repo's
static-shape/one-compile discipline):

- The device side is a pair of static-shape arrays ``[L, num_blocks,
  block_len, H, Dh]`` (layer-major; the engine's per-layer ``lax.scan``
  carries the whole pair and writes and gathers it in place by (layer,
  block, offset) — it never slices one layer's pool out or writes one back).
  ``kv_dtype`` reuses ``init_cache``'s storage-dtype option: bf16 blocks
  halve the cache bytes a decode step reads (the serving cells run bf16;
  PERF.md section 5 has the step's parts).
- The host side is a free-list allocator handing out block *indices*; each
  live sequence owns a row of a ``[num_slots, max_blocks_per_seq]`` block
  table mapping its logical positions to pool blocks. Attention gathers a
  sequence's blocks through its table row, so physical placement never
  affects the math (pinned bitwise in tests/test_serving.py: the bar of
  the XLA path, in float32, on the CPU). On a TPU the decode program does
  not gather: a kernel reads the live blocks where they lie, one
  contiguous ``block_len x H x Dh`` run a block of a layer
  (``ops/paged_attention.py``), held to the XLA path by tolerance
  (tests/test_paged_attention.py) and to the float32 reference on the chip
  (``served_logit_gap``, PERF.md).
- Block 0 is reserved as the TRASH block: inactive slots and padded
  prefill tail tokens route their cache *writes* there (a static-shape
  program always writes somewhere), and unallocated table entries point at
  it. Garbage in trash is never read un-masked — decode attention masks by
  absolute position (``kpos <= pos``), the same invariant that makes
  ``generate``'s unwritten cache tail safe.

- A model with a state-space mixer has a second kind of cache beside the
  pool, which no block holds and nothing pages: ``init_state``, a recurrent
  state and the convolution's carried inputs a SLOT a layer, fixed in size
  whatever the length. Admission reckons blocks for the pool and a slot for
  the state; the engine keeps both in one donated tree.

Sizing math (docs/COMPONENTS.md "Serving" carries the worked example):
one block holds ``2 · L · block_len · H · Dh · itemsize`` bytes of K+V;
a request of prompt ``P`` generating ``M`` tokens writes positions
``0..P+M-2`` (the final sampled token is never fed back — same horizon as
``generate``'s scan) and therefore needs ``ceil((P+M-1)/block_len)``
blocks. The pool is intentionally sized BELOW peak naive demand
(N_concurrent · max_len): admission control queues requests the free list
cannot cover, and retirement frees blocks at the next token boundary.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax.numpy as jnp

from ..config import describe

# Block index 0 is never allocated: it absorbs the writes of inactive
# slots / padded prefill tails so every compiled step can write
# unconditionally at a static shape.
TRASH_BLOCK = 0


@dataclass(frozen=True)
class PagedKVConfig:
    """Pool geometry. ``num_blocks`` INCLUDES the reserved trash block, so
    ``num_blocks - 1`` blocks are allocatable. ``max_blocks_per_seq``
    bounds one sequence's block-table row; ``max_seq_len`` is the longest
    prompt+generation the engine can serve (and the padded length every
    attention gather sees — one compile, any mix of live lengths)."""

    num_blocks: int
    block_len: int
    max_blocks_per_seq: int
    kv_dtype: Optional[str] = None

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError(f"num_blocks={self.num_blocks}: need at least "
                             "one allocatable block beside the trash block")
        if self.block_len < 1 or self.max_blocks_per_seq < 1:
            raise ValueError(f"bad pool geometry: {self}")

    @property
    def max_seq_len(self) -> int:
        return self.block_len * self.max_blocks_per_seq


def blocks_for(n_tokens: int, block_len: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache positions."""
    return -(-max(0, n_tokens) // block_len)


LANES = 128


def row_stride(desc) -> int:
    """Values a latent pool keeps for one position in one layer: the row
    (``LatentAttention.row_dim``) rounded up to whole vectors of ``LANES``,
    the rest zero. The chip tiles an array's last dimension in 128 lanes, so
    a gatherable row of 576 occupies 640 whatever is declared; declared as
    576 the runtime lays the pool out with the block index last instead, and
    each program then copies the whole pool in and out (PERF.md, PR 29)."""
    return -(-desc.attention.row_dim // LANES) * LANES


def init_pool(cfg, paged: PagedKVConfig) -> dict:
    """Zeroed block pool, sized from the model's description
    (``config.describe``). K and V per head: {"k","v"} each [L, num_blocks,
    block_len, H, Dh], ``H`` the key/value heads (fewer than the query heads
    where they are grouped). Latent attention: {"c"} [L, num_blocks, block_len,
    row_dim], ONE row a position a layer (``models/latent.py::latent_row``)
    and nothing per head, in ``row_stride`` lanes. Layer-major like ``init_cache``, but the engine
    does not scan the leading axis: the whole stacked pool is its layer
    scan's carry, and each layer scatters into and gathers from it at
    (layer, block, offset)."""
    dt = jnp.dtype(paged.kv_dtype or cfg.dtype)
    desc = describe(cfg)
    lead = (desc.n_layers, paged.num_blocks, paged.block_len)
    if desc.attention is not None:
        return {"c": jnp.zeros(lead + (row_stride(desc),), dt)}
    shape = lead + (desc.num_kv_heads, desc.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_state(cfg, num_slots: int) -> dict:
    """The second kind of cache, for a model with a state-space mixer
    (``config.StateSpaceMixer``; {} for every other): what each SLOT carries
    whatever its length, zeroed. {"s": the recurrent state [L, num_slots,
    heads, head_dim, state] in ``state_dtype``, "tail": the convolution's
    last ``conv - 1`` inputs [L, num_slots, conv - 1, conv_dim] in the
    compute type}. Nothing pages it and no block holds it: a slot owns its
    rows from admission to retirement, the engine's two programs take the
    store donated beside the pool and hand it back written in place, and a
    request's first prefill chunk starts from zeros whatever the slot's last
    request left (``engine._block_parallel``)."""
    desc = describe(cfg)
    mx = desc.mixer
    if mx is None:
        return {}
    lead = (desc.n_layers, num_slots)
    return {"s": jnp.zeros(lead + (mx.heads, mx.head_dim, mx.state),
                           jnp.dtype(mx.state_dtype)),
            "tail": jnp.zeros(lead + (mx.conv - 1, mx.conv_dim),
                              jnp.dtype(desc.dtype))}


def state_bytes_per_slot(cfg) -> int:
    """Bytes of ``init_state`` one slot owns across all layers; 0 for a
    model without a state-space mixer."""
    desc = describe(cfg)
    mx = desc.mixer
    if mx is None:
        return 0
    return desc.n_layers * (
        mx.heads * mx.head_dim * mx.state * jnp.dtype(mx.state_dtype).itemsize
        + (mx.conv - 1) * mx.conv_dim * jnp.dtype(desc.dtype).itemsize)


def kv_bytes_per_token(cfg, kv_dtype: Optional[str] = None) -> int:
    """Bytes one cache position occupies across all layers: K and V of
    every head, or one latent row a layer as the pool stores it."""
    dt = jnp.dtype(kv_dtype or cfg.dtype)
    desc = describe(cfg)
    row = desc.cache_row if desc.attention is None else row_stride(desc)
    return desc.n_layers * row * dt.itemsize


def pool_bytes(cfg, paged: PagedKVConfig) -> int:
    """Total device bytes of the block pool (the serving KV footprint)."""
    return (paged.num_blocks * paged.block_len
            * kv_bytes_per_token(cfg, paged.kv_dtype))


def naive_cache_bytes(cfg, n_streams: int, max_len: int,
                      kv_dtype: Optional[str] = None) -> int:
    """What ``generate`` would allocate for ``n_streams`` concurrent
    requests: one whole ``max_len`` cache each. The smoke asserts
    ``pool_bytes < naive_cache_bytes`` at peak concurrency — the paged
    pool's reason to exist."""
    return n_streams * max_len * kv_bytes_per_token(cfg, kv_dtype)


class BlockAllocator:
    """Host-side free list over block indices ``1..num_blocks-1``, with
    per-block REFERENCE COUNTS for copy-on-write prefix sharing.

    ``alloc`` is all-or-nothing (a sequence's full reservation or None) so
    admission control can never strand a half-provisioned request — the
    liveness argument in scheduler.py rests on this. Lowest-index-first
    hand-out keeps runs reproducible; block identity never reaches the
    math (attention gathers through the table), so the order is a
    debugging nicety, not a correctness requirement.

    Sharing (ROADMAP 2c): ``share`` takes additional references on
    already-allocated blocks — requests whose prompts share a full-block
    prefix map the SAME physical blocks read-only (the engine masks their
    writes to trash), so N identical prefixes cost one block set plus
    refcounts instead of N. ``free`` decrements and returns a block to
    the free list only at zero — and reports which blocks PHYSICALLY
    freed, so the engine can evict their prefix-cache entries. ``in_use``
    and ``peak_in_use`` count physical blocks: the peak DROPPING on a
    shared-prefix workload is the satellite's acceptance bar.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"num_blocks={num_blocks}: nothing to allocate")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))   # pop() -> lowest
        self._refs: dict = {}            # block -> live references
        self.peak_in_use = 0

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def fragmentation(self) -> dict:
        """Free-list fragmentation census (schema v9 ``memory`` events):
        ``holes`` is the number of maximal contiguous index runs the free
        list has shattered into, ``largest_run`` the longest of them — the
        biggest single reservation the pool could grant contiguously. An
        empty free list is 0 holes / 0 run; a fully-free pool is exactly 1
        hole spanning ``capacity``. O(free) over a sorted copy — called at
        meter cadence (scheduler ticks), never per token."""
        if not self._free:
            return {"holes": 0, "largest_run": 0}
        holes, run, largest = 1, 1, 1
        ordered = sorted(self._free)
        for prev, cur in zip(ordered, ordered[1:]):
            if cur == prev + 1:
                run += 1
            else:
                holes += 1
                run = 1
            largest = max(largest, run)
        return {"holes": holes, "largest_run": largest}

    @property
    def holes(self) -> int:
        return self.fragmentation()["holes"]

    @property
    def largest_run(self) -> int:
        return self.fragmentation()["largest_run"]

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` blocks, or None if the pool cannot cover them (caller
        queues — never a partial grant)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        for b in got:
            self._refs[b] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return got

    def share(self, blocks: List[int]) -> None:
        """Take one more reference on each (already-allocated) block —
        the CoW mapping step. Never touches the free list, so it can
        never fail for capacity and never moves the physical peak."""
        for b in blocks:
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"share({b}): block is not allocated")
        for b in blocks:
            self._refs[b] += 1

    def free(self, blocks: List[int]) -> List[int]:
        """Drop one reference per block; blocks reaching zero return to
        the free list. Returns the PHYSICALLY freed blocks (refcount hit
        zero) so prefix-cache entries can be evicted with them."""
        for b in blocks:
            if not 1 <= b < self.num_blocks:
                raise ValueError(f"free({b}): not an allocatable block")
        counts: dict = {}
        for b in blocks:
            counts[b] = counts.get(b, 0) + 1
        for b, n in counts.items():
            if self._refs.get(b, 0) < n:
                raise ValueError(f"free({b}): double free")
        freed = []
        for b, n in counts.items():
            self._refs[b] -= n
            if self._refs[b] == 0:
                del self._refs[b]
                freed.append(b)
        # Re-sort so the free list stays lowest-first regardless of
        # retirement order — allocation traces depend only on the
        # alloc/free sequence, not on which request finished first.
        if freed:
            self._free = sorted(set(self._free) | set(freed), reverse=True)
        return freed
