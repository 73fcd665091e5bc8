"""Paged KV cache: a global pool of fixed-size blocks backing EVERY
decode-time KV write of the continuous-batching serve scheduler.

Layout (vLLM-style, adapted to TPU: blocks are dense [page_size, H, Dh]
tiles so attention gathers whole blocks, never elements):

* ``pages_k`` / ``pages_v``  [L, n_blocks, page_size, H, Dh] — the physical
  pool, shared by every serving slot.
* ``page_table``             int32 [n_slots, max_pages] — physical block
  backing each slot's logical page (-1 = unallocated).
* A slot's *logical* row ``r`` lives at physical pool row
  ``page_table[slot, r // page_size] * page_size + r % page_size``.

Allocation is a host-side free-list (:class:`BlockPool`): the scheduler
allocates a slot's blocks at ADMISSION and frees them at RETIREMENT,
between scan segments — so inside the jitted decode scan the mapping is
a fixed-shape table lookup, never a data-dependent allocation.

The WRITE side is where the paper lands: inserting a token's (k, v) at an
arbitrary physical pool row is the RDMA-write analogue (random destination
page). Both paths go through this module's destination mapping:

* DIRECT (offload): scatter the tile straight to its physical row.
* STAGED (unload):  append to the per-slot ring overlay (``ring_k`` /
  ``ring_v`` / ``ring_pos`` / ``ring_fill`` keys on the same cache dict);
  attention reads pool-view ∪ ring; drains bulk-copy the ring into the
  pool through ``core.ring.scatter_rows`` (-> the ``staged_scatter``
  Pallas kernel on TPU). Ring entries record LOGICAL rows — physical
  rows are resolved through the page table at drain time, so a drain
  stays correct even though the pool is shared across slots (block
  ownership keeps drain destinations unique across slots).

The decision module's *region* for a write is its physical BLOCK id —
interleaved multi-slot traffic therefore hits a genuinely shared region
universe, exactly the mixed write stream the paper's monitor sees.
"""
from __future__ import annotations

import collections
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import ring as R

PagedKV = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# Host-side block allocator
# ---------------------------------------------------------------------------


class BlockPool:
    """Refcounted, content-hash-indexed allocator over the physical block
    pool (host side).

    Exclusive blocks keep the original free-list discipline: LIFO reuse
    (the most recently freed blocks are handed out first, so hot pool rows
    stay hot), tracked per slot in ALLOCATION order so a fragmented free
    restores the stack exactly (``free_slot`` releases in reverse
    allocation order). ``owner[b]`` tracks the slot that first acquired
    block ``b`` (-1 = free) — the scheduler-invariant tests audit it
    directly.

    On top of that, three lifecycle extensions back prefix caching and the
    host tier (DESIGN.md §8):

    * **refcounts** — ``refcount[b]`` counts live references; ``share``
      adds a reference to an existing block instead of allocating,
      ``release_block``/``free_slot`` decrement instead of freeing, and a
      block only leaves the live set at refcount 0.
    * **content hashes** — ``register(slot, block, h)`` publishes a block
      under a chained page-content hash (first registrant wins);
      ``lookup(h)`` finds it for admission-time prefix reuse. A hashed
      block whose refcount drops to 0 is parked on an LRU *evictable*
      list instead of the free list: it stays addressable by hash until
      ``alloc`` reclaims it under pressure (eviction de-registers).
    * **copy-on-write** — ``cow(slot, block)`` swaps a shared/registered
      block for a fresh private one in place (same page index), so a slot
      about to write never mutates a block other holders can see.

    Every mutation ends in a conservation audit: free + live + evictable
    must equal ``n_blocks``, and refcounts must sum to the held
    references — a double free or leaked reference raises immediately
    instead of corrupting the pool.
    """

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self.owner = np.full((n_blocks,), -1, np.int32)
        self.refcount = np.zeros((n_blocks,), np.int32)
        # slot -> blocks in allocation order (== the slot's page order)
        self._held: Dict[int, List[int]] = {}
        # content-hash index: hash -> block and its inverse
        self._hash_block: Dict[int, int] = {}
        self._block_hash: Dict[int, int] = {}
        # refcount-0 blocks still holding registered content, LRU order
        self._evictable: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_available(self) -> int:
        """Blocks an ``alloc`` can produce right now (free + evictable)."""
        return len(self._free) + len(self._evictable)

    def held(self, slot: int) -> Tuple[int, ...]:
        """``slot``'s blocks in allocation (= page) order."""
        return tuple(self._held.get(slot, ()))

    def is_registered(self, block: int) -> bool:
        return int(block) in self._block_hash

    def audit(self) -> None:
        """Conservation check (raises on lifecycle corruption): every
        block is exactly one of free / live (refcount > 0) / evictable,
        and refcounts sum to the held references."""
        live = int(np.count_nonzero(self.refcount > 0))
        if len(self._free) + live + len(self._evictable) != self.n_blocks:
            raise RuntimeError(
                f"BlockPool conservation violated: "
                f"{len(self._free)} free + {live} live + "
                f"{len(self._evictable)} evictable != {self.n_blocks}")
        refs = sum(len(v) for v in self._held.values())
        if refs != int(self.refcount.sum()):
            raise RuntimeError(
                f"BlockPool refcount drift: {refs} held references vs "
                f"refcount sum {int(self.refcount.sum())}")

    def _take(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks off the free list, evicting LRU cached blocks
        (de-registering their hashes) when the free list runs short."""
        if n > self.n_available:
            return None
        while len(self._free) < n:
            b, _ = self._evictable.popitem(last=False)  # LRU first
            h = self._block_hash.pop(b)
            del self._hash_block[h]
            self._free.append(b)
        return [self._free.pop() for _ in range(n)]

    def alloc(self, slot: int, n: int) -> Optional[np.ndarray]:
        """Pop ``n`` fresh (refcount-1, private) blocks for ``slot``;
        None (no partial alloc) if free + evictable can't cover the
        request. ``alloc(slot, 0)`` is a well-defined no-op returning an
        empty block list."""
        if n == 0:
            return np.zeros((0,), np.int32)
        taken = self._take(n)
        if taken is None:
            return None
        blocks = np.asarray(taken, np.int32)
        self.owner[blocks] = slot
        self.refcount[blocks] = 1
        self._held.setdefault(slot, []).extend(taken)
        self.audit()
        return blocks

    def lookup(self, h: int) -> Optional[int]:
        """Block registered under content hash ``h`` (live or evictable),
        or None."""
        return self._hash_block.get(h)

    def share(self, slot: int, block: int) -> None:
        """Add ``slot``'s reference to an existing block (prefix-cache
        hit): refcount++, revive from the evictable list if parked."""
        block = int(block)
        if self.refcount[block] == 0:
            if block not in self._evictable:
                raise ValueError(
                    f"block {block} is free — cannot share it")
            del self._evictable[block]
            self.owner[block] = slot
        self.refcount[block] += 1
        self._held.setdefault(slot, []).append(block)
        self.audit()

    def register(self, slot: int, block: int, h: int) -> bool:
        """Publish ``block`` under content hash ``h``. First registrant
        wins: returns False (no state change) when ``h`` is already
        registered or the block already carries a hash."""
        block = int(block)
        if h in self._hash_block or block in self._block_hash:
            return False
        held = self._held.get(slot)
        if not held or block not in held:
            raise ValueError(
                f"slot {slot} does not hold block {block} — refusing to "
                f"register it")
        self._hash_block[h] = block
        self._block_hash[block] = h
        return True

    def _deref(self, block: int) -> None:
        if self.refcount[block] <= 0:
            raise ValueError(
                f"block {block} is already free (double free)")
        self.refcount[block] -= 1
        if self.refcount[block] == 0:
            self.owner[block] = -1
            if block in self._block_hash:
                # registered content survives at refcount 0: evictable
                self._evictable[block] = None
            else:
                self._free.append(block)

    def release_block(self, slot: int, block: int) -> None:
        """Drop ``slot``'s reference to ``block``. Raises on freeing a
        block the slot does not hold (unowned / already-free)."""
        block = int(block)
        held = self._held.get(slot)
        if not held or block not in held:
            raise ValueError(
                f"slot {slot} does not hold block {block} "
                f"(double free or unowned block)")
        held.remove(block)
        if not held:
            del self._held[slot]
        self._deref(block)
        self.audit()

    def cow(self, slot: int, block: int) -> Optional[int]:
        """Copy-on-write: swap ``block`` for a fresh private block at the
        SAME page index in ``slot``'s held list, dropping the shared
        reference. Returns the new block id (the caller copies the device
        content), or None when the pool can't supply one."""
        block = int(block)
        held = self._held.get(slot)
        if not held or block not in held:
            raise ValueError(
                f"slot {slot} does not hold block {block} — cannot CoW")
        taken = self._take(1)
        if taken is None:
            return None
        new_b = taken[0]
        held[held.index(block)] = new_b
        self.owner[new_b] = slot
        self.refcount[new_b] = 1
        self._deref(block)
        self.audit()
        return new_b

    def free_slot(self, slot: int) -> np.ndarray:
        """Drop all of ``slot``'s references, in REVERSE allocation order
        (LIFO: the free-list stack is restored exactly as if the slot's
        allocs were undone, so hot pool rows stay hot even after
        fragmented frees). Returns the blocks released."""
        blocks = list(self._held.get(slot, ()))
        for b in reversed(blocks):
            self._deref(b)
        self._held.pop(slot, None)
        self.audit()
        return np.asarray(blocks[::-1], np.int32)


def prefix_page_hashes(prompt, page_size: int) -> List[int]:
    """Chained content hash per FULL page-aligned prompt chunk.

    ``h_i = hash(h_{i-1}, tokens of page i)`` — chaining makes a page
    hash identify the whole prefix through it, so two prompts share page
    ``i`` iff their first ``(i+1) * page_size`` tokens agree. Only full
    pages hash (a partial trailing page is never shareable: decode rows
    land in it)."""
    p = np.asarray(prompt).reshape(-1)
    out: List[int] = []
    h = 0
    for i in range(len(p) // page_size):
        chunk = tuple(int(t) for t in p[i * page_size:(i + 1) * page_size])
        h = hash((h, page_size, chunk))
        out.append(h)
    return out


# ---------------------------------------------------------------------------
# Host tier: device<->host bulk block transfers
# ---------------------------------------------------------------------------


def unload_blocks(cache: PagedKV, blocks) -> Tuple[np.ndarray, np.ndarray]:
    """Bulk device->host gather of whole blocks — the staging machinery's
    bulk-transfer lane run in REVERSE (the paper's unload move applied to
    capacity: contiguous PHASE_BULK traffic out of the device pool).
    Returns host-resident ``(k, v)`` arrays ``[L, len(blocks), ps, H,
    Dh]``; ``np.asarray`` forces the copy to completion, so the caller
    may free and rewrite the blocks immediately after.

    Under mesh-sharded serving the pool is head-sharded; ``np.asarray``
    on the sliced (still-sharded) array gathers each shard's slab of the
    selected blocks into the single-process host store — one bulk
    transfer per shard, no shard ever ships another shard's heads
    (``core.paths.validate`` rejects host_tier on meshes with
    non-addressable devices)."""
    bl = jnp.asarray(np.asarray(blocks, np.int32))
    return (np.asarray(cache["pages_k"][:, bl]),
            np.asarray(cache["pages_v"][:, bl]))


def load_blocks(cache: PagedKV, blocks, host_k: np.ndarray,
                host_v: np.ndarray) -> PagedKV:
    """Bulk host->device restore into freshly allocated blocks (the
    page-in half of the host tier); one contiguous set per plane. On a
    sharded pool the ``.at[].set`` scatters each shard's head slab back
    from the host copy — the per-shard inverse of
    :func:`unload_blocks`."""
    bl = jnp.asarray(np.asarray(blocks, np.int32))
    cache = dict(cache)
    cache["pages_k"] = cache["pages_k"].at[:, bl].set(
        jnp.asarray(host_k, cache["pages_k"].dtype))
    cache["pages_v"] = cache["pages_v"].at[:, bl].set(
        jnp.asarray(host_v, cache["pages_v"].dtype))
    return cache


def copy_block(cache: PagedKV, src: int, dst: int) -> PagedKV:
    """Device-side whole-block copy (the CoW data move: duplicate a
    shared block's content into the private replacement)."""
    cache = dict(cache)
    cache["pages_k"] = cache["pages_k"].at[:, dst].set(
        cache["pages_k"][:, src])
    cache["pages_v"] = cache["pages_v"].at[:, dst].set(
        cache["pages_v"][:, src])
    return cache


def constrain(cache: PagedKV, shardings) -> PagedKV:
    """Pin every cache plane to its serve-layer sharding (a
    ``{name: NamedSharding}`` dict from
    ``distributed.sharding.serve_cache_shardings``) inside a jitted body.

    The serve scheduler calls this at the END of each scan segment: the
    pool/ring data planes stay head-sharded across segment boundaries
    (instead of whatever layout GSPMD last propagated) and the replicated
    planes (page table, ring metadata) stay replicated for the host's
    between-segment mutation. No-op for planes without an entry."""
    return {k: (jax.lax.with_sharding_constraint(v, shardings[k])
                if k in shardings else v)
            for k, v in cache.items()}


# ---------------------------------------------------------------------------
# Device cache construction / addressing
# ---------------------------------------------------------------------------


def make_paged_kv(
    n_layers: int,
    n_blocks: int,
    page_size: int,
    n_slots: int,
    max_pages: int,
    h: int,
    dh: int,
    dtype=jnp.float32,
    ring_size: int = 0,
) -> PagedKV:
    """Paged cache dict; ``ring_size > 0`` attaches the staging overlay."""
    cache = {
        "pages_k": jnp.zeros((n_layers, n_blocks, page_size, h, dh), dtype),
        "pages_v": jnp.zeros((n_layers, n_blocks, page_size, h, dh), dtype),
        "page_table": jnp.full((n_slots, max_pages), -1, jnp.int32),
    }
    if ring_size:
        cache["ring_k"] = jnp.zeros((n_layers, n_slots, ring_size, h, dh), dtype)
        cache["ring_v"] = jnp.zeros_like(cache["ring_k"])
        # staged entries record LOGICAL rows (-1 = empty); the page table
        # resolves them to physical pool rows at drain time
        cache["ring_pos"] = jnp.full((n_slots, ring_size), -1, jnp.int32)
        cache["ring_fill"] = jnp.zeros((), jnp.int32)
    return cache


def has_ring(cache: PagedKV) -> bool:
    return "ring_pos" in cache


def pool_rows(cache: PagedKV) -> int:
    """Total physical rows (the out-of-range write sentinel)."""
    nb, ps = cache["pages_k"].shape[1:3]
    return nb * ps


def view_len(cache: PagedKV) -> int:
    """Logical rows per slot (max_pages * page_size)."""
    return cache["page_table"].shape[1] * cache["pages_k"].shape[2]


def logical_to_physical(cache: PagedKV, rows: jnp.ndarray) -> jnp.ndarray:
    """Per-slot logical row -> physical pool row. ``rows`` int32 [n_slots].

    Rows on unallocated pages (or negative sentinels) map to the
    out-of-range sentinel ``pool_rows`` so downstream scatters DROP them —
    a retired or empty slot can never write."""
    ps = cache["pages_k"].shape[2]
    n_slots = cache["page_table"].shape[0]
    safe = jnp.clip(rows, 0, view_len(cache) - 1)
    block = cache["page_table"][jnp.arange(n_slots), safe // ps]
    phys = block * ps + safe % ps
    ok = (rows >= 0) & (rows < view_len(cache)) & (block >= 0)
    return jnp.where(ok, phys, pool_rows(cache)).astype(jnp.int32)


def logical_to_physical_many(cache: PagedKV, rows: jnp.ndarray) -> jnp.ndarray:
    """Per-slot logical rows -> physical pool rows, ``rows`` int32
    [n_slots, C] (the chunk generalization of :func:`logical_to_physical`;
    column ``j`` of slot ``b`` resolves through slot ``b``'s page table).
    Invalid rows (negative sentinel, out of view, unallocated page) map to
    the out-of-range sentinel ``pool_rows`` so scatters DROP them."""
    ps = cache["pages_k"].shape[2]
    n_slots = cache["page_table"].shape[0]
    safe = jnp.clip(rows, 0, view_len(cache) - 1)
    block = cache["page_table"][jnp.arange(n_slots)[:, None], safe // ps]
    phys = block * ps + safe % ps
    ok = (rows >= 0) & (rows < view_len(cache)) & (block >= 0)
    return jnp.where(ok, phys, pool_rows(cache)).astype(jnp.int32)


def view_rows(cache: PagedKV) -> jnp.ndarray:
    """int32 [n_slots, V]: physical pool row backing every logical row
    (clamped to 0 where unallocated — mask with :func:`view_mask`)."""
    ps = cache["pages_k"].shape[2]
    table = cache["page_table"]
    base = jnp.maximum(table, 0) * ps  # [n_slots, max_pages]
    rows = base[:, :, None] + jnp.arange(ps)[None, None, :]
    return rows.reshape(table.shape[0], -1).astype(jnp.int32)


class StepPlan(NamedTuple):
    """Page-table-derived read-path products, hoisted ONCE per segment.

    The page table only changes host-side between scan segments (allocation
    at admission, frees at retirement), so everything derived from it —
    the logical->physical row map the reference gather uses, the clamped
    block table the fused kernel's scalar prefetch walks, and the
    page-allocated mask — is loop-invariant across a whole segment, not
    just across layers. The scheduler builds one plan per segment and
    threads it through every decode step.
    """

    view_ids: jnp.ndarray   # int32 [n_slots, V] physical row per logical row
    blocks: jnp.ndarray     # int32 [n_slots, P] clamped physical block ids
    allocated: jnp.ndarray  # bool [n_slots, V] page-allocated per logical row


def kernel_blocks(cache: PagedKV) -> jnp.ndarray:
    """int32 [n_slots, max_pages]: the fused kernel's scalar-prefetch
    operand — physical block ids, clamped to 0 where unallocated. Clamped
    entries walk block 0 and read the SAME garbage ``gather_view`` gathers
    through the clamped :func:`view_rows`, and the view mask hides it in
    both implementations, so fused and reference agree even on dead
    slots."""
    return jnp.maximum(cache["page_table"], 0).astype(jnp.int32)


@jax.named_scope("kv_view")
def step_plan(cache: PagedKV) -> StepPlan:
    """Build the per-segment :class:`StepPlan` (see its docstring)."""
    ps = cache["pages_k"].shape[2]
    return StepPlan(
        view_ids=view_rows(cache),
        blocks=kernel_blocks(cache),
        allocated=jnp.repeat(cache["page_table"] >= 0, ps, axis=1),
    )


def view_mask_from(allocated: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """:func:`view_mask` from a hoisted ``StepPlan.allocated``."""
    logical = jnp.arange(allocated.shape[1])[None, :]
    return (logical <= pos[:, None]) & allocated


def view_mask(cache: PagedKV, pos: jnp.ndarray) -> jnp.ndarray:
    """bool [n_slots, V]: logical rows holding live KV once row ``pos``
    is written this step (linear addressing: rows 0..pos on allocated
    pages)."""
    ps = cache["pages_k"].shape[2]
    allocated = jnp.repeat(cache["page_table"] >= 0, ps, axis=1)
    return view_mask_from(allocated, pos)


def gather_view(pages_l: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """One layer's per-slot contiguous KV view.

    pages_l [n_blocks, ps, H, Dh], rows int32 [n_slots, V] ->
    [n_slots, V, H, Dh]. Rows of unallocated pages gather block 0 garbage;
    the attention mask (:func:`view_mask`) excludes them."""
    flat = pages_l.reshape((-1,) + pages_l.shape[2:])
    return flat[rows]


def scatter_token(
    pages: jnp.ndarray,     # [L, n_blocks, ps, H, Dh] the whole pool plane
    layer: jnp.ndarray,     # int32 scalar: the layer written
    dest: jnp.ndarray,      # int32 [n_slots] physical rows (sentinel drops)
    tile: jnp.ndarray,      # [n_slots, H, Dh]
) -> jnp.ndarray:
    """Direct (offload-path) write of one decode step's tiles at
    ``[layer, dest]``. Only those rows change: no layer plane is sliced
    out and put back, so a pool carried through the layer scan is updated
    in place."""
    flat = pages.reshape(pages.shape[:1] + (-1,) + pages.shape[3:])
    flat = flat.at[layer, dest].set(tile.astype(flat.dtype), mode="drop")
    return flat.reshape(pages.shape)


def scatter_chunk(
    pages: jnp.ndarray,     # [L, n_blocks, ps, H, Dh] the whole pool plane
    layer: jnp.ndarray,     # int32 scalar: the layer written
    dest: jnp.ndarray,      # int32 [n_slots, C] physical rows (sentinel drops)
    tiles: jnp.ndarray,     # [n_slots, C, H, Dh]
) -> jnp.ndarray:
    """Direct (offload-path) bulk write of one mixed-phase step's tiles —
    the prefill-chunk analogue of :func:`scatter_token`, in place at
    ``[layer, dest]``. Destinations are unique across slots (block
    ownership) and within a chunk (consecutive logical rows), so the
    scatter never collides."""
    return scatter_token(pages, layer, dest.reshape(-1),
                         tiles.reshape((-1,) + tiles.shape[2:]))


# ---------------------------------------------------------------------------
# Staging-ring overlay (instantiation of core.ring, logical-row keys)
# ---------------------------------------------------------------------------


def ring_state(cache: PagedKV) -> R.RingState:
    """Dense-mode ring bookkeeping view (``core.ring.dense_state`` on this
    overlay's logical-row metadata — cf. ``kvcache.staged.ring_state``)."""
    return R.dense_state(cache["ring_pos"], cache["ring_fill"])


def ring_validity(cache: PagedKV) -> jnp.ndarray:
    return ring_state(cache).live


def ring_full(cache: PagedKV) -> jnp.ndarray:
    return R.full(ring_state(cache), wrap=False)


def ring_conflicts(cache: PagedKV, pos: jnp.ndarray) -> jnp.ndarray:
    """True if this step's logical destinations collide with pending staged
    entries of the same slot (drain first: keeps drain rows unique)."""
    return R.conflicts(ring_state(cache), (cache["ring_pos"],),
                       (pos[:, None],))


def stage_tile(plane: jnp.ndarray, layer: jnp.ndarray, tile: jnp.ndarray,
               cur: jnp.ndarray) -> jnp.ndarray:
    """Append layer ``layer``'s tiles [n_slots, H, Dh] at ring column
    ``cur`` of the whole ring plane [L, n_slots, R, H, Dh], in place."""
    return lax.dynamic_update_slice(
        plane, tile[None, :, None].astype(plane.dtype), (layer, 0, cur, 0, 0))


def ring_commit(cache: PagedKV, pos: jnp.ndarray,
                unload_mask: jnp.ndarray) -> PagedKV:
    """Metadata half of the append: record logical rows (-1 where the slot
    wrote direct or is retired) at the cursor, advance it."""
    cur = cache["ring_fill"]
    rows = jnp.where(unload_mask, pos, -1).astype(jnp.int32)
    cache = dict(cache)
    cache["ring_pos"] = R.push_column(cache["ring_pos"], cur, rows)
    cache["ring_fill"] = cur + 1
    return cache


def overlay_step_parts(
    cache: PagedKV,
    vmask: jnp.ndarray,        # bool [n_slots, V] view validity after write
    pos: jnp.ndarray,          # int32 [n_slots] this step's logical rows
    unload_mask: jnp.ndarray,  # bool [n_slots] True = stage
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-step overlay bookkeeping, kept as SEPARATE sources.

    Returns (view_ok [n_slots, V] pool-view validity with staged rows
    shadowed out, ring_ok [n_slots, R] ring-lane validity including this
    step's append, cur — the ring column this step appends to). The fused
    kernel consumes the two masks directly (pool walk + ring lanes as a
    second softmax source); the reference path concatenates them
    (:func:`overlay_step`) — same booleans either way, so mask parity
    between the implementations is by construction.
    """
    b, v = vmask.shape
    r = cache["ring_pos"].shape[1]
    cur = cache["ring_fill"]
    ring_valid = ring_validity(cache) | (
        (jnp.arange(r)[None, :] == cur) & unload_mask[:, None]
    )
    shadowed = R.shadow_mask(
        ring_validity(cache), cache["ring_pos"], v,
        extra_rows=jnp.where(unload_mask, pos, v),
    )
    return vmask & ~shadowed, ring_valid, cur


def overlay_step(
    cache: PagedKV,
    vmask: jnp.ndarray,        # bool [n_slots, V] view validity after write
    pos: jnp.ndarray,          # int32 [n_slots] this step's logical rows
    unload_mask: jnp.ndarray,  # bool [n_slots] True = stage
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-step overlay bookkeeping for ``decode_step_paged``.

    Returns (full_mask [n_slots, V+R] attention validity over view ∪ ring,
    cur — the ring column this step appends to). The authoritative value
    for a staged entry lives in the RING until drained, so its logical row
    is shadowed out of the view mask.
    """
    view_ok, ring_valid, cur = overlay_step_parts(cache, vmask, pos,
                                                  unload_mask)
    full_mask = jnp.concatenate([view_ok, ring_valid], axis=1)
    return full_mask, cur


def view_chunk_mask(cache: PagedKV, positions: jnp.ndarray) -> jnp.ndarray:
    """bool [n_slots, C, V]: per-query view validity for a mixed-phase
    chunk step. ``positions`` int32 [n_slots, C] — query ``j`` of slot
    ``b`` sits at logical row ``positions[b, j]``; linear addressing means
    a view row is causally visible when its logical id is <= the query's
    position, and attendable only on an allocated page (this step's chunk
    rows are scattered into the pool BEFORE the gather, so in-chunk causal
    visibility falls out of the same rule)."""
    ps = cache["pages_k"].shape[2]
    allocated = jnp.repeat(cache["page_table"] >= 0, ps, axis=1)
    return view_chunk_mask_from(allocated, positions)


def view_chunk_mask_from(allocated: jnp.ndarray,
                         positions: jnp.ndarray) -> jnp.ndarray:
    """:func:`view_chunk_mask` from a hoisted ``StepPlan.allocated``."""
    rows = jnp.arange(allocated.shape[1])[None, None, :]
    return (rows <= positions[:, :, None]) & allocated[:, None, :]


def overlay_chunk_parts(
    cache: PagedKV,
    positions: jnp.ndarray,    # int32 [n_slots, C] per-query logical rows
    unload_mask: jnp.ndarray,  # bool [n_slots] True = column-0 write stages
    allocated: Optional[jnp.ndarray] = None,  # hoisted StepPlan.allocated
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked analogue of :func:`overlay_step_parts`.

    Returns (view_ok [n_slots, C, V], ring_ok [n_slots, R] — per-lane, NOT
    broadcast over C: a slot's pending ring entries always hold rows
    strictly below its current position (conflict-forced drains), so ring
    validity needs no per-query causal term — and cur, the ring column this
    step appends to).
    """
    r = cache["ring_pos"].shape[1]
    cur = cache["ring_fill"]
    live = ring_validity(cache)
    ring_valid = live | (
        (jnp.arange(r)[None, :] == cur) & unload_mask[:, None]
    )
    v = view_len(cache)
    shadowed = R.shadow_mask(
        live, cache["ring_pos"], v,
        extra_rows=jnp.where(unload_mask, positions[:, 0], v),
    )
    if allocated is None:
        vmask = view_chunk_mask(cache, positions)
    else:
        vmask = view_chunk_mask_from(allocated, positions)
    return vmask & ~shadowed[:, None, :], ring_valid, cur


def overlay_chunk(
    cache: PagedKV,
    positions: jnp.ndarray,    # int32 [n_slots, C] per-query logical rows
    unload_mask: jnp.ndarray,  # bool [n_slots] True = column-0 write stages
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mixed-phase generalization of :func:`overlay_step`.

    Returns (full_mask bool [n_slots, C, V+R] attention validity over
    view ∪ ring, cur — the ring column this step appends to). Only the
    scattered column-0 (decode-phase) write may stage; prefill chunks are
    bulk/direct, and a prefilling slot's ring lane is empty (lanes drain at
    every segment boundary, before the slot could have been admitted).
    """
    view_ok, ring_valid, cur = overlay_chunk_parts(cache, positions,
                                                   unload_mask)
    c = positions.shape[1]
    r = ring_valid.shape[1]
    ring_ok = jnp.broadcast_to(ring_valid[:, None, :],
                               (positions.shape[0], c, r))
    return jnp.concatenate([view_ok, ring_ok], axis=2), cur


@jax.named_scope("kv_drain")
def drain_ring(cache: PagedKV, use_kernel: bool,
               shardings=None) -> PagedKV:
    """Bulk-copy all staged entries into the pool, empty the ring.

    Per layer, ALL slots' entries flatten into ONE entry list (``core.ring.
    merge_lanes``) and land with a single ``scatter_rows`` call — block
    ownership makes destinations unique across slots, conflict-forced
    drains make them unique within a slot (the ``staged_scatter``
    precondition). ``use_kernel`` picks the ``staged_scatter`` kernel or
    the jnp scatter; serving takes it from its plan
    (``core.paths.resolve_drain_kernel``).

    Mesh-sharded serving: each layer's scatter works on ``[rows,
    H * Dh]`` views whose ROW axis (physical pool row / ring entry) is
    replicated and whose WIDTH axis carries the sharded heads (H major,
    Dh minor — the reshape merges the head shard boundary cleanly). The
    drain's routing inputs (``ring_pos``, page table) are replicated, so
    every shard computes the SAME destination rows and scatters only its
    own head slice: a per-shard ``staged_scatter``, no cross-shard
    traffic (the paper's keep-the-unload-local contract under TP). The
    jnp scatter gets that from GSPMD; the kernel is run per shard
    explicitly (``shard_map`` over the cache ``shardings``, the serve
    scheduler's placement dict), since a Mosaic kernel is never
    partitioned by the compiler."""
    # resolve logical -> physical per ring column, then flatten lanes
    phys = jax.vmap(lambda rows: logical_to_physical(cache, rows),
                    in_axes=1, out_axes=1)(cache["ring_pos"])
    rows, ok = R.merge_lanes(ring_state(cache), phys)
    # logical_to_physical maps every invalid row to exactly the pool's
    # row count, which scatter_rows drops — no re-clamp needed

    def drain_planes(pages, staging, rows, ok):
        _, nb, ps, h, dh = pages.shape
        _, b, r, _, _ = staging.shape

        def drain_layer(pages_l, staging_l):
            flat = pages_l.reshape(nb * ps, h * dh)
            out = R.scatter_rows(flat, staging_l.reshape(b * r, h * dh),
                                 rows, ok, use_kernel=use_kernel)
            return out.reshape(pages_l.shape)

        return jax.vmap(drain_layer)(pages, staging)

    if use_kernel and shardings is not None:
        pool = shardings["pages_k"]
        drain_planes = jax.shard_map(
            drain_planes, mesh=pool.mesh,
            in_specs=(pool.spec, shardings["ring_k"].spec, P(), P()),
            out_specs=pool.spec, check_vma=False)
    new_k = drain_planes(cache["pages_k"], cache["ring_k"], rows, ok)
    new_v = drain_planes(cache["pages_v"], cache["ring_v"], rows, ok)
    return dict(
        cache,
        pages_k=new_k,
        pages_v=new_v,
        ring_pos=jnp.full_like(cache["ring_pos"], -1),
        ring_fill=jnp.zeros_like(cache["ring_fill"]),
    )


def maybe_drain(
    cache: PagedKV,
    use_kernel: bool,
    incoming_pos: Optional[jnp.ndarray] = None,
    shardings=None,
) -> Tuple[PagedKV, jnp.ndarray]:
    """Fixed-shape conditional drain: ring full OR incoming logical rows
    conflict with pending entries. Returns (cache, drained bool)."""
    due = ring_full(cache)
    if incoming_pos is not None:
        due = due | ring_conflicts(cache, incoming_pos)
    cache = lax.cond(
        due,
        lambda c: drain_ring(c, use_kernel=use_kernel, shardings=shardings),
        lambda c: dict(c),
        cache,
    )
    return cache, due


# ---------------------------------------------------------------------------
# Speculative-decoding draft-cache rollback (DESIGN.md §11)
# ---------------------------------------------------------------------------


def snapshot_stack(snaps: List[PagedKV]) -> PagedKV:
    """Stack draft-cache pytrees along a NEW leading snapshot axis.

    The spec segment's draft loop collects one cache snapshot per draft
    step (``snaps[j]`` = state after the draft consumed ``j`` proposal
    tokens). Recurrent draft families (SSM) carry NO sequence axis, so
    the stack is tiny — k+2 copies of a per-slot state vector — and the
    per-slot rollback after the verify pass is a single gather
    (:func:`select_snapshot`). Positional drafts (dense/moe KV lanes)
    never need this: their rollback is cursor arithmetic — stale rows
    past the committed position are overwritten before any read."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *snaps)


def select_snapshot(stacked, idx: jnp.ndarray):
    """Per-slot rollback gather over a :func:`snapshot_stack` result.

    Every cache leaf follows the repo-wide lanes convention — batch on
    axis 1 — so a stacked leaf is ``[n_snaps, X, B, ...]``; slot ``b``
    rolls back to snapshot ``idx[b]`` (its committed-token count this
    round: 0 = full rejection, k+1 = full acceptance + bonus)."""
    def pick(leaf):
        moved = jnp.moveaxis(leaf, 2, 0)            # [B, n_snaps, X, ...]
        sel = moved[jnp.arange(moved.shape[0]), idx]
        return jnp.moveaxis(sel, 0, 1)              # [X, B, ...]
    return jax.tree.map(pick, stacked)
