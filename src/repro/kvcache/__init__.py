"""KV-cache substrate: dense caches with staged-ring overlay (unload path
for decode writes, instantiating the unified ``core.ring`` abstraction) and
the paged block pool backing the continuous-batching serve scheduler."""
from .paged import (
    BlockPool,
    copy_block,
    drain_ring as drain_ring_paged,
    gather_view,
    load_blocks,
    logical_to_physical,
    make_paged_kv,
    maybe_drain as maybe_drain_paged,
    pool_rows,
    prefix_page_hashes,
    scatter_token,
    unload_blocks,
    view_len,
    view_rows,
)
from .staged import (
    add_ring,
    drain_ring,
    maybe_drain,
    overlay_kv,
    overlay_masks,
    overlay_step,
    ring_commit,
    ring_conflicts,
    ring_full,
    ring_state,
    ring_validity,
    stage_tile,
    strip_ring,
)

__all__ = [
    "BlockPool", "copy_block", "drain_ring_paged", "gather_view",
    "load_blocks", "logical_to_physical", "make_paged_kv",
    "maybe_drain_paged", "pool_rows", "prefix_page_hashes",
    "scatter_token", "unload_blocks", "view_len", "view_rows",
    "add_ring", "drain_ring", "maybe_drain", "overlay_kv", "overlay_masks",
    "overlay_step", "ring_commit", "ring_conflicts", "ring_full",
    "ring_state", "ring_validity", "stage_tile", "strip_ring",
]
