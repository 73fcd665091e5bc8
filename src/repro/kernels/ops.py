"""ops — jit'd public wrappers around the Pallas kernels.

Each wrapper:
* dispatches to the Pallas kernel: compiled by Mosaic on a TPU, run with
  ``interpret=True`` only when JAX's backend is the CPU (the test lane);
* can be forced to the pure-jnp oracle with ``impl='ref'`` (used by tests
  and as a paranoid fallback);
* is shape/dtype polymorphic within the kernels' documented constraints.
"""
from __future__ import annotations

from functools import partial

import jax

from . import ref
from .cms import cms_query as _cms_query_kernel
from .cms import cms_update as _cms_update_kernel
from .flash_attention import flash_attention as _flash_attention_kernel
from .flash_decode import flash_decode as _flash_decode_kernel
from .flash_decode import flash_decode_paged as _flash_decode_paged_kernel
from .flash_decode import (
    flash_decode_paged_sharded as _flash_decode_paged_sharded_kernel,
)
from .staged_scatter import staged_scatter as _staged_scatter_kernel


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("impl", "block_w"))
def staged_scatter(dest, staging, dst_row, valid, impl: str = "auto", block_w: int = 512):
    """Unload-path drain: move staged rows to destination rows."""
    if impl == "ref":
        return ref.staged_scatter_ref(dest, staging, dst_row, valid)
    bw = block_w
    while dest.shape[1] % bw:
        bw //= 2
    return _staged_scatter_kernel(
        dest, staging, dst_row, valid, block_w=bw, interpret=_on_cpu()
    )


@partial(jax.jit, static_argnames=("impl",))
def cms_update(counts, ids, impl: str = "auto"):
    if impl == "ref":
        return ref.cms_update_ref(counts, ids)
    return _cms_update_kernel(counts, ids, interpret=_on_cpu())


@partial(jax.jit, static_argnames=("impl",))
def cms_query(counts, ids, impl: str = "auto"):
    if impl == "ref":
        return ref.cms_query_ref(counts, ids)
    return _cms_query_kernel(counts, ids, interpret=_on_cpu())


@partial(jax.jit, static_argnames=("causal", "window", "impl", "block_q", "block_k"))
def flash_attention(
    q, k, v, causal: bool = True, window: int = 0,
    impl: str = "auto", block_q: int = 128, block_k: int = 128,
):
    """Tiled attention; q [B,Hq,S,D], kv [B,Hkv,T,D]."""
    if impl == "ref" or (impl == "auto" and _on_cpu()):
        # interpret-mode flash over 32k+ sequences is too slow for CPU
        # smoke/examples; the kernel itself is validated by tests with
        # impl='kernel'.
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    bq = block_q
    while q.shape[2] % bq:
        bq //= 2
    bk = block_k
    while k.shape[2] % bk:
        bk //= 2
    return _flash_attention_kernel(
        q, k, v, causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=_on_cpu(),
    )


@partial(jax.jit, static_argnames=("impl", "block_k"))
def flash_decode(q, k, v, kv_mask, impl: str = "auto", block_k: int = 512):
    """One-token decode attention; q [B,Hq,D], kv [B,T,Hkv,D]."""
    if impl == "ref" or (impl == "auto" and _on_cpu()):
        return ref.flash_decode_ref(q, k, v, kv_mask)
    bk = block_k
    while k.shape[1] % bk:
        bk //= 2
    return _flash_decode_kernel(q, k, v, kv_mask, block_k=bk, interpret=_on_cpu())


@partial(jax.jit, static_argnames=("impl",))
def flash_decode_paged(q, pages_k, pages_v, layer, blocks, view_ok,
                       ring_k=None, ring_v=None, ring_ok=None,
                       impl: str = "auto"):
    """Fused paged decode: page-table walk + staging-ring overlay + SDPA,
    reading layer ``layer`` of the stacked pool and ring.

    Unlike ``flash_decode``, ``auto`` does NOT silently fall back to the
    oracle on CPU: which implementation serves decode is a negotiated
    engine capability (``core.paths.resolve_attention``), so by the time
    this wrapper runs the caller has already chosen the kernel — on CPU it
    runs in interpret mode (the parity/validation lane).
    """
    if impl == "ref":
        return ref.flash_decode_paged_ref(
            q, pages_k, pages_v, layer, blocks, view_ok, ring_k, ring_v,
            ring_ok)
    return _flash_decode_paged_kernel(
        q, pages_k, pages_v, layer, blocks, view_ok, ring_k, ring_v, ring_ok,
        interpret=_on_cpu())


def flash_decode_paged_sharded(mesh, q, pages_k, pages_v, layer, blocks,
                               view_ok, ring_k=None, ring_v=None,
                               ring_ok=None, axis: str = "model"):
    """Head-sharded ``flash_decode_paged``: one kernel instance per mesh
    shard over its own head slice (``shard_map``); routing inputs
    replicated. Requires whole GQA groups per shard — see the kernel
    module's wrapper for the divisibility contract."""
    return _flash_decode_paged_sharded_kernel(
        mesh, q, pages_k, pages_v, layer, blocks, view_ok, ring_k, ring_v,
        ring_ok, axis=axis, interpret=_on_cpu())
