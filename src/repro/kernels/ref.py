"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` is the semantic ground truth: tests sweep shapes/dtypes and
assert_allclose the kernel against these (interpret mode in the CPU tests,
compiled on the TPU by ``chip_smoke.py``). ops.py also serves the oracle on
a CPU backend for kernels whose interpret mode would be needlessly slow.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# staged_scatter: the unload-path drain (staging rows -> destination rows)
# ---------------------------------------------------------------------------


def staged_scatter_ref(
    dest: jnp.ndarray,     # [R, W] destination memory (pages/buffers)
    staging: jnp.ndarray,  # [N, W] staging ring payloads (append order)
    dst_row: jnp.ndarray,  # int32[N] destination row per staged entry
    valid: jnp.ndarray,    # bool[N] live entries
) -> jnp.ndarray:
    """PRECONDITION: valid dst_row entries are UNIQUE. The unload module
    guarantees this (a conflicting incoming write forces a drain first,
    see RemoteWriteEngine._conflicts_ring), so a drain batch never holds
    two entries for one destination row."""
    idx = jnp.where(valid, dst_row, dest.shape[0])  # OOB -> dropped
    return dest.at[idx].set(staging.astype(dest.dtype), mode="drop",
                            unique_indices=True)


# ---------------------------------------------------------------------------
# cms: count-min sketch batched update / query
# ---------------------------------------------------------------------------

_CMS_MULTIPLIERS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)
_CMS_OFFSETS = (0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09)


def cms_hash(ids: jnp.ndarray, row: int, log2_width: int) -> jnp.ndarray:
    x = ids.astype(jnp.uint32)
    a = jnp.uint32(_CMS_MULTIPLIERS[row])
    b = jnp.uint32(_CMS_OFFSETS[row])
    return ((x * a + b) >> jnp.uint32(32 - log2_width)).astype(jnp.int32)


def cms_update_ref(counts: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """counts [depth, width] (width = 2**k), ids int32[n] -> new counts."""
    depth, width = counts.shape
    log2w = width.bit_length() - 1
    for r in range(depth):
        counts = counts.at[r, cms_hash(ids, r, log2w)].add(1)
    return counts


def cms_query_ref(counts: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    depth, width = counts.shape
    log2w = width.bit_length() - 1
    est = counts[0, cms_hash(ids, 0, log2w)]
    for r in range(1, depth):
        est = jnp.minimum(est, counts[r, cms_hash(ids, r, log2w)])
    return est


# ---------------------------------------------------------------------------
# flash_attention: tiled causal (optionally sliding-window) attention
# ---------------------------------------------------------------------------


def flash_attention_ref(
    q: jnp.ndarray,  # [B, Hq, S, D]
    k: jnp.ndarray,  # [B, Hkv, T, D]
    v: jnp.ndarray,  # [B, Hkv, T, D]
    causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    if hkv != hq:
        reps = hq // hkv
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32)
    logits = logits * (d ** -0.5)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos + (t - s)  # queries may sit at the end of kv
    if window > 0:
        mask &= kpos > qpos + (t - s) - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v)


# ---------------------------------------------------------------------------
# flash_decode: one-token attention against a (long) KV cache
# ---------------------------------------------------------------------------


def flash_decode_paged_ref(
    q: jnp.ndarray,        # [B, C, Hq, D]
    pages_k: jnp.ndarray,  # [L, n_blocks, ps, Hkv, D] physical pool
    pages_v: jnp.ndarray,
    layer: jnp.ndarray,    # int32 scalar: the layer to read
    blocks: jnp.ndarray,   # int32 [B, P] physical block ids (clamped >= 0)
    view_ok: jnp.ndarray,  # bool [B, C, P*ps]
    bound: jnp.ndarray,    # int32 [B] the kernel's walk per slot, unused
    ring_k: jnp.ndarray | None = None,   # [L, B, R, Hkv, D]
    ring_v: jnp.ndarray | None = None,
    ring_ok: jnp.ndarray | None = None,  # bool [B, R]
) -> jnp.ndarray:
    """Oracle for the fused paged+ring decode kernel: take layer ``layer``
    of pool and ring, gather the per-slot view through the page table,
    append the staging-ring lanes, then the
    exact ``layers._sdpa_once`` op order (fp32 logits -> mask -> softmax ->
    dtype cast -> weighted sum) so the kernel can be held to ulp-level
    fp32 equality (same op order; XLA's shape-dependent GEMM tiling keeps
    the two graphs ~1e-7 apart — DESIGN.md §7). It gathers the whole
    table and ignores the kernel's per-slot page ``bound``: pages past it
    hold only masked rows."""
    del bound
    b, c, hq, d = q.shape
    pages_k, pages_v = pages_k[layer], pages_v[layer]
    if ring_k is not None:
        ring_k, ring_v = ring_k[layer], ring_v[layer]
    ps, hkv = pages_k.shape[1], pages_k.shape[2]
    rows = (blocks[:, :, None] * ps
            + jnp.arange(ps, dtype=blocks.dtype)[None, None, :]).reshape(b, -1)
    flat_k = pages_k.reshape(-1, hkv, d)
    flat_v = pages_v.reshape(-1, hkv, d)
    k = flat_k[rows]           # [B, P*ps, Hkv, D]
    v = flat_v[rows]
    mask = view_ok             # [B, C, P*ps]
    if ring_k is not None:
        k = jnp.concatenate([k, ring_k], axis=1)
        v = jnp.concatenate([v, ring_v], axis=1)
        mask = jnp.concatenate(
            [mask, jnp.broadcast_to(ring_ok[:, None, :],
                                    (b, c, ring_ok.shape[1]))], axis=2)
    if hkv != hq:
        reps = hq // hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    logits = jnp.einsum("bchd,bthd->bhct", q, k).astype(jnp.float32)
    logits = logits * (d ** -0.5)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhct,bthd->bchd", probs, v)


def flash_decode_ref(
    q: jnp.ndarray,        # [B, Hq, D]
    k: jnp.ndarray,        # [B, T, Hkv, D]
    v: jnp.ndarray,        # [B, T, Hkv, D]
    kv_mask: jnp.ndarray,  # bool [B, T] valid cache slots
) -> jnp.ndarray:
    b, hq, d = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        reps = hq // hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    logits = jnp.einsum("bhd,bthd->bht", q, k).astype(jnp.float32) * (d ** -0.5)
    logits = jnp.where(kv_mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bthd->bhd", probs, v)
