"""flash_decode — one-token attention against a long KV cache.

The decode-shape hot spot (decode_32k / long_500k): a single query row per
sequence attends over a 32k–512k-entry KV cache. The kernel streams the
cache in BK-sized blocks, keeping the online-softmax state (m, l, acc) in
VMEM; the cache layout is [B, T, Hkv, D] — the same layout the uRDMA write
engine maintains — so no transpose materializes at decode time.

Under shard_map with the cache sequence-sharded, each device runs this
kernel over its local T-shard and the partial (acc, l, m) triples are
combined with a 3-way psum-style log-sum-exp merge (see ops.flash_decode's
``partial`` mode) — the flash-decode sequence-parallel pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, bk, n_kv, scale, group,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]           # [1, D] single query row (kept 2D for the MXU)
    k = k_ref[0, :, 0]     # [BK, D]
    v = v_ref[0, :, 0]
    valid = mask_ref[0] != 0  # [BK]

    scores = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [1, BK]
    scores = jnp.where(valid[None, :], scores, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    p = jnp.where(valid[None, :], p, 0.0)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    pv = lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = alpha * acc_ref[...] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom)[0].astype(o_ref.dtype)


def _paged_kernel(
    # scalar prefetch
    tab_ref,               # int32 [B, P] physical block ids (clamped >= 0)
    layer_ref,             # int32 [1] the layer of the pool to read
    # inputs
    q_ref,                 # [1, Hkv, G*C, D] queries, grouped by KV head
    k_ref,                 # [1, ps, Hkv, D] one physical KV block, all heads
    v_ref,                 # [1, ps, Hkv, D]
    m_ref,                 # int32 [1, 1, G*C, ps] view-validity for this block
    *rest,                 # (+ ring refs) then o_ref, then scratch
    n_pages, scale, ring,
):
    """Grid (B, P): one walk of one slot's page table.

    Each step reads one physical block straight out of the pool (the
    BlockSpecs index the pool through the scalar-prefetched layer and
    table — no gathered copy ever lands in HBM), scores it for every head
    at once and folds it into an online softmax: running max, denominator
    and unnormalized weighted sum, rescaled as the max grows. The
    staging-ring lanes join at the last page as a second KV source; the
    output is the weighted sum over the denominator. Nothing in VMEM grows
    with the context length.
    """
    if ring:
        rk_ref, rv_ref, rm_ref, o_ref, mx_ref, l_ref, acc_ref = rest
    else:
        o_ref, mx_ref, l_ref, acc_ref = rest
    j = pl.program_id(1)
    q = q_ref[0]             # [Hkv, GC, D]

    @pl.when(j == 0)
    def _init():
        mx_ref[...] = jnp.full_like(mx_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(k, v, ok):      # k, v [T, Hkv, D], ok [GC|1, T]
        s = jnp.einsum("hcd,thd->hct", q, k,
                       preferred_element_type=jnp.float32)
        s = s.astype(q.dtype).astype(jnp.float32) * scale
        s = jnp.where(ok[None], s, _NEG_INF)
        m_prev = mx_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.einsum(
            "hct,thd->hcd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        mx_ref[...] = m_new

    fold(k_ref[0], v_ref[0], m_ref[0, 0] != 0)

    @pl.when(j == n_pages - 1)
    def _finalize():
        if ring:
            fold(rk_ref[0], rv_ref[0], rm_ref[0] != 0)
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_decode_paged(
    q: jnp.ndarray,         # [B, C, Hq, D] query slab (C=1 for step decode)
    pages_k: jnp.ndarray,   # [L, n_blocks, ps, Hkv, D] physical pool
    pages_v: jnp.ndarray,   # [L, n_blocks, ps, Hkv, D]
    layer: jnp.ndarray,     # int32 scalar: the layer of pool and ring to read
    blocks: jnp.ndarray,    # int32 [B, P] per-slot physical block ids (>= 0)
    view_ok: jnp.ndarray,   # bool [B, C, P*ps] paged-view validity mask
    ring_k: jnp.ndarray | None = None,   # [L, B, R, Hkv, D] staging ring
    ring_v: jnp.ndarray | None = None,
    ring_ok: jnp.ndarray | None = None,  # bool [B, R] lane validity
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused paged-attention decode: page-table walk + ring overlay + SDPA.

    Pool and ring come whole, every layer stacked, and ``layer`` picks
    the one to read: the scalar-prefetched ``layer`` and ``blocks`` drive
    the pool BlockSpecs, so each grid step reads its [ps, Hkv, D] KV block
    of that layer directly from the physical pool, and no layer plane is
    ever sliced out of the stack. Undrained staging-ring lanes join the
    same softmax as a second source. Nothing is gathered or overlaid in
    HBM first — the read-side twin of ``staged_scatter``. Returns
    [B, C, Hq, D].

    TPU tiling: every block's last two dims are whole array dims (heads x
    head_dim, query rows x page rows), so any page size, head count and
    head_dim tile legally. Queries and the mask are regrouped here (GQA
    group folded into the query rows); the kernel writes [B, Hkv, G*C, D]
    and the wrapper restores [B, C, Hq, D].
    """
    b, c, hq, d = q.shape
    ps, hkv = pages_k.shape[2], pages_k.shape[3]
    n_pages = blocks.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    gc = group * c
    assert view_ok.shape == (b, c, n_pages * ps), (view_ok.shape, n_pages, ps)
    ring = ring_k is not None
    if ring:
        r = ring_k.shape[2]
        assert ring_ok is not None and ring_ok.shape == (b, r)

    qg = (q.reshape(b, c, hkv, group, d).transpose(0, 2, 3, 1, 4)
          .reshape(b, hkv, gc, d))
    mask = (view_ok.reshape(b, c, n_pages, ps).transpose(0, 2, 1, 3)
            .astype(jnp.int32))
    mask = jnp.tile(mask, (1, 1, group, 1))            # [B, P, G*C, ps]

    kv_spec = pl.BlockSpec(
        (pl.Squeezed(), 1, ps, hkv, d),
        lambda b_, j, tab, lay: (lay[0], tab[b_, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, hkv, gc, d), lambda b_, j, tab, lay: (b_, 0, 0, 0)),
        kv_spec,
        kv_spec,
        pl.BlockSpec((1, 1, gc, ps), lambda b_, j, tab, lay: (b_, j, 0, 0)),
    ]
    args = [qg, pages_k, pages_v, mask]
    if ring:
        lane_spec = pl.BlockSpec(
            (pl.Squeezed(), 1, r, hkv, d),
            lambda b_, j, tab, lay: (lay[0], b_, 0, 0, 0))
        in_specs += [
            lane_spec, lane_spec,
            pl.BlockSpec((1, 1, r), lambda b_, j, tab, lay: (b_, 0, 0)),
        ]
        args += [ring_k, ring_v, ring_ok.astype(jnp.int32).reshape(b, 1, r)]

    fn = pl.pallas_call(
        functools.partial(
            _paged_kernel, n_pages=n_pages, scale=d ** -0.5, ring=ring,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, hkv, gc, d), lambda b_, j, tab, lay: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hkv, gc, 1), jnp.float32),   # running max
                pltpu.VMEM((hkv, gc, 1), jnp.float32),   # denominator
                pltpu.VMEM((hkv, gc, d), jnp.float32),   # weighted sum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gc, d), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )
    out = fn(blocks, jnp.reshape(layer, (1,)).astype(jnp.int32), *args)
    return (out.reshape(b, hkv, group, c, d).transpose(0, 3, 1, 2, 4)
            .reshape(b, c, hq, d))


def flash_decode_paged_sharded(
    mesh,
    q: jnp.ndarray,         # [B, C, Hq, D]
    pages_k: jnp.ndarray,   # [L, n_blocks, ps, Hkv, D]
    pages_v: jnp.ndarray,
    layer: jnp.ndarray,     # int32 scalar              (replicated)
    blocks: jnp.ndarray,    # int32 [B, P]              (replicated)
    view_ok: jnp.ndarray,   # bool [B, C, P*ps]         (replicated)
    ring_k: jnp.ndarray | None = None,   # [L, B, R, Hkv, D]
    ring_v: jnp.ndarray | None = None,
    ring_ok: jnp.ndarray | None = None,  # bool [B, R]   (replicated)
    *,
    axis: str = "model",
    interpret: bool = False,
) -> jnp.ndarray:
    """Head-sharded :func:`flash_decode_paged`: one kernel instance per
    shard via ``shard_map``, each walking the SAME page table over its
    own head slice of the pool/ring (the per-shard read grid of
    DESIGN.md §9).

    Both Hq and Hkv must divide the ``axis`` size so every shard holds
    whole GQA groups — softmax is per-q-head, so no cross-shard combine
    is needed and the result is bitwise the unsharded kernel's. Routing
    inputs (``layer``, ``blocks``, ``view_ok``, ``ring_ok``) are
    replicated; KV-carrying tensors split on their head axis (axis 2 of
    the queries, axis 3 of the stacked pool and ring).
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[axis]
    if tp == 1:
        return flash_decode_paged(q, pages_k, pages_v, layer, blocks,
                                  view_ok, ring_k, ring_v, ring_ok,
                                  interpret=interpret)
    hq, hkv = q.shape[2], pages_k.shape[3]
    if hq % tp or hkv % tp:
        raise ValueError(
            f"flash_decode_paged_sharded: axis {axis!r} of {tp} must "
            f"divide both Hq={hq} and Hkv={hkv} (whole GQA groups per "
            f"shard); use a divisible mesh or the unsharded kernel")
    q_spec = P(None, None, axis, None)
    kv_spec = P(None, None, None, axis, None)
    in_specs = [q_spec, kv_spec, kv_spec, P(), P(None, None),
                P(None, None, None)]
    args = [q, pages_k, pages_v, jnp.asarray(layer, jnp.int32), blocks,
            view_ok]
    ring = ring_k is not None
    if ring:
        in_specs += [kv_spec, kv_spec, P(None, None)]
        args += [ring_k, ring_v, ring_ok]

    def shard_fn(*xs):
        return flash_decode_paged(*xs, interpret=interpret)

    # pallas_call has no replication rule — skip the check
    sm = jax.shard_map(shard_fn, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=q_spec, check_vma=False)
    return sm(*args)


def flash_decode(
    q: jnp.ndarray,        # [B, Hq, D]
    k: jnp.ndarray,        # [B, T, Hkv, D]
    v: jnp.ndarray,        # [B, T, Hkv, D]
    kv_mask: jnp.ndarray,  # bool [B, T]
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    bk = min(block_k, t)
    assert t % bk == 0, (t, bk)
    n_kv = t // bk

    grid = (b, hq, n_kv)
    fn = pl.pallas_call(
        functools.partial(
            _decode_kernel, bk=bk, n_kv=n_kv, scale=d ** -0.5, group=group
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b_, h, j: (b_, h, 0)),
            pl.BlockSpec((1, bk, 1, d), lambda b_, h, j: (b_, j, h // group, 0)),
            pl.BlockSpec((1, bk, 1, d), lambda b_, h, j: (b_, j, h // group, 0)),
            pl.BlockSpec((1, bk), lambda b_, h, j: (b_, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b_, h, j: (b_, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    return fn(q, k, v, kv_mask.astype(jnp.int32))
