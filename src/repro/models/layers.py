"""Shared model building blocks: norms, rotary, attention (GQA / SWA /
QKV-bias / cross), and MLP variants (swiglu / squared-relu / gelu).

Everything is a pure function over explicit parameter pytrees (nested dicts
of jnp arrays) so stacks compose under ``lax.scan`` and shard under pjit.

Conventions
-----------
* Activations: [B, S, D] (batch, sequence, model).
* Attention heads: q [B, S, Hq, Dh]; kv [B, S, Hkv, Dh] (GQA: Hq % Hkv == 0).
* Softmax and norms accumulate in float32 regardless of compute dtype.
* Init functions take a PRNG key and return the parameter dict; shapes only
  depend on the config so ``jax.eval_shape`` can derive abstract params for
  the dry-run without allocating.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import GELU, LAYERNORM, RMSNORM, SQUARED_RELU, SWIGLU, ModelConfig

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def as_param_dtype(cfg: ModelConfig, params):
    """Every float leaf in ``cfg.param_dtype``, the dtype parameters are
    stored in; each use casts to what it computes in."""
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(
        lambda x: (x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
                   else x), params)


def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == LAYERNORM:
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == RMSNORM:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    elif cfg.norm == LAYERNORM:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"] + p["bias"]
    else:
        raise ValueError(cfg.norm)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (with partial-rotary support)
# ---------------------------------------------------------------------------


def rope_frequencies(cfg: ModelConfig, head_dim: int) -> jnp.ndarray:
    """inv_freq [rot_half] for the rotated fraction of the head dim."""
    rot = int(head_dim * cfg.rope_fraction)
    rot -= rot % 2
    if rot == 0:
        return jnp.zeros((0,), jnp.float32)
    exponent = jnp.arange(0, rot, 2, dtype=jnp.float32) / rot
    return 1.0 / (cfg.rope_theta ** exponent)


def apply_rope(
    cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray
) -> jnp.ndarray:
    """Rotate the first ``rope_fraction`` of the head dim.

    x: [B, S, H, Dh]; positions: [B, S] absolute token positions (int32).
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(cfg, head_dim)
    rot = 2 * inv_freq.shape[0]
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    # angles: [B, S, rot/2]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([rotated, x_pass], axis=-1) if x_pass.shape[-1] else rotated


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int


def attn_dims(cfg: ModelConfig) -> AttnDims:
    return AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim)


def init_attention(cfg: ModelConfig, key: jax.Array) -> dict:
    """GQA attention parameters. Shapes keep the head axis explicit so the
    sharding rules can target heads or head_dim depending on divisibility."""
    dims = attn_dims(cfg)
    d, hq, hkv, hd = cfg.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, hq, hd)) * scale).astype(jnp.float32),
        "wk": (jax.random.normal(ks[1], (d, hkv, hd)) * scale).astype(jnp.float32),
        "wv": (jax.random.normal(ks[2], (d, hkv, hd)) * scale).astype(jnp.float32),
        "wo": (jax.random.normal(ks[3], (hq, hd, d)) * (hq * hd) ** -0.5).astype(
            jnp.float32
        ),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), jnp.float32)
        p["bk"] = jnp.zeros((hkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((hkv, hd), jnp.float32)
    return p


def _qkv(cfg: ModelConfig, p: dict, x: jnp.ndarray, kv_x: Optional[jnp.ndarray] = None):
    """Project to q, k, v. ``kv_x`` (if given) is the cross-attention source."""
    dtype = x.dtype
    kv_src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    k = jnp.einsum("btd,dhk->bthk", kv_src, p["wk"].astype(dtype))
    v = jnp.einsum("btd,dhk->bthk", kv_src, p["wv"].astype(dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return q, k, v


def repeat_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """Expand [B, T, Hkv, Dh] -> [B, T, Hq, Dh] for GQA."""
    hkv = k.shape[2]
    if hkv == n_heads:
        return k
    reps = n_heads // hkv
    return jnp.repeat(k, reps, axis=2)


# above this many score elements per batch entry, sdpa processes queries in
# blocks so the fp32 score tensor never materializes at [S, T] (the XLA
# fallback for the TPU flash_attention kernel; same math, bounded temps)
_SDPA_BLOCK_THRESHOLD = 4096 * 2048
_SDPA_QBLOCK = 1024


def _tp_head_pad(h: int) -> int:
    """Padded head count for tensor parallelism (0 = no padding needed).

    When the head count does not divide the "model" axis (qwen2: 28H,
    granite: 24H over TP=16), attention pads heads to the next multiple
    with ZERO q/k/v rows — Megatron-style TP padding, applied to the
    ACTIVATIONS only (params keep the paper-exact head count; padded head
    outputs are sliced off, so the math is exact). Costs h_pad/h extra
    attention FLOPs; buys head-sharded score tensors.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return 0
    m = mesh.shape["model"]
    if h % m == 0:
        return 0
    return (h + m - 1) // m * m


def _shard_heads(x: jnp.ndarray) -> jnp.ndarray:
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P(None, None, "model", None))


def _sdpa_once(q, k, v, mask, scale):
    logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
) -> jnp.ndarray:
    """Scaled dot-product attention, fp32 softmax.

    q: [B, S, H, Dh]; k/v: [B, T, H, Dh]; mask: broadcastable to [B, H, S, T]
    (True = attend). Returns [B, S, H, Dh].

    Long sequences run BLOCKED over queries (exact per-block softmax — the
    full key set is present, so no online rescaling is needed): temp memory
    is O(BQ * T) instead of O(S * T). On TPU the Pallas flash kernel
    replaces this path; the blocked form is the roofline-accountable XLA
    fallback with the same asymptotics in HBM traffic.
    """
    scale = q.shape[-1] ** -0.5
    s, t = q.shape[1], k.shape[1]

    # TP head padding (see _tp_head_pad): keeps score tensors head-sharded
    # for architectures whose head count doesn't divide the model axis.
    h = q.shape[2]
    hp = _tp_head_pad(h)
    if hp:
        pad = [(0, 0), (0, 0), (0, hp - h), (0, 0)]
        q = _shard_heads(jnp.pad(q, pad))
        k = _shard_heads(jnp.pad(k, pad))
        v = _shard_heads(jnp.pad(v, pad))

    if s * t <= _SDPA_BLOCK_THRESHOLD or s <= _SDPA_QBLOCK or s % _SDPA_QBLOCK:
        out = _sdpa_once(q, k, v, mask, scale)
        return out[:, :, :h] if hp else out
    outs = []
    for i in range(0, s, _SDPA_QBLOCK):
        qb = q[:, i : i + _SDPA_QBLOCK]
        mb = None
        if mask is not None:
            mb = mask[:, :, i : i + _SDPA_QBLOCK] if mask.ndim == 4 else mask
        outs.append(_sdpa_once(qb, k, v, mb, scale))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :, :h] if hp else out


def causal_mask(s: int, t: int, window: int = 0, offset: int = 0) -> jnp.ndarray:
    """[1, 1, s, t] causal (optionally sliding-window) mask.

    ``offset``: absolute position of query row 0 minus key col 0 (for
    decode / chunked prefill where queries start mid-sequence).
    """
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None]


def attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    kv_x: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
    use_rope: bool = True,
) -> jnp.ndarray:
    """Full (training / prefill) attention. Causal unless ``kv_x`` given."""
    dims = attn_dims(cfg)
    q, k, v = _qkv(cfg, p, x, kv_x)
    if use_rope and kv_x is None:
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, positions)
    k = repeat_kv(k, dims.n_heads)
    v = repeat_kv(v, dims.n_heads)
    if mask is None and kv_x is None:
        mask = causal_mask(x.shape[1], k.shape[1], cfg.sliding_window)
    out = sdpa(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def project_q(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,          # [B, S, D]
    positions: Optional[jnp.ndarray],  # [B, S] absolute query positions
    use_rope: bool = True,
) -> jnp.ndarray:
    """Query projection (+ bias + RoPE) for decode-time attention.

    THE one q path shared by the jnp attention cores below and the fused
    ``flash_decode_paged`` read kernel — both implementations consume
    bit-identical queries, so fused-vs-reference parity reduces to the
    attention core itself.
    """
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
    if use_rope:
        q = apply_rope(cfg, q, positions)
    return q


def decode_attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    pos: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    kv_len_mask: jnp.ndarray,
    use_rope: bool = True,
) -> jnp.ndarray:
    """One-token decode against a (possibly sequence-sharded) KV cache.

    x: [B, 1, D]; pos: [B] absolute positions of the new token;
    k_cache/v_cache: [B, S, Hkv, Dh] — already contain the new token's kv;
    kv_len_mask: bool [B, S] marking valid cache slots (handles both linear
    fill and SWA ring occupancy).

    The softmax reduction runs over the cache's sequence axis; under pjit
    with the cache sequence-sharded over "model", GSPMD partitions the
    max/sum reductions into the flash-decode partial-softmax + combine
    pattern automatically.
    """
    dims = attn_dims(cfg)
    dtype = x.dtype
    q = project_q(cfg, p, x, pos[:, None], use_rope)
    k = repeat_kv(k_cache, dims.n_heads)
    v = repeat_kv(v_cache, dims.n_heads)
    mask = kv_len_mask[:, None, None, :]  # [B, 1, 1, S]
    out = sdpa(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def masked_chunk_attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,          # [B, C, D] normed chunk activations
    positions: jnp.ndarray,  # [B, C] absolute query positions
    k_cache: jnp.ndarray,    # [B, T, Hkv, Dh] gathered KV set
    v_cache: jnp.ndarray,
    mask: jnp.ndarray,       # bool [B, C, T] explicit validity (True=attend)
    use_rope: bool = True,
) -> jnp.ndarray:
    """Chunk queries against a gathered KV set with an EXPLICIT mask.

    The mixed-phase serving step attends per-slot chunk windows over the
    paged pool view (∪ staging ring), whose validity depends on page-table
    allocation and ring shadowing — structure the caller owns. With C=1
    and ``mask = kv_len_mask[:, None, :]`` this is bit-identical to
    :func:`decode_attention` (same projections, same reduction shapes up
    to the query axis).
    """
    dims = attn_dims(cfg)
    dtype = x.dtype
    q = project_q(cfg, p, x, positions, use_rope)
    k = repeat_kv(k_cache, dims.n_heads)
    v = repeat_kv(v_cache, dims.n_heads)
    out = sdpa(q, k, v, mask[:, None])  # [B, 1, C, T]
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def fused_paged_attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,          # [B, C, D] normed activations (C=1 for step)
    positions: jnp.ndarray,  # [B, C] absolute query positions
    pages_k: jnp.ndarray,    # [L, n_blocks, ps, Hkv, Dh] physical pool
    pages_v: jnp.ndarray,
    layer: jnp.ndarray,      # int32 scalar: the layer of pool and ring read
    blocks: jnp.ndarray,     # int32 [B, P] clamped physical block ids
    view_ok: jnp.ndarray,    # bool [B, C, P*ps]
    ring_k: Optional[jnp.ndarray] = None,   # [L, B, R, Hkv, Dh] staging ring
    ring_v: Optional[jnp.ndarray] = None,
    ring_ok: Optional[jnp.ndarray] = None,  # bool [B, R]
    use_rope: bool = True,
    impl: str = "auto",
    mesh=None,
) -> jnp.ndarray:
    """Decode attention through the ``flash_decode_paged`` read kernel.

    The fused twin of :func:`decode_attention` / :func:`masked_chunk_attention`
    over a paged pool: the kernel walks the page table and overlays the
    staging ring inside one softmax, so no gathered view is materialized.
    Pool and ring come whole (every layer) and the kernel indexes
    ``layer`` itself, so no per-layer plane is sliced out of them.
    Projections (``project_q``) and the output einsum are shared with the
    jnp cores — fused and reference differ ONLY in the attention core,
    which the kernel holds to ulp-level fp32 parity (identical greedy
    tokens; DESIGN.md §7). Under a serving ``mesh`` the pool is
    head-sharded and the kernel runs once per shard (``shard_map``): a
    Mosaic kernel is never partitioned by the compiler.
    """
    from ..kernels import flash_decode_paged, flash_decode_paged_sharded

    dtype = x.dtype
    q = project_q(cfg, p, x, positions, use_rope)   # [B, C, Hq, Dh]
    if mesh is not None:
        out = flash_decode_paged_sharded(mesh, q, pages_k, pages_v, layer,
                                         blocks, view_ok, ring_k, ring_v,
                                         ring_ok)
    else:
        out = flash_decode_paged(q, pages_k, pages_v, layer, blocks, view_ok,
                                 ring_k, ring_v, ring_ok, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def slot_positions(clen: int, last_pos: int) -> jnp.ndarray:
    """Absolute position stored in each cache slot after writing ``last_pos``.

    Works for both linear caches (slot == position) and SWA rings
    (slot = position % clen): negative results mark not-yet-written slots.
    """
    s = jnp.arange(clen)
    phase = last_pos % clen
    return last_pos - ((phase - s) % clen)


def chunk_attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,          # [B, C, D] normed chunk activations
    positions: jnp.ndarray,  # [B, C] absolute query positions
    k_cache: jnp.ndarray,    # [B, clen, Hkv, Dh] (chunk keys already written)
    v_cache: jnp.ndarray,
    slot_pos: jnp.ndarray,   # [clen] absolute position per slot (<0 invalid)
    use_rope: bool = True,
) -> jnp.ndarray:
    """Chunked-prefill attention: C queries against the full cache.

    Memory is O(C * clen) — this is what makes prefill_32k lowerable
    (C=2048 vs the 32k^2 scores of one-shot prefill).
    """
    dims = attn_dims(cfg)
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
    if use_rope:
        q = apply_rope(cfg, q, positions)
    k = repeat_kv(k_cache, dims.n_heads)
    v = repeat_kv(v_cache, dims.n_heads)
    qpos = positions[:, None, :, None]          # [B, 1, C, 1]
    kpos = slot_pos[None, None, None, :]        # [1, 1, 1, clen]
    mask = (kpos <= qpos) & (kpos >= 0)
    if cfg.sliding_window:
        mask &= kpos > qpos - cfg.sliding_window
    out = sdpa(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def write_chunk(cache: jnp.ndarray, chunk: jnp.ndarray, start_pos: int) -> jnp.ndarray:
    """Write a [B, C, H, Dh] chunk into cache slots (ring-aware, contiguous).

    Chunk writes are the offload/direct path by construction: they are
    dense slice updates (the paper keeps large/contiguous writes offloaded).
    """
    b, c = chunk.shape[:2]
    clen = cache.shape[1]
    s0 = start_pos % clen
    if c >= clen:
        # chunk covers the whole ring: keep the last clen positions, rolled
        tail = chunk[:, -clen:]
        shift = (start_pos + c) % clen
        return jnp.roll(tail, shift, axis=1) if shift else tail
    if s0 + c <= clen:
        return jax.lax.dynamic_update_slice(cache, chunk, (0, s0, 0, 0))
    first = clen - s0
    cache = jax.lax.dynamic_update_slice(cache, chunk[:, :first], (0, s0, 0, 0))
    return jax.lax.dynamic_update_slice(cache, chunk[:, first:], (0, 0, 0, 0))


def project_kv(
    cfg: ModelConfig, p: dict, x: jnp.ndarray, positions: Optional[jnp.ndarray]
):
    """k, v for cache insertion (decode writes / cross-attn precompute)."""
    dtype = x.dtype
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(dtype))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(dtype))
    if "bk" in p:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if positions is not None:
        k = apply_rope(cfg, k, positions)
    return k, v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key: jax.Array, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "wi": (jax.random.normal(ks[0], (d, f)) * d ** -0.5).astype(jnp.float32),
        "wo": (jax.random.normal(ks[1], (f, d)) * f ** -0.5).astype(jnp.float32),
    }
    if cfg.activation == SWIGLU:
        p["wg"] = (jax.random.normal(ks[2], (d, f)) * d ** -0.5).astype(jnp.float32)
    return p


def apply_mlp(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    dtype = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(dtype))
    if cfg.activation == SWIGLU:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(dtype))
        h = jax.nn.silu(g) * h
    elif cfg.activation == SQUARED_RELU:
        h = jnp.square(jax.nn.relu(h))
    elif cfg.activation == GELU:
        h = jax.nn.gelu(h)
    else:
        raise ValueError(cfg.activation)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------


def init_embed(cfg: ModelConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 2)
    p = {
        "tok": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02).astype(
            jnp.float32
        )
    }
    if not cfg.tie_embeddings:
        p["head"] = (
            jax.random.normal(ks[1], (cfg.vocab, cfg.d_model))
            * cfg.d_model ** -0.5
        ).astype(jnp.float32)
    return p


def shard_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain a [B, ...] activation to batch sharding over the data axes.

    The embedding table is D-sharded (lookup locality), so its output
    inherits a D-sharded layout; without this constraint the layer scan's
    saved residuals keep that layout and GSPMD falls back to full
    rematerialization (replicating [B, S, D] per layer). One constraint at
    the residual stream's source pins the whole scan to batch sharding.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if not dp or x.shape[0] % size:
        return x
    from jax.sharding import PartitionSpec as P

    spec = dp if len(dp) > 1 else dp[0]
    # two-step reshard: batch-shard while KEEPING the last dim sharded, then
    # all-gather the last dim. The direct one-step reshard trips an SPMD
    # partitioner bug ("slice dim size > dynamic slice dimension") on some
    # gather outputs.
    if (
        x.ndim == 3
        and "model" in mesh.axis_names
        and x.shape[-1] % mesh.shape["model"] == 0
    ):
        x = jax.lax.with_sharding_constraint(x, P(spec, None, "model"))
    return jax.lax.with_sharding_constraint(
        x, P(spec, *((None,) * (x.ndim - 1)))
    )


def embed_tokens(cfg: ModelConfig, p: dict, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    # batch-shard the INDICES first: the gather then natively produces a
    # (batch, D-shard) layout, and shard_batch only all-gathers D — without
    # this, resharding the gather's batch dim trips an SPMD replicate-
    # fallback bug on some shapes.
    tokens = shard_batch(tokens)
    return shard_batch(p["tok"].astype(dtype)[tokens])


def lm_logits(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final logits in float32 (loss numerics).

    TP vocab padding: odd vocab sizes (whisper 51865, granite 49155,
    mamba2 50280) cannot shard over the model axis, which would REPLICATE
    the [B, S, V] fp32 logits on every model rank. Under a mesh, the head
    matrix is zero-padded to the next multiple of the axis and the padded
    lanes are masked to -inf — logsumexp/softmax/argmax are all exact, and
    the logits shard.
    """
    w = p["tok"] if cfg.tie_embeddings else p["head"]
    v = w.shape[0]
    vp = 0
    mesh = jax.sharding.get_abstract_mesh()
    if "model" in mesh.axis_names:
        m = mesh.shape["model"]
        if v % m:
            vp = (v + m - 1) // m * m
    if vp:
        w = jnp.pad(w, ((0, vp - v), (0, 0)))
    logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), w.astype(jnp.float32))
    if vp:
        from jax.sharding import PartitionSpec as P

        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(lane < v, logits, jnp.float32(-1e30))
        logits = jax.lax.with_sharding_constraint(
            logits, P(*((None,) * (logits.ndim - 1)), "model")
        )
    return logits


def cross_entropy_loss(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Mean token cross-entropy; logits [B, S, V] fp32, labels int32 [B, S].

    The gold logit is extracted with a where-iota reduction instead of
    ``take_along_axis``: a gather over the (TP-vocab-sharded) logits would
    force SPMD to replicate them; the masked reduction partitions cleanly
    over the vocab axis (one extra elementwise pass, fused by XLA).
    """
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
    )
    nll = logz - gold
    if mask is not None:
        denom = jnp.maximum(jnp.sum(mask), 1)
        return jnp.sum(nll * mask) / denom
    return jnp.mean(nll)
