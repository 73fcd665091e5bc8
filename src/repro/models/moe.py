"""Mixture-of-Experts layer with uRDMA-style dual-path dispatch.

This is the PRIMARY integration point of the paper's technique into training
(DESIGN.md §3): dispatching a token to an expert is a "remote write" into a
sharded per-expert buffer, and we provide both paths:

* ``direct``  (paper: OFFLOAD path) — every token-expert assignment is
  scattered straight into the per-expert buffer at a dynamically computed
  slot. Destinations are effectively random (like RDMA writes to arbitrary
  registered regions): XLA lowers this to an unsorted scatter whose cost
  grows with destination irregularity — the MTT-miss analogue.
* ``staged``  (paper: UNLOAD path) — assignments are first SORTED by
  destination expert (the "staging ring": a contiguous, sequentially-written
  buffer), then drained into expert-major order with a regular, perfectly
  tiled copy (the target-CPU memcpy analogue; Pallas kernel
  ``repro.kernels.staged_scatter`` implements the drain on TPU).
* ``adaptive`` — the decision module routes each assignment: assignments to
  HOT experts (heavy-hitter counters, exactly the paper's frequency policy)
  take the direct path — they reuse "cached" destinations; assignments to
  cold experts are staged. Both sub-paths are fixed-shape so the adaptive
  layer jits and shards.

Expert-load counters double as the monitor state: the router updates them
every step, and ``repro.core.policy`` consumes them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L

Params = Dict[str, jnp.ndarray]

DISPATCH_MODES = ("direct", "staged", "adaptive")


def _constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint that no-ops outside a mesh context and
    drops axes that don't divide the corresponding dim."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    fixed = []
    for dim, s in zip(x.shape, spec):
        if isinstance(s, str) and s in mesh.axis_names and dim % mesh.shape[s] == 0:
            fixed.append(s)
        else:
            fixed.append(None)
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P(*fixed))


def buf_constraint(buf: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Expert-buffer sharding: EP over "model" when E divides it, else the
    capacity dim over "data" (keeps dispatch scatters shard-local-ish)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return buf
    if "model" in mesh.axis_names and n_experts % mesh.shape["model"] == 0:
        return _constrain(buf, "model", None, None)
    return _constrain(buf, None, "data", None)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_moe_mlp(cfg: ModelConfig, key: jax.Array) -> Params:
    """Stacked expert SwiGLU weights + router."""
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, f)) * d ** -0.5).astype(jnp.float32),
        "wg": (jax.random.normal(ks[2], (e, d, f)) * d ** -0.5).astype(jnp.float32),
        "wo": (jax.random.normal(ks[3], (e, f, d)) * f ** -0.5).astype(jnp.float32),
    }


def init_moe_block(cfg: ModelConfig, key: jax.Array) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(cfg, k1),
        "ln2": L.init_norm(cfg),
        "moe": init_moe_mlp(cfg, k2),
    }


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def route(
    cfg: ModelConfig, p: Params, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing.

    x: [T, D] flat tokens. Returns (expert_idx [T,K], weights [T,K],
    aux_loss scalar, expert_load [E] int32 — the monitor counter update).
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # load-balance auxiliary loss (Switch-style): E * sum_e f_e * p_e
    e = cfg.n_experts
    assign_onehot = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    f_e = jnp.mean(assign_onehot, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(f_e * p_e)

    load = jnp.zeros((e,), jnp.int32).at[idx.reshape(-1)].add(1)
    return idx, weights.astype(x.dtype), aux, load


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Static per-expert capacity, rounded up to a lane-friendly multiple."""
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, (c + 7) // 8 * 8)


# ---------------------------------------------------------------------------
# Expert FFN over packed buffers
# ---------------------------------------------------------------------------


def expert_ffn(cfg: ModelConfig, p: Params, buf: jnp.ndarray) -> jnp.ndarray:
    """buf [E, C, D] -> [E, C, D], SwiGLU per expert (batched einsum)."""
    dtype = buf.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dtype))
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# Path 1: DIRECT dispatch (offload analogue) — unsorted random scatter
# ---------------------------------------------------------------------------


def dispatch_direct(
    x: jnp.ndarray,
    expert_idx: jnp.ndarray,
    keep: jnp.ndarray,
    capacity: int,
    n_experts: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter assignments straight into per-expert buffers.

    x: [T, D]; expert_idx/keep: [T, K]. Returns (buffer [E, C, D],
    slot [T, K] — the slot each kept assignment landed in, -1 if dropped).

    The slot for each assignment is its rank among same-expert assignments
    (computed with a cumulative one-hot — the straightforward "just post the
    write" structure of the offload path). The scatter's destination order
    is data-dependent and unsorted.
    """
    t, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    onehot = onehot * keep.reshape(-1, 1).astype(jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot  # rank among same-expert
    slot = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
    ok = keep.reshape(-1) & (slot < capacity)
    # sentinel = E*C (out of range -> dropped); -1 would WRAP to the last slot
    flat_dst = jnp.where(ok, flat_e * capacity + slot, n_experts * capacity)
    x_rep = jnp.repeat(x, k, axis=0)  # [T*K, D]
    x_rep = _constrain(x_rep, "data", None)
    buf = jnp.zeros((n_experts * capacity, x.shape[1]), x.dtype)
    buf = buf.at[flat_dst].set(x_rep, mode="drop", unique_indices=True)
    buf = buf_constraint(buf.reshape(n_experts, capacity, x.shape[1]), n_experts)
    return buf, jnp.where(ok, slot, -1).reshape(t, k)


def combine_direct(
    out_buf: jnp.ndarray,
    expert_idx: jnp.ndarray,
    slot: jnp.ndarray,
    weights: jnp.ndarray,
) -> jnp.ndarray:
    """Gather expert outputs back to token order and mix with router weights."""
    e, c, d = out_buf.shape
    flat = out_buf.reshape(e * c, d)
    idx = expert_idx * c + jnp.maximum(slot, 0)
    gathered = flat[idx]  # [T, K, D]
    w = jnp.where(slot >= 0, weights, 0.0)[..., None].astype(out_buf.dtype)
    return jnp.sum(gathered * w, axis=1)


# ---------------------------------------------------------------------------
# Path 2: STAGED dispatch (unload analogue) — sort into staging, then drain
# ---------------------------------------------------------------------------


def dispatch_staged(
    x: jnp.ndarray,
    expert_idx: jnp.ndarray,
    keep: jnp.ndarray,
    capacity: int,
    n_experts: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort assignments by destination expert, then drain contiguously.

    The sorted assignment list IS the staging buffer: writes into it are
    sequential appends. The drain to expert-major [E, C, D] order then only
    moves contiguous runs (per-expert segments) — a regular copy that the
    ``staged_scatter`` Pallas kernel performs with dense VMEM tiles.

    Returns (buffer [E, C, D], sort_perm [T*K], slot [T, K]).
    """
    t, k = expert_idx.shape
    tk = t * k
    flat_e = jnp.where(keep.reshape(-1), expert_idx.reshape(-1), n_experts)
    # staging append: stable sort by destination expert
    perm = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[perm]
    # rank within expert segment = position - segment start
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts + 1))
    slot_sorted = jnp.arange(tk, dtype=jnp.int32) - seg_start[sorted_e]
    ok = (sorted_e < n_experts) & (slot_sorted < capacity)
    # sentinel = E*C (out of range -> dropped); -1 would WRAP to the last slot
    dst = jnp.where(ok, sorted_e * capacity + slot_sorted, n_experts * capacity)

    token_sorted = perm // k
    staged = x[token_sorted]  # [T*K, D] — contiguous staging buffer content
    staged = _constrain(staged, "data", None)
    buf = jnp.zeros((n_experts * capacity, x.shape[1]), x.dtype)
    # drain: destination indices are monotonically increasing — XLA sees a
    # sorted scatter (on TPU: repro.kernels.staged_scatter does this copy).
    buf = buf.at[dst].set(staged, mode="drop", unique_indices=True)
    buf = buf_constraint(buf.reshape(n_experts, capacity, x.shape[1]), n_experts)

    # per-assignment slot in ORIGINAL order (for combine): invert the perm
    inv = jnp.zeros((tk,), jnp.int32).at[perm].set(jnp.arange(tk, dtype=jnp.int32))
    slot_orig = jnp.where(ok, slot_sorted, -1)[inv].reshape(t, k)
    return buf, perm, slot_orig


# ---------------------------------------------------------------------------
# MoE layer with path selection
# ---------------------------------------------------------------------------


def moe_ffn_layer(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    mode: str = "staged",
    hot_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] -> (y [B, S, D], aux_loss, expert_load [E]).

    mode:
      direct   — offload path for all assignments
      staged   — unload path for all assignments
      adaptive — hot_mask [E] (from the decision module / expert-hotness
                 counters) sends hot-expert assignments direct, cold staged.
    """
    if mode not in DISPATCH_MODES:
        raise ValueError(f"mode {mode!r} not in {DISPATCH_MODES}")
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    tcount = b * s
    idx, weights, aux, load = route(cfg, p, xt)
    cap = expert_capacity(cfg, tcount)
    keep = jnp.ones_like(idx, jnp.bool_)

    # TP expert padding: when E doesn't divide the model axis (granite: 40
    # over TP=16), pad the expert dimension with zero-weight experts so the
    # dispatch buffers shard EP-style instead of replicating. Padded experts
    # never receive assignments (router logits only span the real E).
    n_experts = cfg.n_experts
    mesh = jax.sharding.get_abstract_mesh()
    if "model" in mesh.axis_names:
        m = mesh.shape["model"]
        if n_experts % m:
            n_experts = (n_experts + m - 1) // m * m
    if n_experts != cfg.n_experts:
        epad = n_experts - cfg.n_experts
        p = dict(
            p,
            wi=jnp.pad(p["wi"], ((0, epad), (0, 0), (0, 0))),
            wg=jnp.pad(p["wg"], ((0, epad), (0, 0), (0, 0))),
            wo=jnp.pad(p["wo"], ((0, epad), (0, 0), (0, 0))),
        )
    cfg_moe = cfg if n_experts == cfg.n_experts else dataclasses.replace(
        cfg, n_experts=n_experts
    )

    if mode == "direct":
        buf, slot = dispatch_direct(xt, idx, keep, cap, n_experts)
        out = expert_ffn(cfg_moe, p, buf)
        y = combine_direct(out, idx, slot, weights)
    elif mode == "staged":
        buf, _, slot = dispatch_staged(xt, idx, keep, cap, n_experts)
        out = expert_ffn(cfg_moe, p, buf)
        y = combine_direct(out, idx, slot, weights)
    else:  # adaptive: split assignments by destination hotness
        if hot_mask is None:
            raise ValueError("adaptive mode needs hot_mask [E]")
        assign_hot = hot_mask[idx]  # [T, K]
        # both sub-paths run fixed-shape on disjoint assignment subsets
        buf_h, slot_h = dispatch_direct(xt, idx, assign_hot, cap, n_experts)
        buf_c, _, slot_c = dispatch_staged(xt, idx, ~assign_hot, cap, n_experts)
        out = expert_ffn(cfg_moe, p, buf_h + buf_c)  # disjoint slots -> one FFN pass
        y_h = combine_direct(out, idx, slot_h, weights)
        y_c = combine_direct(out, idx, slot_c, weights)
        y = y_h + y_c

    return y.reshape(b, s, d), aux, load


def moe_block(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    mask,
    mode: str = "staged",
    hot_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full MoE transformer block: GQA attention + MoE FFN."""
    x = x + L.attention(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], x), positions, mask=mask)
    h, aux, load = moe_ffn_layer(
        cfg, p["moe"], L.apply_norm(cfg, p["ln2"], x), mode, hot_mask
    )
    return x + h, aux, load
