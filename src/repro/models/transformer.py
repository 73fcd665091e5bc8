"""Decoder-only transformer stack (dense + VLM cross-attention variants).

Structure
---------
* Parameters for repeated layers are STACKED along a leading layer axis and
  the stack runs under ``lax.scan`` — HLO size is O(1) in depth, which keeps
  the 40-cell dry-run (and real 1000-node compiles) tractable.
* VLM (llama-3.2-vision style): every ``cross_attn_every``-th layer is a
  gated cross-attention layer over (stub) image embeddings. The scan runs
  over GROUPS of ``cross_attn_every`` layers: (every-1) self layers
  (inner scan) + 1 cross layer.
* The decode path takes a ``kv_writer`` (see ``repro.kvcache``) so KV-cache
  insertion can be routed through the uRDMA write engine (direct scatter =
  offload path, staged ring append + drain = unload path).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kvcache import paged as PG
from ..kvcache import staged as ST
from . import layers as L
from .scan import get_scan

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_dense_block(cfg: ModelConfig, key: jax.Array) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(cfg, k1),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(cfg, k2),
    }


def dense_block(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    mask: Optional[jnp.ndarray],
) -> jnp.ndarray:
    x = x + L.attention(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], x), positions, mask=mask)
    x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    return x


def init_cross_block(cfg: ModelConfig, key: jax.Array) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(cfg, k1),
        "gate_attn": jnp.zeros((), jnp.float32),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(cfg, k2),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


def cross_block(
    cfg: ModelConfig, p: Params, x: jnp.ndarray, media: jnp.ndarray
) -> jnp.ndarray:
    """Gated cross-attention layer (llama-3.2-vision style)."""
    h = L.attention(
        cfg, p["attn"], L.apply_norm(cfg, p["ln1"], x), positions=None,
        kv_x=media, use_rope=False,
    )
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
    h = L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * h
    return x


def stack_init(init_fn, key: jax.Array, n: int) -> Params:
    """Initialize ``n`` blocks with independent keys, stacked on axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# Decode-time KV handling
# ---------------------------------------------------------------------------


def direct_kv_write(kc, vc, k_new, v_new, slots):
    """Default (offload-path) writer: per-sequence scatter.

    kc/vc: [B, S, Hkv, Dh]; k_new/v_new: [B, 1, Hkv, Dh]; slots: int32 [B].
    Out-of-range slots (>= S) are DROPPED — the adaptive path uses this to
    suppress the main-cache write for staged sequences.
    """
    b = kc.shape[0]
    rows = jnp.arange(b)
    kc = kc.at[rows, slots].set(k_new[:, 0].astype(kc.dtype), mode="drop")
    vc = vc.at[rows, slots].set(v_new[:, 0].astype(vc.dtype), mode="drop")
    return kc, vc


def cache_slots(cfg: ModelConfig, pos: jnp.ndarray, cache_len: int) -> jnp.ndarray:
    """Ring addressing for SWA caches; linear otherwise."""
    if cfg.sliding_window and cache_len <= cfg.sliding_window:
        return (pos % cache_len).astype(jnp.int32)
    return jnp.minimum(pos, cache_len - 1).astype(jnp.int32)


def valid_mask(cfg: ModelConfig, pos: jnp.ndarray, cache_len: int) -> jnp.ndarray:
    """bool [B, S]: which cache slots hold live keys after writing at ``pos``.

    Linear cache: slots 0..pos. SWA ring: all slots once pos >= cache_len-1,
    else slots 0..pos.
    """
    slot_ids = jnp.arange(cache_len)[None, :]
    linear = slot_ids <= pos[:, None]
    if cfg.sliding_window and cache_len <= cfg.sliding_window:
        full = (pos[:, None] >= cache_len - 1)
        return jnp.where(full, True, linear)
    return linear


def _reference_view(pk, pv, rk, rv, layer, view_ids):
    """The reference read path's per-slot KV set: layer ``layer`` of the
    whole pool planes gathered through the page table, then that layer's
    ring lanes appended (``rk``/``rv`` None without a ring)."""
    ak = PG.gather_view(pk[layer], view_ids)
    av = PG.gather_view(pv[layer], view_ids)
    if rk is not None:
        ak = jnp.concatenate([ak, rk[layer]], axis=1)
        av = jnp.concatenate([av, rv[layer]], axis=1)
    return ak, av


# ---------------------------------------------------------------------------
# DecoderLM: dense + VLM
# ---------------------------------------------------------------------------


class DecoderLM:
    """Dense decoder-only LM; with ``cfg.cross_attn_every`` also covers VLM."""

    def __init__(self, cfg: ModelConfig, unroll: bool = False):
        self.cfg = cfg
        self._scan = get_scan(unroll)
        self.is_vlm = cfg.cross_attn_every > 0
        if self.is_vlm:
            assert cfg.n_layers % cfg.cross_attn_every == 0
            self.n_groups = cfg.n_layers // cfg.cross_attn_every
            self.n_self_per_group = cfg.cross_attn_every - 1
        else:
            self.n_groups = cfg.n_layers
            self.n_self_per_group = 1

    # -- init ------------------------------------------------------------
    def init(self, key: jax.Array, max_seq: int = 0) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_cross = jax.random.split(key, 3)
        params: Params = {"embed": L.init_embed(cfg, k_emb), "ln_f": L.init_norm(cfg)}
        if self.is_vlm:
            n_self = self.n_groups * self.n_self_per_group
            params["blocks"] = stack_init(partial(init_dense_block, cfg), k_blocks, n_self)
            params["cross_blocks"] = stack_init(
                partial(init_cross_block, cfg), k_cross, self.n_groups
            )
        else:
            params["blocks"] = stack_init(
                partial(init_dense_block, cfg), k_blocks, cfg.n_layers
            )
        return L.as_param_dtype(cfg, params)

    # -- full forward (train / prefill) -----------------------------------
    def _trunk(
        self,
        params: Params,
        x: jnp.ndarray,
        positions: jnp.ndarray,
        media: Optional[jnp.ndarray],
        remat: bool,
    ) -> jnp.ndarray:
        cfg = self.cfg
        mask = L.causal_mask(x.shape[1], x.shape[1], cfg.sliding_window)

        def self_body(carry, p):
            return dense_block(cfg, p, carry, positions, mask), None

        if remat:
            self_body = jax.checkpoint(self_body, prevent_cse=False)

        if not self.is_vlm:
            x, _ = self._scan(self_body, x, params["blocks"])
            return x

        nspg = self.n_self_per_group
        grouped = jax.tree.map(
            lambda a: a.reshape((self.n_groups, nspg) + a.shape[1:]), params["blocks"]
        )

        def group_body(carry, ps):
            self_ps, cross_p = ps
            h, _ = self._scan(self_body, carry, self_ps)
            h = cross_block(cfg, cross_p, h, media)
            return h, None

        if remat:
            group_body = jax.checkpoint(group_body, prevent_cse=False)
        x, _ = self._scan(group_body, x, (grouped, params["cross_blocks"]))
        return x

    def forward(
        self,
        params: Params,
        tokens: jnp.ndarray,
        media: Optional[jnp.ndarray] = None,
        remat: bool = False,
    ) -> jnp.ndarray:
        """tokens [B, S] -> logits [B, S, V] (fp32)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        if media is not None:
            media = media.astype(dtype)
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        )
        x = self._trunk(params, x, positions, media, remat)
        x = L.apply_norm(cfg, params["ln_f"], x)
        return L.lm_logits(cfg, params["embed"], x)

    def loss(self, params: Params, batch: Dict[str, jnp.ndarray], remat: bool = True):
        logits = self.forward(params, batch["tokens"], batch.get("media"), remat=remat)
        return L.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))

    # -- KV cache ----------------------------------------------------------
    def cache_len(self, max_seq: int) -> int:
        cfg = self.cfg
        if cfg.sliding_window:
            return min(max_seq, cfg.sliding_window)
        return max_seq

    def init_cache(self, batch: int, max_seq: int, dtype=None) -> Params:
        """Abstract-shape-friendly KV cache pytree."""
        cfg = self.cfg
        dims = L.attn_dims(cfg)
        dtype = dtype or jnp.dtype(cfg.dtype)
        s = self.cache_len(max_seq)
        n_layers = (
            self.n_groups * self.n_self_per_group if self.is_vlm else cfg.n_layers
        )
        cache = {
            "k": jnp.zeros((n_layers, batch, s, dims.n_kv_heads, dims.head_dim), dtype),
            "v": jnp.zeros((n_layers, batch, s, dims.n_kv_heads, dims.head_dim), dtype),
        }
        if self.is_vlm:
            cache["cross_k"] = jnp.zeros(
                (self.n_groups, batch, cfg.n_image_tokens, dims.n_kv_heads, dims.head_dim),
                dtype,
            )
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        return cache

    def prefill(
        self,
        params: Params,
        tokens: jnp.ndarray,
        max_seq: int,
        media: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Params]:
        """Run the full prompt, build the cache, return last-token logits.

        Dry-run note: prefill writes the whole prompt's KV in one dense slice
        (the offload/direct path — prefill writes are contiguous, exactly the
        case the paper keeps offloaded).
        """
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        if media is not None:
            media = media.astype(dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        mask = L.causal_mask(s, s, cfg.sliding_window)
        cache = self.init_cache(b, max_seq, dtype)
        clen = self.cache_len(max_seq)

        def keep_ring(k):
            """Last ``clen`` positions, placed at slot = pos % clen."""
            if k.shape[1] < clen:
                pad = [(0, 0), (0, clen - k.shape[1]), (0, 0), (0, 0)]
                return jnp.pad(k, pad)
            tail = k[:, -clen:]
            shift = s % clen
            return jnp.roll(tail, shift, axis=1) if shift else tail

        def self_body(carry, p):
            h = carry
            hn = L.apply_norm(cfg, p["ln1"], h)
            k, v = L.project_kv(cfg, p["attn"], hn, positions)
            h = dense_block(cfg, p, h, positions, mask)
            # keep the last `clen` positions (ring semantics for SWA)
            return h, (keep_ring(k), keep_ring(v))

        if not self.is_vlm:
            x, (ks, vs) = self._scan(self_body, x, params["blocks"])
            cache["k"], cache["v"] = ks, vs
        else:
            nspg = self.n_self_per_group
            grouped = jax.tree.map(
                lambda a: a.reshape((self.n_groups, nspg) + a.shape[1:]),
                params["blocks"],
            )

            def group_body(carry, ps):
                self_ps, cross_p = ps
                h, kv = self._scan(self_body, carry, self_ps)
                ck, cv = L.project_kv(cfg, cross_p["attn"], media, None)
                h = cross_block(cfg, cross_p, h, media)
                return h, (kv, (ck, cv))

            x, (kv, cross_kv) = self._scan(group_body, x, (grouped, params["cross_blocks"]))
            ks, vs = kv
            cache["k"] = ks.reshape((-1,) + ks.shape[2:])
            cache["v"] = vs.reshape((-1,) + vs.shape[2:])
            cache["cross_k"], cache["cross_v"] = cross_kv

        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, cache

    # -- chunked prefill -----------------------------------------------------
    def chunk_prefill(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,   # [B, C] one chunk
        start_pos: int,        # static: absolute position of tokens[:, 0]
        media: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Params]:
        """Chunked prefill: process C prompt tokens against the running
        cache (memory O(C * S) instead of O(S^2) — the prefill_32k path).

        Chunk KV writes are dense slice updates — the offload/direct path;
        the paper (and this engine) only unloads small scattered writes.
        """
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, c = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        if media is not None:
            media = media.astype(dtype)
        positions = jnp.broadcast_to(
            start_pos + jnp.arange(c, dtype=jnp.int32), (b, c)
        )
        clen = cache["k"].shape[2]
        spos = L.slot_positions(clen, start_pos + c - 1)

        def self_body(carry, xs):
            h = carry
            p, kc, vc = xs
            hn = L.apply_norm(cfg, p["ln1"], h)
            k_new, v_new = L.project_kv(cfg, p["attn"], hn, positions)
            kc = L.write_chunk(kc, k_new, start_pos)
            vc = L.write_chunk(vc, v_new, start_pos)
            h = h + L.chunk_attention(cfg, p["attn"], hn, positions, kc, vc, spos)
            h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
            return h, (kc, vc)

        if not self.is_vlm:
            x, (ks, vs) = self._scan(
                self_body, x, (params["blocks"], cache["k"], cache["v"])
            )
            new_cache = dict(cache, k=ks, v=vs)
        else:
            nspg = self.n_self_per_group
            grouped = jax.tree.map(
                lambda a: a.reshape((self.n_groups, nspg) + a.shape[1:]),
                params["blocks"],
            )
            kc_g = cache["k"].reshape((self.n_groups, nspg) + cache["k"].shape[1:])
            vc_g = cache["v"].reshape((self.n_groups, nspg) + cache["v"].shape[1:])

            def group_body(carry, xs):
                self_ps, cross_p, kcs, vcs = xs
                h, kv = self._scan(self_body, carry, (self_ps, kcs, vcs))
                ck, cv = L.project_kv(cfg, cross_p["attn"], media, None)
                h = cross_block(cfg, cross_p, h, media)
                return h, (kv, (ck, cv))

            x, (kv, cross_kv) = self._scan(
                group_body, x, (grouped, params["cross_blocks"], kc_g, vc_g)
            )
            ks, vs = kv
            new_cache = dict(
                cache,
                k=ks.reshape((-1,) + ks.shape[2:]),
                v=vs.reshape((-1,) + vs.shape[2:]),
                cross_k=cross_kv[0],
                cross_v=cross_kv[1],
            )

        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache

    # -- decode (paged pool) -----------------------------------------------
    def _scan_paged(self, body, x, params: Params, cache: Params):
        """Run ``body`` over the layer stack with the paged pool (and the
        staging ring) in the scan's CARRY, next to the activations.

        ``body(h, (pages_k, pages_v, ring_k, ring_v), p, layer)`` gets the
        whole planes (ring entries None without a ring) and its layer
        index, writes that layer's rows in place and returns
        ``(h, planes)``. Only the layer parameters and the index ride the
        scan's xs and nothing rides its ys, so no per-layer plane is ever
        sliced out of the pool and restacked. Returns (x, cache with the
        new planes)."""
        keys = ("pages_k", "pages_v", "ring_k", "ring_v")
        planes = tuple(cache.get(k) for k in keys)
        n_layers = cache["pages_k"].shape[0]

        def step(carry, xs):
            return body(*carry, *xs), None

        (x, planes), _ = self._scan(
            step, (x, planes),
            (params["blocks"], jnp.arange(n_layers, dtype=jnp.int32)))
        return x, dict(cache, **{k: v for k, v in zip(keys, planes)
                                 if v is not None})

    def decode_step_paged(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,
        pos: jnp.ndarray,
        write_mask: jnp.ndarray,
        unload_mask: Optional[jnp.ndarray] = None,
        attention: str = "reference",
        plan: Optional[PG.StepPlan] = None,
        mesh=None,
    ) -> Tuple[jnp.ndarray, Params]:
        """One decode step against a PAGED KV pool (``repro.kvcache.paged``).

        tokens [B], pos [B] (logical positions, per-slot) -> logits [B, V'],
        new cache. ``write_mask`` [B]: False suppresses every KV write for
        that slot (retired / empty serve slots — their physical destination
        resolves to the drop sentinel, so a dead slot can never touch the
        pool). ``unload_mask`` [B] routes live writes: True = stage into
        the ring overlay (unload path), False/None = direct scatter to the
        slot's physical row (offload path).

        ``attention`` picks the read implementation (negotiate it through
        ``core.paths.resolve_attention``): ``"reference"`` gathers the
        per-slot view from the pool and concatenates the ring in jnp;
        ``"fused"`` hands the whole stacked pool and ring planes, the
        layer index and the scalar-prefetch block table to
        ``flash_decode_paged``, which walks that layer's page table and
        merges both sources inside one softmax — no gathered view ever
        materializes. Pool and ring ride the layer scan's carry and each
        layer writes its rows in place (``_scan_paged``). The two share
        one op order and agree to fp32 ulp precision with identical
        greedy tokens (the reference is the kernel's oracle; DESIGN.md §7
        has the parity contract). ``plan`` threads per-segment
        hoisted page-table products (``PG.step_plan``); when None it is
        derived here. ``mesh`` is the serving mesh of a head-sharded pool
        (the fused kernel then runs per shard).

        The per-slot attention view is gathered from the pool through the
        page table each step — values are identical to the dense cache
        layout, so paged decode is bit-compatible with ``decode_step``.
        Linear addressing only: SWA ring addressing and the VLM family
        stay on the dense-lane path (see DESIGN.md §Arch-applicability).
        """
        cfg = self.cfg
        if self.is_vlm or cfg.sliding_window:
            raise NotImplementedError(
                "paged KV decode covers linear-addressed dense caches; "
                "SWA/VLM serve from dense lanes (DESIGN.md §Arch-applicability)"
            )
        fused = attention == "fused"
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope("head"):
            x = L.embed_tokens(cfg, params["embed"], tokens[:, None], dtype)
        ring = PG.has_ring(cache)
        with jax.named_scope("kv_view"):
            if plan is None:
                plan = PG.step_plan(cache)
            vmask = PG.view_mask_from(plan.allocated, pos)
            view_ids = plan.view_ids
            if ring:
                if unload_mask is None:
                    unload_mask = jnp.ones_like(write_mask)
                unload_mask = unload_mask & write_mask
                view_ok, ring_ok, cur = PG.overlay_step_parts(
                    cache, vmask, pos, unload_mask)
                full_mask = jnp.concatenate([view_ok, ring_ok], axis=1)
                direct = write_mask & ~unload_mask
            else:
                view_ok = full_mask = vmask
                ring_ok = None
                direct = write_mask
        # physical destination for the direct subset; sentinel (-1 logical
        # -> out-of-range physical) DROPS staged and dead slots
        with jax.named_scope("kv_write"):
            dest = PG.logical_to_physical(cache, jnp.where(direct, pos, -1))

        def self_body(h, planes, p, l):
            pk, pv, rk, rv = planes
            with jax.named_scope("attention"):
                hn = L.apply_norm(cfg, p["ln1"], h)
                k_new, v_new = L.project_kv(cfg, p["attn"], hn, pos[:, None])
            with jax.named_scope("kv_write"):
                pk = PG.scatter_token(pk, l, dest, k_new[:, 0])
                pv = PG.scatter_token(pv, l, dest, v_new[:, 0])
                if ring:
                    rk = PG.stage_tile(rk, l, k_new[:, 0], cur)
                    rv = PG.stage_tile(rv, l, v_new[:, 0], cur)
            if fused:
                with jax.named_scope("attention"):
                    a = L.fused_paged_attention(
                        cfg, p["attn"], hn, pos[:, None], pk, pv, l,
                        plan.blocks, view_ok[:, None, :], rk, rv, ring_ok,
                        mesh=mesh)
            else:
                with jax.named_scope("kv_view"):
                    ak, av = _reference_view(pk, pv, rk, rv, l, view_ids)
                with jax.named_scope("attention"):
                    a = L.decode_attention(cfg, p["attn"], hn, pos, ak, av,
                                           full_mask)
            h = h + a
            with jax.named_scope("mlp"):
                h = h + L.apply_mlp(cfg, p["mlp"],
                                    L.apply_norm(cfg, p["ln2"], h))
            return h, (pk, pv, rk, rv)

        x, new_cache = self._scan_paged(self_body, x, params, cache)
        if ring:
            with jax.named_scope("kv_write"):
                new_cache = PG.ring_commit(new_cache, pos, unload_mask)

        with jax.named_scope("head"):
            x = L.apply_norm(cfg, params["ln_f"], x)
            logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache

    # -- mixed-phase chunk step (paged pool) --------------------------------
    def decode_chunk_paged(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,      # int32 [B, C] token slab
        start: jnp.ndarray,       # int32 [B] logical row/position of column 0
        n_valid: jnp.ndarray,     # int32 [B] live columns (chunk len | 1 | 0)
        write_mask: jnp.ndarray,  # bool [B] gates every KV write
        unload_mask: Optional[jnp.ndarray] = None,
        attention: str = "reference",
        plan: Optional[PG.StepPlan] = None,
        all_logits: bool = False,
        mesh=None,
    ) -> Tuple[jnp.ndarray, Params]:
        """One MIXED-PHASE step against the paged pool: each slot processes
        a [C]-token slab — a prefill chunk (``n_valid`` prompt tokens from
        its chunk cursor), a single decode token (``n_valid == 1``, column
        0), or nothing (``n_valid == 0``, retired/stalled). Column ``j`` of
        slot ``b`` sits at logical row/position ``start[b] + j``.

        Chunk KV writes are dense consecutive rows — the bulk/offload path
        (``unload_mask`` may stage only the scattered column-0 decode
        write). Returns (logits [B, V'] taken at each slot's LAST valid
        column — the sampling position for both phases — and the new
        cache). ``all_logits=True`` instead returns logits [B, C, V'] at
        EVERY column: column ``j`` is the target distribution for
        position ``start + j + 1``, which is exactly what the
        speculative k-token verify consumes (a verify IS a chunked step
        that keeps all its sampling positions).

        Bit-parity: chunk rows land in the pool before the per-slot view is
        gathered, and every projection/reduction matches the whole-prompt
        ``prefill`` + ``decode_step_paged`` pair, so a prompt prefilled in
        chunks decodes the same token stream as one prefilled whole.
        """
        cfg = self.cfg
        if self.is_vlm or cfg.sliding_window:
            raise NotImplementedError(
                "paged KV decode covers linear-addressed dense caches; "
                "SWA/VLM serve from dense lanes (DESIGN.md §Arch-applicability)"
            )
        fused = attention == "fused"
        dtype = jnp.dtype(cfg.dtype)
        b, c = tokens.shape
        with jax.named_scope("head"):
            x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        wvalid = (jnp.arange(c)[None, :] < n_valid[:, None]) & write_mask[:, None]
        ring = PG.has_ring(cache)
        with jax.named_scope("kv_view"):
            if plan is None:
                plan = PG.step_plan(cache)
            if ring:
                if unload_mask is None:
                    unload_mask = jnp.zeros((b,), jnp.bool_)
                unload_mask = unload_mask & wvalid[:, 0]
                view_ok, ring_lane_ok, cur = PG.overlay_chunk_parts(
                    cache, positions, unload_mask, allocated=plan.allocated)
                r = ring_lane_ok.shape[1]
                full_mask = jnp.concatenate(
                    [view_ok,
                     jnp.broadcast_to(ring_lane_ok[:, None, :], (b, c, r))],
                    axis=2)
                direct = wvalid & ~unload_mask[:, None]
            else:
                view_ok = full_mask = PG.view_chunk_mask_from(plan.allocated,
                                                              positions)
                ring_lane_ok = None
                direct = wvalid
            view_ids = plan.view_ids
        with jax.named_scope("kv_write"):
            dest = PG.logical_to_physical_many(
                cache, jnp.where(direct, positions, -1))

        def self_body(h, planes, p, l):
            pk, pv, rk, rv = planes
            with jax.named_scope("attention"):
                hn = L.apply_norm(cfg, p["ln1"], h)
                k_new, v_new = L.project_kv(cfg, p["attn"], hn, positions)
            with jax.named_scope("kv_write"):
                pk = PG.scatter_chunk(pk, l, dest, k_new)
                pv = PG.scatter_chunk(pv, l, dest, v_new)
                if ring:
                    rk = PG.stage_tile(rk, l, k_new[:, 0], cur)
                    rv = PG.stage_tile(rv, l, v_new[:, 0], cur)
            if fused:
                with jax.named_scope("attention"):
                    a = L.fused_paged_attention(
                        cfg, p["attn"], hn, positions, pk, pv, l,
                        plan.blocks, view_ok, rk, rv, ring_lane_ok,
                        mesh=mesh)
            else:
                with jax.named_scope("kv_view"):
                    ak, av = _reference_view(pk, pv, rk, rv, l, view_ids)
                with jax.named_scope("attention"):
                    a = L.masked_chunk_attention(
                        cfg, p["attn"], hn, positions, ak, av, full_mask)
            h = h + a
            with jax.named_scope("mlp"):
                h = h + L.apply_mlp(cfg, p["mlp"],
                                    L.apply_norm(cfg, p["ln2"], h))
            return h, (pk, pv, rk, rv)

        x, new_cache = self._scan_paged(self_body, x, params, cache)
        if ring:
            with jax.named_scope("kv_write"):
                new_cache = PG.ring_commit(new_cache, start, unload_mask)

        with jax.named_scope("head"):
            if all_logits:
                x = L.apply_norm(cfg, params["ln_f"], x)
                return L.lm_logits(cfg, params["embed"], x), new_cache
            # logits at each slot's last valid column: the final prompt
            # token (prefill, phase-flip sampling) or the decode token
            # (column 0)
            sel = jnp.clip(n_valid - 1, 0)[:, None, None]
            x = jnp.take_along_axis(x, sel, axis=1)
            x = L.apply_norm(cfg, params["ln_f"], x)
            logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache

    # -- decode ------------------------------------------------------------
    def decode_step(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,
        pos: jnp.ndarray,
        kv_writer=direct_kv_write,
        unload_mask: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Params]:
        """One decode step. tokens [B], pos [B] -> logits [B, V], new cache.

        KV-write routing (the uRDMA integration):
        * plain cache -> ``kv_writer`` (default: direct scatter = offload
          path);
        * cache with a staging ring (``repro.kvcache.staged.add_ring``) ->
          ``unload_mask`` [B] routes each sequence: True = append to the
          ring (unload path; attention reads cache ∪ ring, the serve loop
          drains in bulk), False = direct scatter. The decision module
          supplies the mask from page-frequency counters.
        """
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b = tokens.shape[0]
        x = L.embed_tokens(cfg, params["embed"], tokens[:, None], dtype)
        clen = cache["k"].shape[2]
        slots = cache_slots(cfg, pos, clen)
        vmask = valid_mask(cfg, pos, clen)

        has_ring = "ring_k" in cache
        if has_ring and self.is_vlm:
            raise NotImplementedError(
                "staging-ring KV overlay is wired for the dense family; "
                "VLM decode uses the direct path (DESIGN.md §Arch-applicability)"
            )
        if has_ring:
            if unload_mask is None:
                unload_mask = jnp.ones((b,), jnp.bool_)
            # unified-ring overlay bookkeeping: attention mask over
            # cache ∪ ring, direct-subset slots (sentinel drops staged
            # sequences), and the ring column this step appends to
            full_mask, direct_slots, cur = ST.overlay_step(
                cache, vmask, slots, unload_mask
            )
        else:
            full_mask = vmask
            direct_slots = slots

        def self_body(carry, xs):
            h = carry
            if has_ring:
                p, kc, vc, rk, rv = xs
            else:
                p, kc, vc = xs
            hn = L.apply_norm(cfg, p["ln1"], h)
            k_new, v_new = L.project_kv(cfg, p["attn"], hn, pos[:, None])
            if has_ring:
                kc, vc = kv_writer(kc, vc, k_new, v_new, direct_slots)
                rk = ST.stage_tile(rk, k_new, cur)
                rv = ST.stage_tile(rv, v_new, cur)
                ak = jnp.concatenate([kc, rk], axis=1)
                av = jnp.concatenate([vc, rv], axis=1)
                a = L.decode_attention(cfg, p["attn"], hn, pos, ak, av, full_mask)
                h = h + a
                h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
                return h, (kc, vc, rk, rv)
            kc, vc = kv_writer(kc, vc, k_new, v_new, direct_slots)
            a = L.decode_attention(cfg, p["attn"], hn, pos, kc, vc, full_mask)
            h = h + a
            h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
            return h, (kc, vc)

        if has_ring and not self.is_vlm:
            x, (ks, vs, rks, rvs) = self._scan(
                self_body, x,
                (params["blocks"], cache["k"], cache["v"],
                 cache["ring_k"], cache["ring_v"]),
            )
            new_cache = ST.ring_commit(
                dict(cache, k=ks, v=vs, ring_k=rks, ring_v=rvs),
                slots, unload_mask,
            )
        elif not self.is_vlm:
            x, (ks, vs) = self._scan(self_body, x, (params["blocks"], cache["k"], cache["v"]))
            new_cache = dict(cache, k=ks, v=vs)
        else:
            nspg = self.n_self_per_group
            grouped = jax.tree.map(
                lambda a: a.reshape((self.n_groups, nspg) + a.shape[1:]),
                params["blocks"],
            )
            kc_g = cache["k"].reshape((self.n_groups, nspg) + cache["k"].shape[1:])
            vc_g = cache["v"].reshape((self.n_groups, nspg) + cache["v"].shape[1:])

            def group_body(carry, xs):
                self_ps, cross_p, kcs, vcs, ck, cv = xs
                h, kv = self._scan(self_body, carry, (self_ps, kcs, vcs))
                # cross attention against precomputed image KV
                hn = L.apply_norm(cfg, cross_p["ln1"], h)
                a = L.decode_attention(
                    cfg, cross_p["attn"], hn, pos, ck, cv,
                    jnp.ones((b, ck.shape[1]), jnp.bool_), use_rope=False,
                )
                h = h + jnp.tanh(cross_p["gate_attn"]).astype(dtype) * a
                m = L.apply_mlp(cfg, cross_p["mlp"], L.apply_norm(cfg, cross_p["ln2"], h))
                h = h + jnp.tanh(cross_p["gate_mlp"]).astype(dtype) * m
                return h, kv

            x, (ks, vs) = self._scan(
                group_body,
                x,
                (grouped, params["cross_blocks"], kc_g, vc_g,
                 cache["cross_k"], cache["cross_v"]),
            )
            new_cache = dict(
                cache,
                k=ks.reshape((-1,) + ks.shape[2:]),
                v=vs.reshape((-1,) + vs.shape[2:]),
            )

        x = L.apply_norm(cfg, params["ln_f"], x)
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache
