"""MoE language model: GQA attention + dual-path expert dispatch per layer.

The dispatch mode ("direct" = paper's offload path, "staged" = unload path,
"adaptive" = decision-module routing with expert-hotness counters) is a
runtime attribute; the adaptive hot-mask is produced by
``repro.core.decision.expert_hot_mask`` from monitor counters carried in the
train/serve state — the paper's frequency policy, verbatim, applied to
expert ids instead of 4 KB pages.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import moe as MOE
from .scan import get_scan
from .transformer import cache_slots, direct_kv_write, stack_init, valid_mask

Params = Dict[str, Any]


class MoELM:
    """Decoder-only MoE LM with uRDMA dual-path dispatch."""

    def __init__(self, cfg: ModelConfig, dispatch_mode: str = "staged",
                 unroll: bool = False):
        self.cfg = cfg
        self._scan = get_scan(unroll)
        self.dispatch_mode = dispatch_mode

    def init(self, key: jax.Array, max_seq: int = 0) -> Params:
        cfg = self.cfg
        k_emb, k_blocks = jax.random.split(key)
        return L.as_param_dtype(cfg, {
            "embed": L.init_embed(cfg, k_emb),
            "blocks": stack_init(partial(MOE.init_moe_block, cfg), k_blocks, cfg.n_layers),
            "ln_f": L.init_norm(cfg),
        })

    # -- full forward --------------------------------------------------------
    def forward_with_stats(
        self,
        params: Params,
        tokens: jnp.ndarray,
        hot_mask: Optional[jnp.ndarray] = None,
        remat: bool = False,
        mode: Optional[str] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """-> (logits [B,S,V], aux_loss scalar, expert_load [L, E])."""
        cfg = self.cfg
        mode = mode or self.dispatch_mode
        dtype = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        mask = L.causal_mask(s, s, cfg.sliding_window)

        def body(carry, p):
            h, aux_acc = carry
            h, aux, load = MOE.moe_block(cfg, p, h, positions, mask, mode, hot_mask)
            return (h, aux_acc + aux), load

        body_fn = jax.checkpoint(body, prevent_cse=False) if remat else body
        (x, aux), loads = self._scan(body_fn, (x, jnp.zeros((), jnp.float32)), params["blocks"])
        x = L.apply_norm(cfg, params["ln_f"], x)
        return L.lm_logits(cfg, params["embed"], x), aux, loads

    def forward(self, params, tokens, media=None, remat: bool = False, hot_mask=None):
        logits, _, _ = self.forward_with_stats(params, tokens, hot_mask, remat)
        return logits

    def loss(self, params, batch, remat: bool = True, hot_mask=None, mode=None):
        logits, aux, _ = self.forward_with_stats(
            params, batch["tokens"], hot_mask, remat, mode
        )
        ce = L.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))
        return ce + aux

    def loss_with_stats(self, params, batch, remat: bool = True, hot_mask=None, mode=None):
        """Returns (loss, expert_load [L, E]) — load feeds the monitor."""
        logits, aux, loads = self.forward_with_stats(
            params, batch["tokens"], hot_mask, remat, mode
        )
        ce = L.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))
        return ce + aux, loads

    # -- caches ---------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=None) -> Params:
        cfg = self.cfg
        dims = L.attn_dims(cfg)
        dtype = dtype or jnp.dtype(cfg.dtype)
        return {
            "k": jnp.zeros((cfg.n_layers, batch, max_seq, dims.n_kv_heads, dims.head_dim), dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_seq, dims.n_kv_heads, dims.head_dim), dtype),
        }

    def prefill(self, params, tokens, max_seq: int, media=None, hot_mask=None):
        cfg = self.cfg
        mode = self.dispatch_mode
        dtype = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        mask = L.causal_mask(s, s, cfg.sliding_window)

        def body(carry, p):
            h = carry
            hn = L.apply_norm(cfg, p["ln1"], h)
            k, v = L.project_kv(cfg, p["attn"], hn, positions)
            h, _, _ = MOE.moe_block(cfg, p, h, positions, mask, mode, hot_mask)
            return h, (k, v)

        x, (ks, vs) = self._scan(body, x, params["blocks"])
        if s < max_seq:
            pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
            ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
        cache = {"k": ks, "v": vs}
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, cache

    def chunk_prefill(self, params, cache, tokens, start_pos: int, media=None,
                      hot_mask=None):
        """Chunked prefill (see DecoderLM.chunk_prefill) with MoE FFNs."""
        cfg = self.cfg
        mode = self.dispatch_mode
        dtype = jnp.dtype(cfg.dtype)
        b, c = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = jnp.broadcast_to(
            start_pos + jnp.arange(c, dtype=jnp.int32), (b, c)
        )
        clen = cache["k"].shape[2]
        spos = L.slot_positions(clen, start_pos + c - 1)

        def body(carry, xs):
            h = carry
            p, kc, vc = xs
            hn = L.apply_norm(cfg, p["ln1"], h)
            k_new, v_new = L.project_kv(cfg, p["attn"], hn, positions)
            kc = L.write_chunk(kc, k_new, start_pos)
            vc = L.write_chunk(vc, v_new, start_pos)
            h = h + L.chunk_attention(cfg, p["attn"], hn, positions, kc, vc, spos)
            m, _, _ = MOE.moe_ffn_layer(
                cfg, p["moe"], L.apply_norm(cfg, p["ln2"], h), mode, hot_mask
            )
            return h + m, (kc, vc)

        x, (ks, vs) = self._scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache = dict(cache, k=ks, v=vs)
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache

    def decode_step(
        self, params, cache, tokens, pos, kv_writer=direct_kv_write, hot_mask=None
    ):
        cfg = self.cfg
        mode = self.dispatch_mode
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens[:, None], dtype)
        clen = cache["k"].shape[2]
        slots = cache_slots(cfg, pos, clen)
        vmask = valid_mask(cfg, pos, clen)

        def body(carry, xs):
            h = carry
            p, kc, vc = xs
            hn = L.apply_norm(cfg, p["ln1"], h)
            k_new, v_new = L.project_kv(cfg, p["attn"], hn, pos[:, None])
            kc, vc = kv_writer(kc, vc, k_new, v_new, slots)
            h = h + L.decode_attention(cfg, p["attn"], hn, pos, kc, vc, vmask)
            m, _, _ = MOE.moe_ffn_layer(
                cfg, p["moe"], L.apply_norm(cfg, p["ln2"], h), mode, hot_mask
            )
            return h + m, (kc, vc)

        x, (ks, vs) = self._scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache = dict(cache, k=ks, v=vs)
        x = L.apply_norm(cfg, params["ln_f"], x)
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache
