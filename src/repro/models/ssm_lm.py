"""SSM and hybrid language models.

* ``MambaLM``  — mamba2-130m: pure stack of SSD blocks (attention-free).
* ``ZambaLM``  — zamba2-2.7b: mamba2 trunk with ONE SHARED attention+MLP
  block applied every ``hybrid_attn_every`` layers (zamba2's shared
  transformer block: its weights are reused at every application; each
  application keeps its OWN KV cache at decode time).

Both expose the same API as ``DecoderLM``: init / loss / prefill /
decode_step, with recurrent state (+ per-application KV for zamba) instead
of (or alongside) KV caches — which is what makes ``long_500k`` runnable.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import mamba2 as M
from .scan import get_scan
from .transformer import (
    dense_block,
    direct_kv_write,
    init_dense_block,
    stack_init,
    valid_mask,
)

Params = Dict[str, Any]


class MambaLM:
    """Pure SSD stack (mamba2)."""

    def __init__(self, cfg: ModelConfig, unroll: bool = False):
        self.cfg = cfg
        self._scan = get_scan(unroll)

    def init(self, key: jax.Array, max_seq: int = 0) -> Params:
        cfg = self.cfg
        k_emb, k_blocks = jax.random.split(key)
        return L.as_param_dtype(cfg, {
            "embed": L.init_embed(cfg, k_emb),
            "blocks": stack_init(partial(M.init_mamba_block, cfg), k_blocks, cfg.n_layers),
            "ln_f": L.init_norm(cfg),
        })

    def forward(self, params, tokens, remat: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)

        def body(carry, p):
            y, _, _ = M.mamba_block(cfg, p, carry)
            return y, None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = self._scan(body, x, params["blocks"])
        x = L.apply_norm(cfg, params["ln_f"], x)
        return L.lm_logits(cfg, params["embed"], x)

    def loss(self, params, batch, remat: bool = True):
        logits = self.forward(params, batch["tokens"], remat=remat)
        return L.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))

    # -- recurrent cache --------------------------------------------------
    def init_cache(self, batch: int, max_seq: int = 0, dtype=None) -> Params:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        per_layer = M.init_mamba_state(cfg, batch, dtype)
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), per_layer
        )

    def prefill(self, params, tokens, max_seq: int = 0, media=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)

        def body(carry, p):
            y, st, tail = M.mamba_block(cfg, p, carry)
            return y, (st, tail)

        x, (ssm, conv) = self._scan(body, x, params["blocks"])
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, {"ssm": ssm, "conv": conv}

    def chunk_prefill(self, params, cache, tokens, start_pos: int, media=None):
        """Chunked prefill: run one chunk through the SSD blocks, carrying
        recurrent state in/out (SSM prefill is inherently chunkable)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)

        def body(carry, xs):
            p, ssm, conv = xs
            y, st, tail = M.mamba_block_chunk(cfg, p, carry, ssm, conv)
            return y, (st, tail)

        x, (ssm, conv) = self._scan(
            body, x, (params["blocks"], cache["ssm"], cache["conv"])
        )
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, {"ssm": ssm, "conv": conv}

    def decode_step(self, params, cache, tokens, pos, kv_writer=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens[:, None], dtype)[:, 0]

        def body(carry, xs):
            p, ssm, conv = xs
            y, ssm, conv = M.mamba_decode_step(cfg, p, carry, ssm, conv)
            return y, (ssm, conv)

        x, (ssm, conv) = self._scan(body, x, (params["blocks"], cache["ssm"], cache["conv"]))
        x = L.apply_norm(cfg, params["ln_f"], x[:, None])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, {"ssm": ssm, "conv": conv}


class ZambaLM:
    """Zamba2-style hybrid: mamba2 trunk + shared attention block."""

    def __init__(self, cfg: ModelConfig, unroll: bool = False):
        self.cfg = cfg
        self._scan = get_scan(unroll)
        assert cfg.hybrid_attn_every > 0
        assert cfg.n_layers % cfg.hybrid_attn_every == 0
        self.n_groups = cfg.n_layers // cfg.hybrid_attn_every
        self.per_group = cfg.hybrid_attn_every

    def init(self, key: jax.Array, max_seq: int = 0) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_shared = jax.random.split(key, 3)
        return L.as_param_dtype(cfg, {
            "embed": L.init_embed(cfg, k_emb),
            "blocks": stack_init(partial(M.init_mamba_block, cfg), k_blocks, cfg.n_layers),
            "shared": init_dense_block(cfg, k_shared),  # ONE shared block
            "ln_f": L.init_norm(cfg),
        })

    def _grouped(self, params):
        return jax.tree.map(
            lambda a: a.reshape((self.n_groups, self.per_group) + a.shape[1:]),
            params["blocks"],
        )

    def forward(self, params, tokens, remat: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        mask = L.causal_mask(s, s)
        shared = params["shared"]

        def inner(carry, p):
            y, _, _ = M.mamba_block(cfg, p, carry)
            return y, None

        if remat:
            # checkpoint the inner mamba layers too: a group holds
            # hybrid_attn_every SSD blocks whose in_proj/ssd temps would
            # otherwise all be live during the group's backward pass
            inner = jax.checkpoint(inner, prevent_cse=False)

        def group_body(carry, ps):
            h, _ = self._scan(inner, carry, ps)
            h = dense_block(cfg, shared, h, positions, mask)
            return h, None

        if remat:
            group_body = jax.checkpoint(group_body, prevent_cse=False)
        x, _ = self._scan(group_body, x, self._grouped(params))
        x = L.apply_norm(cfg, params["ln_f"], x)
        return L.lm_logits(cfg, params["embed"], x)

    def loss(self, params, batch, remat: bool = True):
        logits = self.forward(params, batch["tokens"], remat=remat)
        return L.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))

    # -- caches ------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=None) -> Params:
        cfg = self.cfg
        dims = L.attn_dims(cfg)
        dtype = dtype or jnp.dtype(cfg.dtype)
        per_layer = M.init_mamba_state(cfg, batch, dtype)
        cache = jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), per_layer
        )
        cache["k"] = jnp.zeros(
            (self.n_groups, batch, max_seq, dims.n_kv_heads, dims.head_dim), dtype
        )
        cache["v"] = jnp.zeros_like(cache["k"])
        return cache

    def prefill(self, params, tokens, max_seq: int, media=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        mask = L.causal_mask(s, s)
        shared = params["shared"]

        def inner(carry, p):
            y, st, tail = M.mamba_block(cfg, p, carry)
            return y, (st, tail)

        def group_body(carry, ps):
            h, states = self._scan(inner, carry, ps)
            hn = L.apply_norm(cfg, shared["ln1"], h)
            k, v = L.project_kv(cfg, shared["attn"], hn, positions)
            h = dense_block(cfg, shared, h, positions, mask)
            return h, (states, (k, v))

        x, ((ssm, conv), (ks, vs)) = self._scan(group_body, x, self._grouped(params))
        # pad prompt KV out to max_seq cache slots
        if s < max_seq:
            pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
            ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
        cache = {
            "ssm": ssm.reshape((-1,) + ssm.shape[2:]),
            "conv": conv.reshape((-1,) + conv.shape[2:]),
            "k": ks,
            "v": vs,
        }
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, cache

    def chunk_prefill(self, params, cache, tokens, start_pos: int, media=None):
        """Chunked prefill: mamba states carried per layer; the shared
        attention block does chunked attention against its per-application
        KV caches."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, c = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        positions = jnp.broadcast_to(
            start_pos + jnp.arange(c, dtype=jnp.int32), (b, c)
        )
        clen = cache["k"].shape[2]
        spos = L.slot_positions(clen, start_pos + c - 1)
        shared = params["shared"]
        ssm_g = cache["ssm"].reshape(
            (self.n_groups, self.per_group) + cache["ssm"].shape[1:]
        )
        conv_g = cache["conv"].reshape(
            (self.n_groups, self.per_group) + cache["conv"].shape[1:]
        )

        def inner(carry, xs):
            p, ssm, conv = xs
            y, st, tail = M.mamba_block_chunk(cfg, p, carry, ssm, conv)
            return y, (st, tail)

        def group_body(carry, xs):
            ps, ssm, conv, kc, vc = xs
            h, states = self._scan(inner, carry, (ps, ssm, conv))
            hn = L.apply_norm(cfg, shared["ln1"], h)
            k_new, v_new = L.project_kv(cfg, shared["attn"], hn, positions)
            kc = L.write_chunk(kc, k_new, start_pos)
            vc = L.write_chunk(vc, v_new, start_pos)
            h = h + L.chunk_attention(cfg, shared["attn"], hn, positions, kc, vc, spos)
            h = h + L.apply_mlp(cfg, shared["mlp"], L.apply_norm(cfg, shared["ln2"], h))
            return h, (states, (kc, vc))

        x, ((ssm, conv), (ks, vs)) = self._scan(
            group_body, x,
            (self._grouped(params), ssm_g, conv_g, cache["k"], cache["v"]),
        )
        new_cache = {
            "ssm": ssm.reshape((-1,) + ssm.shape[2:]),
            "conv": conv.reshape((-1,) + conv.shape[2:]),
            "k": ks,
            "v": vs,
        }
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache

    def decode_step(self, params, cache, tokens, pos, kv_writer=direct_kv_write):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed_tokens(cfg, params["embed"], tokens[:, None], dtype)[:, 0]
        shared = params["shared"]
        clen = cache["k"].shape[2]
        slots = jnp.minimum(pos, clen - 1).astype(jnp.int32)
        vmask = valid_mask(cfg, pos, clen)
        ssm_g = cache["ssm"].reshape((self.n_groups, self.per_group) + cache["ssm"].shape[1:])
        conv_g = cache["conv"].reshape((self.n_groups, self.per_group) + cache["conv"].shape[1:])

        def inner(carry, xs):
            p, ssm, conv = xs
            y, ssm, conv = M.mamba_decode_step(cfg, p, carry, ssm, conv)
            return y, (ssm, conv)

        def group_body(carry, xs):
            ps, ssm, conv, kc, vc = xs
            h, states = self._scan(inner, carry, (ps, ssm, conv))
            hn = L.apply_norm(cfg, shared["ln1"], h[:, None])
            k_new, v_new = L.project_kv(cfg, shared["attn"], hn, pos[:, None])
            kc, vc = kv_writer(kc, vc, k_new, v_new, slots)
            a = L.decode_attention(cfg, shared["attn"], hn, pos, kc, vc, vmask)[:, 0]
            h = h + a
            h2 = L.apply_mlp(cfg, shared["mlp"], L.apply_norm(cfg, shared["ln2"], h[:, None]))
            h = h + h2[:, 0]
            return h, (states, (kc, vc))

        x, ((ssm, conv), (ks, vs)) = self._scan(
            group_body, x, (self._grouped(params), ssm_g, conv_g, cache["k"], cache["v"])
        )
        new_cache = {
            "ssm": ssm.reshape((-1,) + ssm.shape[2:]),
            "conv": conv.reshape((-1,) + conv.shape[2:]),
            "k": ks,
            "v": vs,
        }
        x = L.apply_norm(cfg, params["ln_f"], x[:, None])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache
