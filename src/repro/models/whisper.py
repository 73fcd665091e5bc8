"""Whisper-style encoder-decoder backbone (audio frontend stubbed).

Per the assignment, the mel-spectrogram conv frontend is a STUB:
``input_specs()`` supplies precomputed frame embeddings [B, F, D]. The
transformer backbone is complete: a bidirectional encoder over frames and a
causal decoder with cross-attention, GELU MLPs, LayerNorm, and learned
absolute positions (no rotary).

Decode-time caches: growing self-attention KV (routable through the uRDMA
write engine) + static cross-attention KV precomputed from the encoder.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from .scan import get_scan
from .transformer import direct_kv_write, init_dense_block, stack_init, valid_mask

Params = Dict[str, Any]


def init_decoder_block(cfg: ModelConfig, key: jax.Array) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.init_norm(cfg),
        "self_attn": L.init_attention(cfg, k1),
        "ln2": L.init_norm(cfg),
        "cross_attn": L.init_attention(cfg, k2),
        "ln3": L.init_norm(cfg),
        "mlp": L.init_mlp(cfg, k3),
    }


def decoder_block(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    enc_out: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    x = x + L.attention(
        cfg, p["self_attn"], L.apply_norm(cfg, p["ln1"], x), None,
        mask=mask, use_rope=False,
    )
    x = x + L.attention(
        cfg, p["cross_attn"], L.apply_norm(cfg, p["ln2"], x), None,
        kv_x=enc_out, use_rope=False,
    )
    x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln3"], x))
    return x


class WhisperModel:
    """Enc-dec backbone with the DecoderLM-compatible API."""

    def __init__(self, cfg: ModelConfig, unroll: bool = False):
        self.cfg = cfg
        self._scan = get_scan(unroll)

    def init(self, key: jax.Array, max_seq: int) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        n_pos = max(cfg.max_position, max_seq)
        return L.as_param_dtype(cfg, {
            "embed": L.init_embed(cfg, ks[0]),
            "enc_pos": (jax.random.normal(ks[1], (cfg.n_audio_frames, cfg.d_model)) * 0.01
                        ).astype(jnp.float32),
            "dec_pos": (jax.random.normal(ks[2], (n_pos, cfg.d_model)) * 0.01
                        ).astype(jnp.float32),
            "enc_blocks": stack_init(partial(init_dense_block, cfg), ks[3], cfg.n_enc_layers),
            "ln_enc": L.init_norm(cfg),
            "dec_blocks": stack_init(partial(init_decoder_block, cfg), ks[4], cfg.n_layers),
            "ln_f": L.init_norm(cfg),
        })

    # -- encoder -----------------------------------------------------------
    def encode(self, params: Params, frames: jnp.ndarray, remat: bool = False):
        """frames: [B, F, D] stub embeddings -> encoder output [B, F, D]."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = frames.astype(dtype) + params["enc_pos"].astype(dtype)[None, : frames.shape[1]]
        positions = jnp.zeros(frames.shape[:2], jnp.int32)  # unused (no rope)

        def body(carry, p):
            h = carry
            h = h + L.attention(
                cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h), positions,
                mask=None, use_rope=False,
            )
            h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
            return h, None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = self._scan(body, x, params["enc_blocks"])
        return L.apply_norm(cfg, params["ln_enc"], x)

    # -- decoder full forward ------------------------------------------------
    def forward(self, params, tokens, media, remat: bool = False):
        """tokens [B, S]; media = stub audio frames [B, F, D]."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        enc_out = self.encode(params, media, remat)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        x = x + params["dec_pos"].astype(dtype)[None, :s]
        mask = L.causal_mask(s, s)

        def body(carry, p):
            return decoder_block(cfg, p, carry, enc_out, mask), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = self._scan(body, x, params["dec_blocks"])
        x = L.apply_norm(cfg, params["ln_f"], x)
        return L.lm_logits(cfg, params["embed"], x)

    def loss(self, params, batch, remat: bool = True):
        logits = self.forward(params, batch["tokens"], batch["media"], remat=remat)
        return L.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))

    # -- caches ------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=None) -> Params:
        cfg = self.cfg
        dims = L.attn_dims(cfg)
        dtype = dtype or jnp.dtype(cfg.dtype)
        def mk(s):
            return jnp.zeros(
                (cfg.n_layers, batch, s, dims.n_kv_heads, dims.head_dim), dtype)
        return {
            "k": mk(max_seq), "v": mk(max_seq),
            "cross_k": mk(cfg.n_audio_frames), "cross_v": mk(cfg.n_audio_frames),
        }

    def prefill(self, params, tokens, max_seq: int, media=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        enc_out = self.encode(params, media)
        b, s = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        x = x + params["dec_pos"].astype(dtype)[None, :s]
        mask = L.causal_mask(s, s)

        def body(carry, p):
            h = carry
            hn = L.apply_norm(cfg, p["ln1"], h)
            k, v = L.project_kv(cfg, p["self_attn"], hn, None)
            ck, cv = L.project_kv(cfg, p["cross_attn"], enc_out, None)
            h = decoder_block(cfg, p, h, enc_out, mask)
            return h, (k, v, ck, cv)

        x, (ks, vs, cks, cvs) = self._scan(body, x, params["dec_blocks"])
        if s < max_seq:
            pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
            ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
        cache = {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs}
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, cache

    def chunk_prefill(self, params, cache, tokens, start_pos: int, media=None):
        """Chunked decoder prefill. If ``media`` is given (first chunk), the
        encoder runs and cross-KV is (re)computed; later chunks reuse it."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, c = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens, dtype)
        x = x + jax.lax.dynamic_slice_in_dim(
            params["dec_pos"].astype(dtype), start_pos, c, axis=0
        )[None]
        positions = jnp.broadcast_to(
            start_pos + jnp.arange(c, dtype=jnp.int32), (b, c)
        )
        clen = cache["k"].shape[2]
        spos = L.slot_positions(clen, start_pos + c - 1)
        enc_out = self.encode(params, media) if media is not None else None

        def body(carry, xs):
            h = carry
            p, kc, vc, ck, cv = xs
            hn = L.apply_norm(cfg, p["ln1"], h)
            k_new, v_new = L.project_kv(cfg, p["self_attn"], hn, None)
            kc = L.write_chunk(kc, k_new, start_pos)
            vc = L.write_chunk(vc, v_new, start_pos)
            h = h + L.chunk_attention(
                cfg, p["self_attn"], hn, positions, kc, vc, spos, use_rope=False
            )
            if enc_out is not None:
                ck, cv = L.project_kv(cfg, p["cross_attn"], enc_out, None)
            hn2 = L.apply_norm(cfg, p["ln2"], h)
            h = h + L.chunk_attention(
                cfg, p["cross_attn"], hn2, positions, ck, cv,
                jnp.zeros((cfg.n_audio_frames,), jnp.int32),  # all valid, pos 0
                use_rope=False,
            )
            h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln3"], h))
            return h, (kc, vc, ck, cv)

        x, (ks, vs, cks, cvs) = self._scan(
            body, x,
            (params["dec_blocks"], cache["k"], cache["v"],
             cache["cross_k"], cache["cross_v"]),
        )
        new_cache = {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs}
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache

    def decode_step(self, params, cache, tokens, pos, kv_writer=direct_kv_write):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b = tokens.shape[0]
        x = L.embed_tokens(cfg, params["embed"], tokens[:, None], dtype)
        x = x + jnp.take(params["dec_pos"].astype(dtype), pos, axis=0)[:, None]
        clen = cache["k"].shape[2]
        slots = jnp.minimum(pos, clen - 1).astype(jnp.int32)
        vmask = valid_mask(cfg, pos, clen)
        cross_mask = jnp.ones((b, cfg.n_audio_frames), jnp.bool_)

        def body(carry, xs):
            h = carry
            p, kc, vc, ck, cv = xs
            hn = L.apply_norm(cfg, p["ln1"], h)
            k_new, v_new = L.project_kv(cfg, p["self_attn"], hn, None)
            kc, vc = kv_writer(kc, vc, k_new, v_new, slots)
            h = h + L.decode_attention(cfg, p["self_attn"], hn, pos, kc, vc, vmask,
                                       use_rope=False)
            hn2 = L.apply_norm(cfg, p["ln2"], h)
            h = h + L.decode_attention(cfg, p["cross_attn"], hn2, pos, ck, cv,
                                       cross_mask, use_rope=False)
            h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln3"], h))
            return h, (kc, vc)

        x, (ks, vs) = self._scan(
            body, x,
            (params["dec_blocks"], cache["k"], cache["v"], cache["cross_k"], cache["cross_v"]),
        )
        new_cache = dict(cache, k=ks, v=vs)
        x = L.apply_norm(cfg, params["ln_f"], x)
        logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
        return logits, new_cache
