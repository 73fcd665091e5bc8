"""A configuration file (``bench/configs/<name>.json``) to the program's
model: the published config.json keys, mapped onto the registered
architecture the file names under ``arch``.

Every mapped key must land: a width the program would silently ignore is
an error here, not a smaller model.
"""
from __future__ import annotations

import dataclasses

from .flops import Shapes


def model_config(conf: dict):
    from repro.configs import get_config

    base = get_config(conf["arch"])
    norm = conf["norm"]
    eps = conf["layer_norm_eps"] if norm == "layernorm" else conf["rms_norm_eps"]
    want = dict(
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["hidden_size"]) // int(conf["num_attention_heads"]),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        rope_fraction=float(conf.get("partial_rotary_factor", 1.0)),
        qkv_bias=bool(conf["use_qkv_bias"]),
        norm=norm,
        norm_eps=float(eps),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"],
        param_dtype=conf["torch_dtype"],
    )
    if conf.get("qk_layernorm") or conf.get("use_parallel_residual"):
        raise ValueError("q/k LayerNorm and parallel residuals are not mapped")
    if conf["hidden_act"] != "silu" or base.activation != "swiglu":
        raise ValueError("only gated-SiLU (SwiGLU) MLPs are mapped")
    cfg = dataclasses.replace(base, **want)
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{conf['name']}: {k} did not land ({getattr(cfg, k)} != {v})")
    return cfg


def shapes(cfg) -> Shapes:
    return Shapes(n_layers=cfg.n_layers, d_model=cfg.d_model,
                  n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                  head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                  vocab=cfg.vocab, gated_mlp=True, bytes_per_el=2)
