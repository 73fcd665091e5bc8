"""Plain reference forward pass of a dense decoder, in float32 at full
matmul precision: no kernels, no cache, no batching, one sequence at a
time. It imports nothing of the program; it reads the configuration file
(published config.json keys) and a parameter dict by name.

What it follows: StableLM-2 (LayerNorm with bias, partial rotary over the
first ``partial_rotary_factor`` of each head, q/k/v biases) and Qwen2
(RMSNorm, q/k/v biases, grouped-query attention), with SwiGLU MLPs and an
untied output head. One departure, shared with the program: rotary pairs
are interleaved (channels 2i, 2i+1), where the published checkpoints pair
channel i with i + rot/2. That is a fixed permutation of the rotated q/k
channels of a checkpoint, so on seeded weights it is the same family of
functions.

``quant="fp8"`` is the control: every linear layer's input and weight
rounded to float8 e4m3 with a per-tensor (weight) or per-row (activation)
scale, the rest as above. It stands for the lower precision a later change
might be tempted to serve in.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _linear(x, w, spec, quant, w_axes):
    """einsum over float32 copies; under fp8 both operands are rounded."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant == "fp8":
        x = _q8(x, -1)
        w = _q8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(conf, p, x):
    if conf["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + conf["layer_norm_eps"])
        return y * p["scale"].astype(F32) + p["bias"].astype(F32)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + conf["rms_norm_eps"]) * p["scale"].astype(F32)


def _rope(conf, x, pos):
    """x [T, H, Dh]: rotate the first ``partial_rotary_factor`` of Dh in
    interleaved pairs."""
    dh = x.shape[-1]
    rot = int(dh * conf.get("partial_rotary_factor", 1.0))
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (conf["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(x.shape[:-1] + (rot,)), x[..., rot:]], -1)


def _layer(conf, quant, h, p):
    t = h.shape[0]
    pos = jnp.arange(t)
    a = p["attn"]
    x = _norm(conf, p["ln1"], h)
    q = _linear(x, a["wq"], "td,dhk->thk", quant, 0)
    k = _linear(x, a["wk"], "td,dhk->thk", quant, 0)
    v = _linear(x, a["wv"], "td,dhk->thk", quant, 0)
    if conf["use_qkv_bias"]:
        q, k, v = (q + a["bq"].astype(F32), k + a["bk"].astype(F32),
                   v + a["bv"].astype(F32))
    q, k = _rope(conf, q, pos), _rope(conf, k, pos)
    hq, hkv, dh = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(t, hkv, hq // hkv, dh)
    s = jnp.einsum("tgjd,sgd->gjts", qg, k,
                   precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("gjts,sgd->tgjd", pr, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(t, hq, dh)
    h = h + _linear(o, a["wo"], "thk,hkd->td", quant, (0, 1))
    m = p["mlp"]
    x = _norm(conf, p["ln2"], h)
    gate = _linear(x, m["wg"], "td,df->tf", quant, 0)
    up = _linear(x, m["wi"], "td,df->tf", quant, 0)
    return h + _linear(jax.nn.silu(gate) * up, m["wo"], "tf,fd->td", quant, 0)


@functools.partial(jax.jit, static_argnames=("conf_items", "n_rows", "quant"))
def _rows_logits(weights, tokens, start, conf_items, n_rows, quant):
    conf = dict(conf_items)
    h = weights["embed"]["tok"][tokens].astype(F32)

    def body(h, p):
        return _layer(conf, quant, h, p), None

    h, _ = jax.lax.scan(body, h, weights["blocks"])
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows, 0)
    rows = _norm(conf, weights["ln_f"], rows)
    return _linear(rows, weights["embed"]["head"], "td,vd->tv", quant, 1)


_KEYS = ("norm", "layer_norm_eps", "rms_norm_eps", "rope_theta",
         "partial_rotary_factor", "use_qkv_bias")


def logits_at(weights, conf: dict, tokens: np.ndarray, start: int,
              pad_to: int, n_rows: int, quant: Optional[str] = None):
    """float32 logits [n_rows, V] at positions ``start`` ..
    ``start + n_rows - 1`` of ``tokens``, which is zero-padded at the end to
    ``pad_to`` (padding sits after every row read, so causal attention
    never sees it)."""
    padded = np.zeros((pad_to,), np.int32)
    padded[: len(tokens)] = tokens
    items = tuple((k, conf[k]) for k in _KEYS if k in conf)
    with jax.default_matmul_precision("highest"):
        return _rows_logits(weights, jnp.asarray(padded), jnp.int32(start),
                            items, int(n_rows), quant)
