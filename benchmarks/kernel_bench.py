"""Kernel micro-benchmarks on the CPU: the REF oracles' wall time
(XLA:CPU, ``*_ref_ms``) and the kernels' analytic TPU v5e roofline times
(``*_tpu_roofline_us``, from the published peaks in ``launch.device``) for
the shapes the serving engine uses. Neither is a chip measurement."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.launch.device import PEAKS, V5E


def _wall(f, *args, n=20) -> float:
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3  # ms


def run() -> list:
    rows = []
    rng = np.random.RandomState(0)

    # staged_scatter drain: KV-page-sized rows
    r, w, n = 512, 2048, 64
    dest = jnp.asarray(rng.randn(r, w), jnp.float32)
    staging = jnp.asarray(rng.randn(n, w), jnp.float32)
    rows_i = jnp.asarray(rng.permutation(r)[:n], jnp.int32)
    valid = jnp.ones((n,), bool)
    f = jax.jit(ref.staged_scatter_ref)
    rows.append(("kern/staged_scatter_ref_ms", _wall(f, dest, staging, rows_i, valid), "ms"))
    bytes_moved = n * w * 4 * 2
    rows.append(("kern/staged_scatter_tpu_roofline_us", bytes_moved / PEAKS[V5E].hbm_bw * 1e6, "us"))

    # flash attention prefill tile: chunked-prefill geometry
    b, hq, hkv, s, t, d = 2, 16, 4, 1024, 8192, 128
    q = jnp.asarray(rng.randn(b, hq, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, hkv, t, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, hkv, t, d), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    rows.append(("kern/flash_attn_ref_ms", _wall(f, q, k, v, n=5), "ms"))
    flops = 4 * b * hq * s * t * d
    rows.append(("kern/flash_attn_tpu_roofline_us", flops / PEAKS[V5E].flops_bf16 * 1e6, "us"))

    # flash decode: 32k cache
    tkv = 32768
    qd = jnp.asarray(rng.randn(8, hq, d), jnp.bfloat16)
    kd = jnp.asarray(rng.randn(8, tkv, hkv, d), jnp.bfloat16)
    vd = jnp.asarray(rng.randn(8, tkv, hkv, d), jnp.bfloat16)
    mask = jnp.ones((8, tkv), bool)
    f = jax.jit(ref.flash_decode_ref)
    rows.append(("kern/flash_decode_ref_ms", _wall(f, qd, kd, vd, mask, n=5), "ms"))
    bytes_kv = 8 * tkv * hkv * d * 2 * 2
    rows.append(("kern/flash_decode_tpu_roofline_us", bytes_kv / PEAKS[V5E].hbm_bw * 1e6, "us"))

    # cms monitor hot path
    counts = jnp.zeros((4, 4096), jnp.int32)
    ids = jnp.asarray(rng.randint(0, 1 << 20, 256), jnp.int32)
    f = jax.jit(ref.cms_update_ref)
    rows.append(("kern/cms_update_ref_ms", _wall(f, counts, ids), "ms"))
    return rows
