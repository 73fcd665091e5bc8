"""Device facts shared by the launcher, the benchmarks and ``chip_smoke.py``.

* :data:`PEAKS` — published per-chip peaks, keyed by the ``device_kind``
  JAX reports. A chip that is not in the table is a ``KeyError``, never a
  default: a roofline share computed against the wrong chip is wrong.
* :func:`enable_compile_cache` — JAX's persistent compilation cache at a
  place that can be set from outside (``JAX_COMPILATION_CACHE_DIR``) and
  otherwise at one fixed directory of the checkout, so a later run of the
  same program finds its compiled code again.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float     # FLOP/s, dense bf16 matmul
    hbm_bw: float         # bytes/s
    ici_link_bw: float    # bytes/s per chip-to-chip link
    source: str


PEAKS = {
    # 4 ICI links share the 1,600 Gbit/s per chip: 50 GB/s each
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip'),
}

V5E = "TPU v5 lite"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache goes to the checkout's
    ``.jax_cache/``: a fixed path, because the path is part of what a
    later run must match to find an entry.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
