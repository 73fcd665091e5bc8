"""Roofline analysis from compiled dry-run artifacts.

Hardware model: the dry run's target chip, TPU v5e, at its published peaks
(``launch.device.PEAKS``: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s per ICI
link).
Per (arch x shape) on the single-pod mesh we derive:

    compute_s    = FLOPs_per_device / peak FLOP/s
    memory_s     = bytes_per_device / HBM bytes/s
    collective_s = collective_bytes_per_device / ICI link bytes/s

``compiled.cost_analysis()`` is PER-DEVICE on an SPMD module (verified: a
512-way-sharded einsum reports global/512 flops), so no further division by
chip count is needed. Collective bytes are parsed from ``compiled.as_text()``
(post-partitioning, i.e. per-device shapes): for each all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute op we count
the LARGEST shape on the op line (≈ bytes crossing the local ICI links; an
all-reduce moves ~2x this in a ring — reported as-is and noted in
EXPERIMENTS.md).

Scan-body accounting: XLA's cost analysis counts a while-loop body ONCE, so
all probe lowers run with UNROLLED stacks on depth-reduced configs, and the
full-depth cost is reconstructed affinely:

    cost(L) = base + marginal * L        (marginal from depth-1/depth-2)
    train:  cost(L, M) = opt(L) + M * micro(L); opt scaled by param ratio.

The full-depth scanned compile (launch/dryrun.py) independently proves
compilability and memory fit.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict

import jax

from ..configs.base import ModelConfig, ShapeSpec
from . import cells as C
from .device import PEAKS, V5E

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*[^=]*?\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-kind byte totals for collective ops (per device, post-SPMD)."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        kind = m.group(1)
        sizes = [_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(line)]
        if sizes:
            out[kind] = out.get(kind, 0.0) + max(sizes)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def compiled_metrics(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": coll["total"],
        **{f"coll_{k.replace('-', '_')}": v for k, v in coll.items() if k != "total"},
    }


def _combine(a: Dict[str, float], b: Dict[str, float], fa: float, fb: float):
    keys = set(a) | set(b)
    return {k: fa * a.get(k, 0.0) + fb * b.get(k, 0.0) for k in keys}


# ---------------------------------------------------------------------------
# Probing
# ---------------------------------------------------------------------------


def _lower_metrics(cfg, shape, mesh, *, microbatches=None, dispatch_mode="staged"):
    step, args, _meta = C.build_cell(
        cfg, shape, mesh, unroll=True, microbatches=microbatches,
        dispatch_mode=dispatch_mode,
    )
    args = tuple(a for a in args if a is not None)
    with mesh, jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        compiled = jax.jit(step).lower(*args).compile()
    return compiled_metrics(compiled)


def probe_cell(
    cfg: ModelConfig,
    shape: ShapeSpec,
    mesh,
    dispatch_mode: str = "staged",
) -> Dict[str, float]:
    """Affine-extrapolated per-device metrics for the FULL-depth cell."""
    probes = C.depth_probes(cfg)

    if shape.step == "train":
        mb = C.TRAIN_MICROBATCHES.get(cfg.name, C.TRAIN_MICROBATCHES["default"])
        micro_bs = shape.global_batch // mb
        micro_shape = dataclasses.replace(shape, global_batch=micro_bs)

        if cfg.family == "encdec":
            c11 = C.probe_config(cfg, probes[0][1])
            c21 = C.probe_config(cfg, probes[1][1])
            c12 = C.probe_config(cfg, probes[2][1])
            p11 = _lower_metrics(c11, micro_shape, mesh, microbatches=1,
                                 dispatch_mode=dispatch_mode)
            p21 = _lower_metrics(c21, micro_shape, mesh, microbatches=1,
                                 dispatch_mode=dispatch_mode)
            p12 = _lower_metrics(c12, micro_shape, mesh, microbatches=1,
                                 dispatch_mode=dispatch_mode)
            two_shape = dataclasses.replace(shape, global_batch=2 * micro_bs)
            pm2 = _lower_metrics(c11, two_shape, mesh, microbatches=2,
                                 dispatch_mode=dispatch_mode)
            micro_11 = _combine(pm2, p11, 1.0, -1.0)          # one extra microbatch
            opt_11 = _combine(p11, micro_11, 1.0, -1.0)
            mu_dec = _combine(p21, p11, 1.0, -1.0)
            mu_enc = _combine(p12, p11, 1.0, -1.0)
            ld, le = cfg.n_layers, cfg.n_enc_layers
            micro_l = _combine(
                _combine(micro_11, mu_dec, 1.0, float(ld - 1)),
                mu_enc, 1.0, float(le - 1),
            )
            ratio = cfg.param_count() / c11.param_count()
            opt_l = {k: v * ratio for k, v in opt_11.items()}
            return _combine(opt_l, micro_l, 1.0, float(mb))

        d1_cfg = C.probe_config(cfg, probes[0][1])
        d2_cfg = C.probe_config(cfg, probes[1][1])
        p11 = _lower_metrics(d1_cfg, micro_shape, mesh, microbatches=1,
                             dispatch_mode=dispatch_mode)
        p21 = _lower_metrics(d2_cfg, micro_shape, mesh, microbatches=1,
                             dispatch_mode=dispatch_mode)
        two_shape = dataclasses.replace(shape, global_batch=2 * micro_bs)
        p12 = _lower_metrics(d1_cfg, two_shape, mesh, microbatches=2,
                             dispatch_mode=dispatch_mode)
        micro_1 = _combine(p12, p11, 1.0, -1.0)   # cost of one more microbatch @d1
        opt_1 = _combine(p11, micro_1, 1.0, -1.0)
        mu = _combine(p21, p11, 1.0, -1.0)        # per-depth-unit marginal @M=1
        units = C.full_depth_units(cfg)
        micro_l = _combine(micro_1, mu, 1.0, float(units - 1))
        ratio = cfg.param_count() / d1_cfg.param_count()
        opt_l = {k: v * ratio for k, v in opt_1.items()}
        return _combine(opt_l, micro_l, 1.0, float(mb))

    # prefill / decode: cost(L) = p1 + (L-1) * (p2 - p1)
    if cfg.family == "encdec" and shape.step == "prefill":
        c11 = C.probe_config(cfg, probes[0][1])
        c21 = C.probe_config(cfg, probes[1][1])
        c12 = C.probe_config(cfg, probes[2][1])
        p11 = _lower_metrics(c11, shape, mesh, dispatch_mode=dispatch_mode)
        p21 = _lower_metrics(c21, shape, mesh, dispatch_mode=dispatch_mode)
        p12 = _lower_metrics(c12, shape, mesh, dispatch_mode=dispatch_mode)
        mu_dec = _combine(p21, p11, 1.0, -1.0)
        mu_enc = _combine(p12, p11, 1.0, -1.0)
        return _combine(
            _combine(p11, mu_dec, 1.0, float(cfg.n_layers - 1)),
            mu_enc, 1.0, float(cfg.n_enc_layers - 1),
        )

    d1_cfg = C.probe_config(cfg, probes[0][1])
    d2_cfg = C.probe_config(cfg, probes[1][1])
    p1 = _lower_metrics(d1_cfg, shape, mesh, dispatch_mode=dispatch_mode)
    p2 = _lower_metrics(d2_cfg, shape, mesh, dispatch_mode=dispatch_mode)
    units = C.full_depth_units(cfg)
    if isinstance(units, tuple):
        # enc-dec decode: the encoder does not run in decode_step — only
        # the decoder depth scales (probes 0/1 vary decoder layers).
        units = units[0]
    mu = _combine(p2, p1, 1.0, -1.0)
    return _combine(p1, mu, 1.0, float(units - 1))


# ---------------------------------------------------------------------------
# Terms
# ---------------------------------------------------------------------------


def roofline_terms(metrics: Dict[str, float], cfg: ModelConfig,
                   shape: ShapeSpec) -> Dict[str, Any]:
    peaks = PEAKS[V5E]
    compute_s = metrics["flops"] / peaks.flops_bf16
    memory_s = metrics["bytes"] / peaks.hbm_bw
    coll_s = metrics["coll_bytes"] / peaks.ici_link_bw
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", coll_s),
        key=lambda kv: kv[1],
    )[0]
    # MODEL_FLOPS: 6·N·D (dense) / 6·N_active·D (MoE); D = tokens processed.
    if shape.step == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * cfg.active_param_count() * tokens
    elif shape.step == "prefill":
        chunk = C.PREFILL_CHUNK.get(cfg.name, C.PREFILL_CHUNK["default"])
        tokens = shape.global_batch * min(chunk, shape.seq_len)
        model_flops = 2 * cfg.active_param_count() * tokens
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        model_flops = 2 * cfg.active_param_count() * tokens
    model_flops_per_dev = model_flops / 256  # single-pod mesh
    useful = model_flops_per_dev / metrics["flops"] if metrics["flops"] else 0.0
    bound = max(compute_s, memory_s, coll_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "model_flops_ratio": useful,
        "roofline_fraction": (compute_s / bound) if bound else 0.0,
        "flops_per_dev": metrics["flops"],
        "bytes_per_dev": metrics["bytes"],
        "coll_bytes_per_dev": metrics["coll_bytes"],
    }
