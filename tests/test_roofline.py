"""Roofline tooling unit tests: HLO collective parser, affine combination,
scan-vs-unroll cost accounting assumptions."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.roofline import (
    _combine,
    _shape_bytes,
    collective_bytes,
    roofline_terms,
)


def test_shape_bytes():
    assert _shape_bytes("f32", "128,256") == 128 * 256 * 4
    assert _shape_bytes("bf16", "16") == 32
    assert _shape_bytes("pred", "8,8") == 64
    assert _shape_bytes("s32", "") == 4  # scalar


def test_collective_parser_counts_ops():
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}
  %ag.1 = bf16[64,128]{1,0} all-gather(bf16[4,128]{1,0} %y), dimensions={0}
  %rs = f32[8]{0} reduce-scatter(f32[128]{0} %z), dimensions={0}
  %cp = f32[256]{0} collective-permute(f32[256]{0} %w)
  %notacoll = f32[9]{0} add(f32[9]{0} %a, f32[9]{0} %b)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 4096
    assert out["all-gather"] == 64 * 128 * 2  # max shape on the line
    assert out["reduce-scatter"] == 128 * 4
    assert out["collective-permute"] == 1024
    assert out["total"] == sum(
        v for k, v in out.items() if k != "total"
    )


def test_collective_parser_skips_done_ops():
    hlo = """
  %s = f32[64]{0} all-reduce-start(f32[64]{0} %x)
  %d = f32[64]{0} all-reduce-done(f32[64]{0} %s)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 256  # start counted once, done skipped


def test_affine_combine():
    a = {"flops": 10.0, "bytes": 4.0}
    b = {"flops": 6.0, "coll": 2.0}
    out = _combine(a, b, 2.0, 3.0)
    assert out["flops"] == 2 * 10 + 3 * 6
    assert out["bytes"] == 8.0
    assert out["coll"] == 6.0


def test_roofline_terms_dominance():
    from repro.configs import get_shape

    cfg = get_config("stablelm-1.6b")
    shape = get_shape("train_4k")
    m = {"flops": 197e12, "bytes": 819e9 * 10, "coll_bytes": 50e9}
    t = roofline_terms(m, cfg, shape)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(10.0)
    assert t["collective_s"] == pytest.approx(1.0)
    assert t["dominant"] == "memory"
    assert t["roofline_fraction"] == pytest.approx(0.1)


def test_scan_undercounts_unroll_doesnt():
    """The methodology premise: cost_analysis counts a while body once."""
    from jax import lax

    def f_scan(x, w):
        return lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), x, w)[0]

    def f_unroll(x, w):
        for i in range(4):
            x = jnp.tanh(x @ w[i])
        return x

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    fs = jax.jit(f_scan).lower(x, w).compile().cost_analysis()["flops"]
    fu = jax.jit(f_unroll).lower(x, w).compile().cost_analysis()["flops"]
    assert fu > 3 * fs  # unrolled sees ~4x the flops


def test_depth_probe_configs_preserve_structure():
    from repro.launch.cells import depth_probes, full_depth_units, probe_config

    for arch in ("qwen2-7b", "llama-3.2-vision-90b", "zamba2-2.7b",
                 "whisper-medium", "mamba2-130m"):
        cfg = get_config(arch)
        for _, kw, _ in depth_probes(cfg):
            pc = probe_config(cfg, kw)
            assert pc.family == cfg.family
            assert pc.d_model == cfg.d_model
            if cfg.family == "vlm":
                assert pc.n_layers % pc.cross_attn_every == 0
            if cfg.family == "hybrid":
                assert pc.n_layers % pc.hybrid_attn_every == 0
        units = full_depth_units(cfg)
        assert units == (cfg.n_layers, cfg.n_enc_layers) \
            if cfg.family == "encdec" else units >= 1
