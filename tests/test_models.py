"""Per-arch smoke + consistency tests.

Every assigned architecture instantiates its REDUCED config (same structure,
small sizes), runs one forward/train step on CPU, asserts shapes and
finiteness, and checks the prefill -> decode path agrees with the parallel
forward pass (the core serving invariant).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import build_model, media_spec, needs_media
from repro.optim import AdamW
from repro.train import init_train_state, make_train_step

ALL_ARCHS = sorted(ARCHS)


def _setup(arch, no_drop=False):
    cfg = get_config(arch).reduced()
    if no_drop and cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=100.0)
    model = build_model(cfg)
    params = model.init(jax.random.key(0), 64)
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
    media = None
    if needs_media(cfg):
        media = jax.random.normal(
            jax.random.key(2), media_spec(cfg, B, jnp.float32).shape
        )
    return cfg, model, params, tokens, media


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_shapes_and_finiteness(arch):
    cfg, model, params, tokens, media = _setup(arch)
    batch = {"tokens": tokens, "labels": tokens}
    if media is not None:
        batch["media"] = media
    loss = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch
    logits = (model.forward(params, tokens, media) if media is not None
              else model.forward(params, tokens))
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_init_stores_params_in_param_dtype(arch):
    """``param_dtype`` decides the dtype every float parameter is stored
    in (serving sets it to the compute dtype); the model runs on them."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="bfloat16",
                              param_dtype="bfloat16")
    model = build_model(cfg)
    params = model.init(jax.random.key(0), 64)
    floats = [x for x in jax.tree.leaves(params)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    assert floats and all(x.dtype == jnp.bfloat16 for x in floats)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    if needs_media(cfg):
        media = jax.random.normal(jax.random.key(2),
                                  media_spec(cfg, 2, jnp.bfloat16).shape,
                                  jnp.bfloat16)
        logits = model.forward(params, tokens, media)
    else:
        logits = model.forward(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_one_train_step(arch):
    cfg, model, params, tokens, media = _setup(arch)
    opt = AdamW(lr=1e-3)
    state = init_train_state(model, opt, jax.random.key(0), 64,
                             n_hot_experts=2 if cfg.n_experts else 0)
    step = make_train_step(model, opt, microbatches=1)
    batch = {"tokens": tokens, "labels": tokens}
    if media is not None:
        batch["media"] = media
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_consistency(arch):
    """decode(prefill(prompt)) logits == parallel forward logits."""
    cfg, model, params, tokens, media = _setup(arch, no_drop=True)
    B, S = tokens.shape
    kw = {"media": media} if media is not None else {}
    full = (model.forward(params, tokens, media) if media is not None
            else model.forward(params, tokens))
    logits_pre, cache = model.prefill(params, tokens[:, : S - 1], 64, **kw)
    lg_dec, _ = model.decode_step(
        params, cache, tokens[:, S - 1], jnp.full((B,), S - 1, jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(logits_pre), np.asarray(full[:, S - 2]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lg_dec), np.asarray(full[:, S - 1]),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_chunked_prefill_equals_oneshot(arch):
    cfg, model, params, tokens, media = _setup(arch, no_drop=True)
    B, S, C = tokens.shape[0], tokens.shape[1], 16
    kw = {"media": media} if media is not None else {}
    lg_ref, _ = model.prefill(params, tokens, 64, **kw)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: model.init_cache(B, 64, jnp.float32)),
    )
    _, cache = model.chunk_prefill(params, cache, tokens[:, :C], 0, media=media)
    lg, _ = model.chunk_prefill(params, cache, tokens[:, C:], C, media=media)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_multi_step_decode(arch):
    """8 sequential decode steps stay finite and match teacher forcing."""
    cfg, model, params, tokens, media = _setup(arch, no_drop=True)
    B, S = tokens.shape
    kw = {"media": media} if media is not None else {}
    half = S // 2
    full = (model.forward(params, tokens, media) if media is not None
            else model.forward(params, tokens))
    _, cache = model.prefill(params, tokens[:, :half], 64, **kw)
    for t in range(half, min(half + 8, S)):
        lg, cache = model.decode_step(
            params, cache, tokens[:, t], jnp.full((B,), t, jnp.int32)
        )
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, t]),
                                   atol=1e-4, rtol=1e-4)


def test_scan_unroll_equivalence():
    for arch in ("qwen2-7b", "zamba2-2.7b", "whisper-medium"):
        cfg = get_config(arch).reduced()
        m1, m2 = build_model(cfg), build_model(cfg, unroll=True)
        params = m1.init(jax.random.key(0), 32)
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
        media = None
        if needs_media(cfg):
            media = jax.random.normal(
                jax.random.key(2), media_spec(cfg, 2, jnp.float32).shape
            )
            o1, o2 = m1.forward(params, tokens, media), m2.forward(params, tokens, media)
        else:
            o1, o2 = m1.forward(params, tokens), m2.forward(params, tokens)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)


def test_param_counts_match_analytic():
    """Analytic param_count (used for MODEL_FLOPS) matches actual trees."""
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        model = build_model(cfg)
        abstract = jax.eval_shape(
            lambda k: model.init(k, 128), jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        actual = sum(np.prod(l.shape) for l in jax.tree.leaves(abstract))
        expected = cfg.param_count()
        if cfg.learned_pos:  # pos tables sized by runtime max_seq, excluded
            expected = expected - cfg.max_position * cfg.d_model + 128 * cfg.d_model
        assert abs(actual - expected) / expected < 0.02, (
            arch, actual, expected)
