"""The plain float32 reference against the program's own serving math
(prefill, then cached decode steps) at smoke widths, for both families:
LayerNorm with 25% partial rotary and q/k/v biases (stablelm-2), and
RMSNorm with q/k/v biases and grouped-query attention (qwen2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib.model import model_config
from bench.lib.weights import make_weights
from bench.reference.forward import logits_at
from bench.tests.tiny import conf


@pytest.mark.parametrize("family", ["stablelm", "qwen2"])
def test_reference_matches_prefill_then_decode(family):
    from repro.models import build_model

    c = conf(family)
    model = build_model(model_config(c))
    params = make_weights(model, 2 ** 33 + 17)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, c["vocab_size"], size=11).astype(np.int32)
    follow = rng.integers(0, c["vocab_size"], size=5).astype(np.int32)
    max_seq = 32
    logits, cache = model.prefill(params, jnp.asarray(prompt)[None], max_seq)
    got = [np.asarray(logits[0])]
    for j, tok in enumerate(follow[:-1]):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok]), jnp.asarray([len(prompt) + j]))
        got.append(np.asarray(logits[0]))
    got = np.stack(got)
    seq = np.concatenate([prompt, follow[:-1]])
    want = np.asarray(logits_at(params, c, seq, len(prompt) - 1, max_seq,
                                len(follow)))
    # both in float32 over the same weights: rounding only, in another order
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert float(np.abs(want).max()) > 0.5    # not vacuous


def test_fp8_control_departs_from_float32():
    from repro.models import build_model

    c = conf("qwen2")
    params = make_weights(build_model(model_config(c)), 5)
    seq = np.arange(20, dtype=np.int32) * 7 % c["vocab_size"]
    f32 = np.asarray(logits_at(params, c, seq, 0, 32, 20))
    f8 = np.asarray(logits_at(params, c, seq, 0, 32, 20, quant="fp8"))
    err = np.abs(f8 - f32).max()
    assert 1e-3 < err < 1.0
    assert jax.numpy.isfinite(f8).all()
