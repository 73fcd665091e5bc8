"""Continuous-batching scheduler: batched decode must be a pure throughput
optimization — bit-identical tokens to sequential per-request decode (the
same engine pinned to one slot AND the dense per-request ``ServeEngine``),
in every write mode, greedy and sampled, with EOS/max-len retirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.paths import SpecConfig
from repro.data import synthetic_requests
from repro.models import build_model
from repro.serve import BatchConfig, BatchedServeEngine, ServeConfig, ServeEngine

N_REQ, PLEN, MAX_NEW = 5, 12, 10


def _model():
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), 64)
    return cfg, model, params


def _queue(cfg, max_new=MAX_NEW):
    return synthetic_requests(N_REQ, PLEN, cfg.vocab, max_new, seed=3)


def _engine(model, params, mode, n_slots, **kw):
    kw.setdefault("segment_len", 4)
    kw.setdefault("ring_size", 4)
    kw.setdefault("hot_threshold", 3)
    return BatchedServeEngine(model, params, BatchConfig(
        max_seq=32, n_slots=n_slots, write_mode=mode, page_size=8, **kw), _internal=True)


@pytest.mark.parametrize("mode", ["direct", "staged", "adaptive"])
def test_batched_equals_sequential_and_per_request(mode):
    cfg, model, params = _model()
    eng_b = _engine(model, params, mode, n_slots=2)
    out_b = eng_b.serve(_queue(cfg))
    out_s = _engine(model, params, mode, n_slots=1).serve(_queue(cfg))
    assert set(out_b) == set(out_s) == set(range(N_REQ))
    for r in out_b:
        np.testing.assert_array_equal(out_b[r], out_s[r])
    # and against the dense per-request engine (different substrate:
    # contiguous lanes vs paged pool — identical greedy tokens)
    q = _queue(cfg)
    for r in range(N_REQ):
        req = q.pop()
        ref = ServeEngine(model, params, ServeConfig(
            max_seq=64, write_mode=mode, ring_size=4, page_size=8,
            hot_threshold=3,
        ), _internal=True).generate(jnp.asarray(req.prompt)[None], MAX_NEW)
        np.testing.assert_array_equal(out_b[r], np.asarray(ref)[0])
    assert eng_b.layout == "paged"
    total = eng_b.stats["direct_writes"] + eng_b.stats["staged_writes"]
    assert total == N_REQ * (MAX_NEW - 1)  # one KV write per decode step
    if mode == "staged":
        assert eng_b.stats["staged_writes"] == total


def test_staged_mode_drains_inside_the_scan():
    """ring_size < segment_len forces full-ring drains inside the jitted
    segment (not just the boundary drain)."""
    cfg, model, params = _model()
    eng = _engine(model, params, "staged", n_slots=2, segment_len=8,
                  ring_size=4)
    eng.serve(_queue(cfg))
    assert eng.stats["drains"] > 0


def test_adaptive_routes_a_mix_over_the_shared_pool():
    cfg, model, params = _model()
    eng = _engine(model, params, "adaptive", n_slots=2, hot_threshold=2)
    eng.serve(_queue(cfg))
    assert eng.stats["staged_writes"] > 0
    assert eng.stats["direct_writes"] > 0


def test_sampled_decode_keys_are_per_request():
    """Per-slot PRNG keys fold in the request id, so sampled outputs are a
    function of the request alone — identical across batch sizes."""
    cfg, model, params = _model()
    out_b = _engine(model, params, "direct", n_slots=2,
                    greedy=False).serve(_queue(cfg))
    out_s = _engine(model, params, "direct", n_slots=1,
                    greedy=False).serve(_queue(cfg))
    for r in out_b:
        np.testing.assert_array_equal(out_b[r], out_s[r])


def test_eos_retires_early_and_frees_the_slot():
    cfg, model, params = _model()
    base = _engine(model, params, "direct", n_slots=2).serve(_queue(cfg))
    # pick a token the greedy stream actually emits mid-sequence
    eos = int(base[0][4])
    eng = _engine(model, params, "direct", n_slots=2, eos_id=eos)
    out = eng.serve(_queue(cfg))
    assert len(out[0]) <= 5 and out[0][-1] == eos
    for r in out:  # every request stops at eos or budget, never beyond
        assert len(out[r]) <= MAX_NEW
        if len(out[r]) < MAX_NEW:
            assert out[r][-1] == eos
    assert eng.stats["retired"] == N_REQ
    assert not any(eng._occupied)


def test_max_new_one_needs_no_decode_step():
    cfg, model, params = _model()
    eng = _engine(model, params, "direct", n_slots=2)
    out = eng.serve(_queue(cfg, max_new=1))
    assert all(out[r].shape == (1,) for r in out)
    assert eng.stats["direct_writes"] == 0  # prefill-only

@pytest.mark.parametrize("kind", ["decode", "mixed", "spec"])
def test_segment_fn_compiles_once(kind):
    """Each (kind, mode) segment program is built once and survives
    ``reset()``."""
    cfg, model, params = _model()
    kw = {"decode": {}, "mixed": dict(chunked=True, chunk_size=4),
          "spec": dict(spec=SpecConfig(enabled=True, k=2))}[kind]
    eng = _engine(model, params, "adaptive", n_slots=2, **kw)
    eng.serve(_queue(cfg))
    built = dict(eng._segments)
    assert kind in {k for k, _ in built}
    eng.reset()
    eng.serve(_queue(cfg))
    # reset keeps the compiled loops, and serving again builds none
    assert eng._segments.keys() == built.keys()
    assert all(eng._segments[key] is fn for key, fn in built.items())
