"""flash_decode_paged — the fused paged-attention read kernel.

Parity contract (DESIGN.md §7), tested at two boundaries:

* KERNEL boundary: the Pallas kernel (interpret mode on CPU) computes
  the jnp reference's terms (logits -> mask -> softmax -> weighted sum)
  as an online softmax and is held to fp32 ulp-level equality (~1e-7
  abs; tolerance carries 10x margin) — swept across ring states (empty / partial /
  full / wrapped / conflict-shaped), chunk sizes, GQA group sizes, and
  page geometries, and cross-checked against the REAL reference core
  (``gather_view`` + ring concat + ``layers`` sdpa math, which IS
  bitwise-equal to the packaged oracle) so the oracle can't drift into a
  strawman. Bit-identity is not achievable: the kernel sums the softmax
  denominator page by page with running-max rescaling, and the weighted
  values page by page before dividing, where the reference normalizes
  first and reduces each in one pass — the same terms in another fp32
  association, hence ulps.
* ENGINE boundary: fused vs reference serving produces IDENTICAL token
  streams across every paged-layout arch in the config matrix × write
  modes (direct / staged / adaptive) × chunked scheduling — ulp noise
  never flips a greedy argmax in these sweeps, and token equality is the
  contract serving actually needs.

Also here: ``core.paths.resolve_attention`` negotiation and the
drain-kernel selection (``core.paths.resolve_drain_kernel``, its own
parity included).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core.paths import resolve_attention, resolve_drain_kernel
from repro.data import synthetic_requests
from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode_paged
from repro.kvcache import paged as PG
from repro.models import build_model
from repro.serve import BatchConfig, BatchedServeEngine
from repro.serve.scheduler import paged_capable

MAX_SEQ, PLEN, MAX_NEW = 32, 8, 5


def _paged_archs():
    picks = []
    for arch in sorted(ARCHS):
        cfg = get_config(arch).reduced()
        if paged_capable(build_model(cfg)):
            picks.append(arch)
    return picks


PAGED_ARCHS = _paged_archs()


# ---------------------------------------------------------------------------
# kernel vs oracle: fp32 ulp-level, swept
# ---------------------------------------------------------------------------


def _assert_ulp_close(actual, desired):
    """Kernel-boundary parity: ~1e-7 observed, 10x margin. Real kernel
    bugs (wrong page, stale mask, dropped lane) miss by >= 1e-3."""
    np.testing.assert_allclose(np.asarray(actual), np.asarray(desired),
                               atol=2e-6, rtol=1e-4)


# every kernel case reads a stacked pool of this many layers; reading the
# last layer as well as the first proves the index map selects the layer
N_LAYERS = 3
LAYERS = [0, N_LAYERS - 1]


def _rand_inputs(rng, b, c, hq, hkv, d, nb, ps, p, r):
    q = jnp.asarray(rng.randn(b, c, hq, d), jnp.float32)
    pk = jnp.asarray(rng.randn(N_LAYERS, nb, ps, hkv, d), jnp.float32)
    pv = jnp.asarray(rng.randn(N_LAYERS, nb, ps, hkv, d), jnp.float32)
    blocks = jnp.asarray(rng.randint(0, nb, (b, p)), jnp.int32)
    view_ok = jnp.asarray(rng.rand(b, c, p * ps) > 0.35)
    ring = None
    if r:
        ring = (jnp.asarray(rng.randn(N_LAYERS, b, r, hkv, d), jnp.float32),
                jnp.asarray(rng.randn(N_LAYERS, b, r, hkv, d), jnp.float32))
    return q, pk, pv, blocks, view_ok, ring


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("b,c,hq,hkv,d,nb,ps,p,r", [
    (2, 1, 4, 4, 16, 8, 4, 4, 0),     # step decode, MHA, no ring
    (2, 1, 4, 2, 16, 8, 4, 4, 8),     # step decode, GQA group 2 + ring
    (1, 1, 8, 1, 32, 6, 8, 3, 4),     # MQA (group 8)
    (3, 4, 4, 2, 16, 12, 8, 4, 8),    # chunk slab C=4
    (2, 8, 4, 4, 8, 10, 4, 5, 2),     # chunk C=8, small ring
    (1, 3, 6, 3, 16, 9, 2, 6, 6),     # odd page size / group 2
])
def test_kernel_matches_oracle(b, c, hq, hkv, d, nb, ps, p, r, layer):
    rng = np.random.RandomState(b * 100 + c * 10 + hq)
    q, pk, pv, blocks, view_ok, ring = _rand_inputs(
        rng, b, c, hq, hkv, d, nb, ps, p, r)
    if ring:
        ring_ok = jnp.asarray(rng.rand(b, r) > 0.5)
        args = (*ring, ring_ok)
    else:
        args = (None, None, None)
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, *args,
                             interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                          *args)
    _assert_ulp_close(out, expected)


RING_STATES = {
    "empty":    lambda b, r, rng: np.zeros((b, r), bool),
    "partial":  lambda b, r, rng: np.broadcast_to(
        np.arange(r)[None] < (r // 2), (b, r)),
    "full":     lambda b, r, rng: np.ones((b, r), bool),
    # wrapped/conflict-shaped occupancy: holes mid-ring (entries that
    # were drained out of order / lanes that skipped a column)
    "wrapped":  lambda b, r, rng: np.roll(
        np.arange(r)[None] < (r - 1), rng.randint(r), axis=1
    ) & np.ones((b, 1), bool),
    "conflict": lambda b, r, rng: rng.rand(b, r) > 0.5,
}


@pytest.mark.parametrize("state", sorted(RING_STATES))
@pytest.mark.parametrize("c", [1, 4])
def test_kernel_ring_states(state, c):
    b, hq, hkv, d, nb, ps, p, r = 3, 4, 2, 16, 12, 4, 4, 6
    rng = np.random.RandomState(abs(hash(state)) % 2**31)
    q, pk, pv, blocks, view_ok, ring = _rand_inputs(
        rng, b, c, hq, hkv, d, nb, ps, p, r)
    ring_ok = jnp.asarray(RING_STATES[state](b, r, rng))
    layer = N_LAYERS - 1
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, *ring,
                             ring_ok, interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                          *ring, ring_ok)
    _assert_ulp_close(out, expected)


@pytest.mark.parametrize("layer", LAYERS)
def test_kernel_dead_slot_and_unallocated_pages(layer):
    """Fully-masked rows (retired slots) and clamped unallocated pages:
    the kernel walks block 0's garbage exactly like the clamped reference
    gather, so even degenerate outputs agree."""
    b, c, hq, hkv, d, nb, ps, p, r = 2, 1, 4, 2, 16, 8, 4, 4, 4
    rng = np.random.RandomState(0)
    q, pk, pv, _, _, ring = _rand_inputs(rng, b, c, hq, hkv, d, nb, ps, p, r)
    # slot 1: nothing allocated -> clamped table walks block 0, all masked
    blocks = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    view_ok = jnp.asarray(
        np.stack([np.ones((c, p * ps), bool), np.zeros((c, p * ps), bool)]))
    ring_ok = jnp.asarray([[True, False, True, False],
                           [False, False, False, False]])
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, *ring,
                             ring_ok, interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                          *ring, ring_ok)
    _assert_ulp_close(out, expected)


def test_oracle_matches_reference_core_bitwise():
    """The packaged oracle IS the reference path's math — gather the view
    through the page table, concat the ring lanes, repeat KV heads, and
    run the exact ``layers`` sdpa op order — and the two identical op
    sequences ARE bitwise-equal (no strawman); the kernel then sits
    within ulp of both."""
    b, c, hq, hkv, d, nb, ps, p, r = 2, 3, 4, 2, 16, 10, 4, 4, 6
    rng = np.random.RandomState(3)
    q, pk, pv, blocks, view_ok, ring = _rand_inputs(
        rng, b, c, hq, hkv, d, nb, ps, p, r)
    ring_ok = jnp.asarray(rng.rand(b, r) > 0.4)
    layer = 1

    rows = (np.asarray(blocks)[:, :, None] * ps
            + np.arange(ps)[None, None]).reshape(b, -1)
    k = jnp.concatenate(
        [PG.gather_view(pk[layer], jnp.asarray(rows, jnp.int32)),
         ring[0][layer]], axis=1)
    v = jnp.concatenate(
        [PG.gather_view(pv[layer], jnp.asarray(rows, jnp.int32)),
         ring[1][layer]], axis=1)
    mask = jnp.concatenate(
        [view_ok, jnp.broadcast_to(ring_ok[:, None], (b, c, r))], axis=2)
    reps = hq // hkv
    kf = jnp.repeat(k, reps, axis=2)
    vf = jnp.repeat(v, reps, axis=2)
    # layers._sdpa_once op order, verbatim
    logits = jnp.einsum("bshk,bthk->bhst", q, kf).astype(jnp.float32) \
        * (d ** -0.5)
    logits = jnp.where(mask[:, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    core = jnp.einsum("bhst,bthk->bshk", probs, vf)

    oracle = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                        *ring, ring_ok)
    kernel = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, *ring,
                                ring_ok, interpret=True)
    np.testing.assert_array_equal(np.asarray(core), np.asarray(oracle))
    _assert_ulp_close(kernel, core)


# ---------------------------------------------------------------------------
# model level: fused vs reference decode paths
# ---------------------------------------------------------------------------


def _paged_cache_with_ring(model, rng, n_slots=4, nb=16, ps=4, mp=8, rs=4):
    cfg = model.cfg
    cache = PG.make_paged_kv(
        cfg.n_layers, nb, ps, n_slots, mp,
        cfg.n_kv_heads or cfg.n_heads, cfg.resolved_head_dim,
        ring_size=rs)
    cache["page_table"] = jnp.asarray(
        [[0, 1, 2, 3, -1, -1, -1, -1],
         [4, 5, -1, -1, -1, -1, -1, -1],
         [6, 7, 8, -1, -1, -1, -1, -1],
         [-1] * 8], jnp.int32)
    for key in ("pages_k", "pages_v", "ring_k", "ring_v"):
        cache[key] = jnp.asarray(rng.randn(*cache[key].shape), jnp.float32)
    cache["ring_pos"] = jnp.asarray(
        [[2, -1, 5, -1], [1, -1, -1, -1], [-1] * 4, [-1] * 4], jnp.int32)
    cache["ring_fill"] = jnp.asarray(3, jnp.int32)
    return cache


@pytest.mark.parametrize("variant", ["step", "chunk"])
def test_model_fused_matches_reference(variant):
    """decode_step_paged / decode_chunk_paged under attention='fused' vs
    'reference': identical argmax tokens, allclose logits, allclose cache
    (cross-graph XLA fusion of the k/v projections carries ~1 ulp — see
    module docstring)."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    rng = np.random.RandomState(1)
    cache = _paged_cache_with_ring(model, rng)
    wm = jnp.asarray([True, True, True, False])
    um = jnp.asarray([True, False, True, False])
    toks = jnp.asarray(rng.randint(0, cfg.vocab, (4, 4)), jnp.int32)
    outs = {}
    for attention in ("reference", "fused"):
        if variant == "step":
            tok = jnp.asarray([5, 9, 3, 0], jnp.int32)
            pos = jnp.asarray([10, 6, 9, 0], jnp.int32)
            outs[attention] = model.decode_step_paged(
                params, dict(cache), tok, pos, wm, unload_mask=um,
                attention=attention)
        else:
            start = jnp.asarray([10, 6, 9, 0], jnp.int32)
            nv = jnp.asarray([4, 1, 2, 0], jnp.int32)
            outs[attention] = model.decode_chunk_paged(
                params, dict(cache), toks, start, nv, wm,
                unload_mask=(nv == 1) & wm, attention=attention)
    lr, cr = outs["reference"]
    lf, cf = outs["fused"]
    np.testing.assert_array_equal(np.asarray(jnp.argmax(lr, -1)),
                                  np.asarray(jnp.argmax(lf, -1)))
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                               atol=1e-5, rtol=1e-5)
    for key in cr:
        np.testing.assert_allclose(
            np.asarray(cr[key], np.float32), np.asarray(cf[key], np.float32),
            atol=1e-5, rtol=1e-5, err_msg=key)


# ---------------------------------------------------------------------------
# engine level: token parity across the config matrix × write modes
# ---------------------------------------------------------------------------


def _serve_tokens(model, params, *, attention, write_mode="adaptive",
                  chunked=False, vocab=256):
    queue = synthetic_requests(3, [PLEN, 5] if chunked else PLEN, vocab,
                               MAX_NEW, seed=7)
    eng = BatchedServeEngine(model, params, BatchConfig(
        max_seq=MAX_SEQ, n_slots=2, segment_len=2, page_size=4,
        write_mode=write_mode, ring_size=2, hot_threshold=2,
        chunked=chunked, chunk_size=3, attention=attention,
    ), _internal=True)
    return eng.serve(queue)


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_engine_fused_token_parity_config_matrix(arch):
    """Every paged-layout arch (the GQA/MQA/bias/rope spread of the config
    matrix) serves the SAME token streams fused and reference."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    ref_out = _serve_tokens(model, params, attention="reference",
                            vocab=cfg.vocab)
    fused_out = _serve_tokens(model, params, attention="fused",
                              vocab=cfg.vocab)
    assert set(ref_out) == set(fused_out) == {0, 1, 2}
    for r in ref_out:
        np.testing.assert_array_equal(ref_out[r], fused_out[r])


@pytest.mark.parametrize("write_mode", ["direct", "staged", "adaptive"])
@pytest.mark.parametrize("chunked", [False, True])
def test_engine_fused_token_parity_write_modes(write_mode, chunked):
    """Fused vs reference across write modes (direct / staged / adaptive —
    staged keeps undrained ring lanes live at read time, exercising the
    kernel's second source, including full-ring and conflict-forced
    drains with ring_size=2) and both scheduling modes."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    ref_out = _serve_tokens(model, params, attention="reference",
                            write_mode=write_mode, chunked=chunked,
                            vocab=cfg.vocab)
    fused_out = _serve_tokens(model, params, attention="fused",
                              write_mode=write_mode, chunked=chunked,
                              vocab=cfg.vocab)
    for r in ref_out:
        np.testing.assert_array_equal(ref_out[r], fused_out[r])


# ---------------------------------------------------------------------------
# negotiation + drain auto-selection
# ---------------------------------------------------------------------------


def test_resolve_attention_negotiation(monkeypatch):
    monkeypatch.delenv("REPRO_ATTENTION", raising=False)
    # explicit choices pass through where legal
    assert resolve_attention("fused", layout="paged") == "fused"
    assert resolve_attention("reference", layout="paged") == "reference"
    assert resolve_attention("reference", layout="lanes") == "reference"
    # fused needs a page table to walk: loud errors, not silent fallback
    with pytest.raises(ValueError, match="paged"):
        resolve_attention("fused", layout="lanes")
    with pytest.raises(ValueError, match="paged"):
        resolve_attention("fused", layout="paged", arch_paged_capable=False)
    with pytest.raises(ValueError, match="unknown attention"):
        resolve_attention("turbo", layout="paged")
    # auto: fused where the kernel compiles natively, reference on CPU
    assert resolve_attention("auto", layout="paged", backend="tpu") == "fused"
    assert resolve_attention("auto", layout="paged", backend="cpu") \
        == "reference"
    assert resolve_attention("auto", layout="lanes", backend="tpu") \
        == "reference"
    # CI override: force the kernel through auto configs
    monkeypatch.setenv("REPRO_ATTENTION", "fused")
    assert resolve_attention("auto", layout="paged", backend="cpu") == "fused"
    monkeypatch.setenv("REPRO_ATTENTION", "reference")
    assert resolve_attention("auto", layout="paged", backend="tpu") \
        == "reference"


def test_engine_resolves_attention(monkeypatch):
    monkeypatch.delenv("REPRO_ATTENTION", raising=False)
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    eng = BatchedServeEngine(model, params,
                             BatchConfig(max_seq=MAX_SEQ), _internal=True)
    # auto on CPU -> reference (the kernel is the TPU serving path)
    assert eng.attention == "reference"
    monkeypatch.setenv("REPRO_ATTENTION", "fused")
    eng = BatchedServeEngine(model, params,
                             BatchConfig(max_seq=MAX_SEQ), _internal=True)
    assert eng.attention == "fused"
    with pytest.raises(ValueError, match="paged"):
        BatchedServeEngine(model, params, BatchConfig(
            max_seq=MAX_SEQ, kv_layout="lanes", attention="fused"),
            _internal=True)


def test_drain_kernel_auto_selection(monkeypatch):
    """The plan picks the drain kernel wherever the layout supports it
    without callers opting in — REPRO_DRAIN_KERNEL=1 routes CPU CI
    through the interpret kernel, and the result is bitwise the jnp
    drain."""
    monkeypatch.delenv("REPRO_DRAIN_KERNEL", raising=False)
    assert resolve_drain_kernel() is (jax.default_backend() != "cpu")
    assert resolve_drain_kernel(backend="tpu") is True
    assert resolve_drain_kernel(backend="cpu") is False
    assert resolve_drain_kernel(False, backend="tpu") is False
    monkeypatch.setenv("REPRO_DRAIN_KERNEL", "1")
    assert resolve_drain_kernel() is True
    monkeypatch.setenv("REPRO_DRAIN_KERNEL", "0")
    assert resolve_drain_kernel(backend="tpu") is False

    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    rng = np.random.RandomState(5)
    cache = _paged_cache_with_ring(model, rng)
    plain = PG.drain_ring(dict(cache), use_kernel=False)
    monkeypatch.setenv("REPRO_DRAIN_KERNEL", "1")
    auto = PG.drain_ring(dict(cache), use_kernel=resolve_drain_kernel())
    for key in plain:
        np.testing.assert_array_equal(np.asarray(plain[key]),
                                      np.asarray(auto[key]), err_msg=key)
    assert int(auto["ring_fill"]) == 0
    assert (np.asarray(auto["ring_pos"]) == -1).all()
