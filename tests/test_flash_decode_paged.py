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


def _mask_bound(view_ok, ps):
    """The kernel's per-slot page bound (``PG.page_bound``) for a mask:
    the pages up to each slot's last valid row."""
    ok = np.asarray(view_ok).any(axis=1)                  # [B, P*ps]
    context = np.where(ok.any(-1), ok.shape[-1] - np.argmax(ok[:, ::-1], -1),
                       0)
    return PG.page_bound(jnp.asarray(context), jnp.asarray(context > 0), ps,
                         ok.shape[-1] // ps)


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
    bound = _mask_bound(view_ok, ps)
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, bound, *args,
                             interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                          bound, *args)
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
    bound = _mask_bound(view_ok, ps)
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, bound, *ring,
                             ring_ok, interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                          bound, *ring, ring_ok)
    _assert_ulp_close(out, expected)


@pytest.mark.parametrize("layer", LAYERS)
def test_kernel_dead_slot_and_unallocated_pages(layer):
    """Fully-masked rows (retired slots) and clamped unallocated pages:
    walking the whole table (a bound of ``P``), the kernel walks block 0's
    garbage exactly like the clamped reference gather, so even degenerate
    outputs agree."""
    q, pk, pv, blocks, view_ok, ring, ring_ok = _dead_slot_inputs()
    p = blocks.shape[1]
    bound = jnp.full((2,), p, jnp.int32)
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, bound, *ring,
                             ring_ok, interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                          bound, *ring, ring_ok)
    _assert_ulp_close(out, expected)


def _dead_slot_inputs():
    b, c, hq, hkv, d, nb, ps, p, r = 2, 1, 4, 2, 16, 8, 4, 4, 4
    rng = np.random.RandomState(0)
    q, pk, pv, _, _, ring = _rand_inputs(rng, b, c, hq, hkv, d, nb, ps, p, r)
    # slot 1: nothing allocated -> clamped table walks block 0, all masked
    blocks = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    view_ok = jnp.asarray(
        np.stack([np.ones((c, p * ps), bool), np.zeros((c, p * ps), bool)]))
    ring_ok = jnp.asarray([[True, False, True, False],
                           [False, False, False, False]])
    return q, pk, pv, blocks, view_ok, ring, ring_ok


@pytest.mark.parametrize("layer", LAYERS)
def test_kernel_dead_slot_walks_page_zero(layer):
    """Serving's bound (``PG.page_bound``) for the same two slots: the live
    slot walks its whole table, and the dead one (bound 0) walks page 0
    alone, so its degenerate output is the oracle's over a one-page
    table (block 0's garbage and the ring lanes, all masked)."""
    q, pk, pv, blocks, view_ok, ring, ring_ok = _dead_slot_inputs()
    ps = pk.shape[2]
    bound = _mask_bound(view_ok, ps)
    np.testing.assert_array_equal(bound, [blocks.shape[1], 0])
    out = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, bound, *ring,
                             ring_ok, interpret=True)
    whole = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                       bound, *ring, ring_ok)
    page0 = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks[:, :1],
                                       view_ok[:, :, :ps], bound, *ring,
                                       ring_ok)
    _assert_ulp_close(out[0], whole[0])
    _assert_ulp_close(out[1], page0[1])


@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("c", [1, 4])
def test_kernel_stops_at_each_slots_bound(c, group, ring):
    """Per-slot page bounds of 0, 1, part of the table and all of it
    (``PG.page_bound``): every page at or past a slot's bound is NaN in K
    and V, and the live slots' outputs are still finite, ulp-close to the
    oracle (run on the same inputs with those pages finite: it gathers
    them) and bitwise the full walk's on finite pages. A walk that folded
    a masked NaN page would poison its sum (0 x NaN)."""
    b, hkv, d, ps, p, r = 4, 2, 16, 4, 5, 6
    hq = group * hkv
    bound = np.array([0, 1, 3, p], np.int32)
    live = bound > 0
    rng = np.random.RandomState(100 * c + 10 * group + ring)
    q, pk, pv, _, _, lanes = _rand_inputs(rng, b, c, hq, hkv, d, b * p, ps,
                                          p, r)
    # every page of every slot on a block of its own, so a block past one
    # slot's bound is never live in another's
    blocks = rng.permutation(b * p).reshape(b, p).astype(np.int32)
    context = np.where(live, (bound - 1) * ps + 1 + rng.randint(ps, size=b),
                       0)
    rows = np.arange(p * ps)[None, None, :]
    view_ok = (rows < context[:, None, None]) & (rng.rand(b, c, p * ps) > 0.35)
    view_ok[live, :, 0] = True       # row 0 of page 0: a live slot's first
    dead = blocks[np.arange(p)[None, :] >= bound[:, None]]
    poisoned = [x.at[:, dead].set(jnp.nan) for x in (pk, pv)]
    args = (jnp.asarray(blocks), jnp.asarray(view_ok))
    ring_args = ((*lanes, jnp.asarray(rng.rand(b, r) > 0.5)) if ring
                 else (None, None, None))
    layer = N_LAYERS - 1

    out = flash_decode_paged(q, *poisoned, layer, *args, jnp.asarray(bound),
                             *ring_args, interpret=True)
    full = flash_decode_paged(q, pk, pv, layer, *args,
                              jnp.full((b,), p, jnp.int32), *ring_args,
                              interpret=True)
    expected = ref.flash_decode_paged_ref(q, pk, pv, layer, *args,
                                          jnp.asarray(bound), *ring_args)
    out, full = np.asarray(out)[live], np.asarray(full)[live]
    assert np.isfinite(out).all()
    _assert_ulp_close(out, np.asarray(expected)[live])
    np.testing.assert_array_equal(out, full)


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

    bound = _mask_bound(view_ok, ps)
    oracle = ref.flash_decode_paged_ref(q, pk, pv, layer, blocks, view_ok,
                                        bound, *ring, ring_ok)
    kernel = flash_decode_paged(q, pk, pv, layer, blocks, view_ok, bound,
                                *ring, ring_ok, interpret=True)
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
    """decode_chunk_paged at C = 1 (the decode step) and at C = 4 under
    attention='fused' vs 'reference': identical argmax tokens, allclose
    logits, allclose cache (cross-graph XLA fusion of the k/v projections
    carries ~1 ulp — see module docstring). The dead slot (no write, bound
    0) walks page 0 alone (``PG.page_bound``), so its row is held to the
    reference over the view that bound defines: the same cache with one
    page per table."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    rng = np.random.RandomState(1)
    cache = _paged_cache_with_ring(model, rng)
    wm = jnp.asarray([True, True, True, False])
    um = jnp.asarray([True, False, True, False])
    toks = jnp.asarray(rng.randint(0, cfg.vocab, (4, 4)), jnp.int32)
    page0 = dict(cache, page_table=cache["page_table"][:, :1])
    outs = {}
    for attention, kv in (("reference", cache), ("fused", cache),
                          ("page0", page0)):
        impl = "reference" if attention == "page0" else attention
        if variant == "step":
            tok = jnp.asarray([5, 9, 3, 0], jnp.int32)
            pos = jnp.asarray([10, 6, 9, 0], jnp.int32)
            outs[attention] = model.decode_chunk_paged(
                params, dict(kv), tok[:, None], pos, wm.astype(jnp.int32),
                wm, unload_mask=um, attention=impl)
        else:
            start = jnp.asarray([10, 6, 9, 0], jnp.int32)
            nv = jnp.asarray([4, 1, 2, 0], jnp.int32)
            outs[attention] = model.decode_chunk_paged(
                params, dict(kv), toks, start, nv, wm,
                unload_mask=(nv == 1) & wm, attention=impl)
    live = np.asarray(wm)
    (lr, cr), (lf, cf), (l0, c0) = (
        (np.asarray(lg), {k: np.asarray(v, np.float32) for k, v in c.items()})
        for lg, c in (outs["reference"], outs["fused"], outs["page0"]))
    # the reference each slot's walk defines: the whole table where it
    # writes, page 0 where it does not
    lr = np.where(live.reshape((-1,) + (1,) * (lr.ndim - 1)), lr, l0)
    for key in ("ring_k", "ring_v"):
        cr[key] = np.where(live[None, :, None, None, None], cr[key],
                           c0[key])
    np.testing.assert_array_equal(np.argmax(lr, -1), np.argmax(lf, -1))
    np.testing.assert_allclose(lr, lf, atol=1e-5, rtol=1e-5)
    for key in cr:
        np.testing.assert_allclose(cr[key], cf[key], atol=1e-5, rtol=1e-5,
                                   err_msg=key)
    # the dead slot's staged tile sits at a lane it never reads
    assert (cf["ring_pos"][~live] < 0).all()


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
