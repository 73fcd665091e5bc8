"""Mesh-sharded serving: bit-parity + placement invariants (DESIGN.md §9).

Runs only on a multi-device platform — the dedicated CI lane exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE jax is
imported; on the single-device tier-1 host every test self-skips.

The contract under test: with ``parallel=ParallelConfig.tensor(tp)`` the
paged pool + staging ring split on the HEAD axis while the page table,
free-list, and every routing decision stay replicated — so the engine's
OUTPUT (tokens, stats, per-request write counts) is bit-identical to the
unsharded engine across write modes, chunked prefill, prefix caching,
and the host tier, and drains never cross shard boundaries.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import synthetic_requests
from repro.distributed import ParallelConfig, serve_cache_pspec
from repro.models import build_model
from repro.serve import (
    Engine,
    EngineConfig,
    MemoryConfig,
    SamplingParams,
)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="sharded serving needs >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [9, 8, 7, 6, 5], [2, 4, 6]]


def _serve(arch, tp, *, path="adaptive", chunked=False, memory=None,
           attention=None, n_slots=4, max_seq=48, prompts=PROMPTS,
           max_tokens=10, segment_len=4):
    cfg = EngineConfig(
        arch=arch, max_seq=max_seq, n_slots=n_slots, segment_len=segment_len,
        page_size=4, ring_size=4, path=path, chunked=chunked,
        chunk_size=4, memory=memory, attention=attention,
        parallel=ParallelConfig.tensor(tp) if tp > 1 else None)
    eng = Engine.from_config(cfg)
    comps = eng.generate(list(prompts), SamplingParams(max_tokens=max_tokens))
    return eng, comps


def _payload(eng, comps):
    return ([c.tokens.tolist() for c in comps],
            [c.path_counts for c in comps],
            dict(eng.stats))


# ---------------------------------------------------------------------------
# bit-parity: sharded == unsharded across the paged config matrix
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "qwen2-7b"])
@pytest.mark.parametrize("path", ["direct", "staged", "adaptive"])
def test_sharded_bit_parity_write_modes(arch, path):
    tp = min(2, jax.device_count())
    ref = _payload(*_serve(arch, 1, path=path))
    got = _payload(*_serve(arch, tp, path=path))
    assert got == ref


@needs_mesh
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "qwen2-7b"])
def test_sharded_bit_parity_chunked(arch):
    tp = min(4, jax.device_count())
    ref = _payload(*_serve(arch, 1, chunked=True))
    got = _payload(*_serve(arch, tp, chunked=True))
    assert got == ref


@needs_mesh
def test_sharded_bit_parity_indivisible_heads_fall_back_replicated():
    # tp=8 does not divide stablelm's reduced Hkv: the pool replicates
    # (serve_cache_pspec divisibility rule) but output parity still holds
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device lane")
    ref = _payload(*_serve("stablelm-1.6b", 1))
    got = _payload(*_serve("stablelm-1.6b", 8))
    assert got == ref


@needs_mesh
@pytest.mark.parametrize("drain_kernel", [False, True])
def test_sharded_bit_parity_fused_attention(drain_kernel):
    # the head-sharded fused read path: Hq=Hkv=4 divide tp=2, so validate
    # admits attention='fused' and the kernel (interpret mode on CPU)
    # must reproduce the reference engine's tokens bit-for-bit; with the
    # drain kernel the staging ring drains per shard (shard_map) inside
    # the scan (ring_size 4 < segment_len 8)
    from repro.serve import AttentionConfig

    tp = min(2, jax.device_count())
    attn = AttentionConfig(impl="fused", drain_kernel=drain_kernel)
    kw = dict(attention=attn, path="staged", segment_len=8, max_tokens=6)
    ref_eng, ref_comps = _serve("stablelm-1.6b", 1, **kw)
    eng, comps = _serve("stablelm-1.6b", tp, **kw)
    assert eng.scheduler._drain_kernel is drain_kernel
    assert eng.stats["drains"] > 0
    assert _payload(eng, comps) == _payload(ref_eng, ref_comps)


@needs_mesh
def test_sharded_bit_parity_prefix_cache():
    # cold serve, warm restart over the kept (sharded) pool, serve the
    # same queue again: the warm pass must HIT the prefix index and stay
    # bit-identical to the unsharded engine doing the same dance
    tp = min(2, jax.device_count())

    def run(tp):
        cfg = EngineConfig(
            arch="stablelm-1.6b", max_seq=32, n_slots=2, segment_len=2,
            page_size=4, ring_size=3, path="adaptive", chunked=True,
            chunk_size=3, memory=MemoryConfig(prefix_cache=True),
            parallel=ParallelConfig.tensor(tp) if tp > 1 else None)
        eng = Engine.from_config(cfg)
        mk = lambda: synthetic_requests(  # noqa: E731
            3, 8, 64, 5, seed=3, shared_prefix=8)
        cold = eng.serve(mk())
        eng.reset(keep_cache=True)
        warm = eng.serve(mk())
        assert all(np.array_equal(warm[r], cold[r]) for r in cold)
        return {r: v.tolist() for r, v in warm.items()}, dict(eng.stats)

    ref_out, ref_stats = run(1)
    got_out, got_stats = run(tp)
    assert got_out == ref_out
    assert got_stats == ref_stats
    assert got_stats["prefix_hit_rows"] > 0


@needs_mesh
def test_sharded_bit_parity_host_tier():
    tp = min(2, jax.device_count())

    def run(tp):
        # 8 requests x 4 pages over a 5-block pool: parking must trigger
        cfg = EngineConfig(
            arch="stablelm-1.6b", max_seq=32, n_slots=4, segment_len=4,
            page_size=4, ring_size=3, path="adaptive", n_blocks=5,
            memory=MemoryConfig(host_tier=True),
            parallel=ParallelConfig.tensor(tp) if tp > 1 else None)
        eng = Engine.from_config(cfg)
        q = synthetic_requests(8, 8, 64, 9, seed=2)
        out = eng.serve(q)
        return {k: v.tolist() for k, v in out.items()}, dict(eng.stats)

    ref_out, ref_stats = run(1)
    got_out, got_stats = run(tp)
    assert got_out == ref_out
    assert got_stats == ref_stats
    assert got_stats["host_unloads"] > 0     # the tier actually engaged


# ---------------------------------------------------------------------------
# placement invariants: what is sharded, what is replicated
# ---------------------------------------------------------------------------


@needs_mesh
def test_pool_and_ring_are_head_sharded_decision_plane_replicated():
    tp = min(2, jax.device_count())
    eng, _ = _serve("stablelm-1.6b", tp, path="adaptive")
    cache = eng.scheduler.cache
    h = cache["pages_k"].shape[3]
    assert h % tp == 0
    for name in ("pages_k", "pages_v", "ring_k", "ring_v"):
        arr = cache[name]
        shard = arr.addressable_shards[0].data
        assert shard.shape[3] == h // tp, (name, shard.shape)
        assert arr.sharding.spec[3] == "model"
    for name in ("page_table", "ring_pos", "ring_fill"):
        arr = cache[name]
        assert arr.addressable_shards[0].data.shape == arr.shape, name


@needs_mesh
def test_per_shard_drain_volumes_sum_to_unsharded():
    # the keep-the-unload-local invariant: each shard drains exactly its
    # own head slice — per-shard drained element volumes sum to the
    # unsharded drain volume, and the drain COUNT telemetry is identical
    tp = min(2, jax.device_count())
    # ring_size (4) < segment_len (8): full-ring drains fire INSIDE the
    # jitted scan, not just at the segment boundary
    e1, _ = _serve("stablelm-1.6b", 1, path="staged", segment_len=8)
    e2, _ = _serve("stablelm-1.6b", tp, path="staged", segment_len=8)
    assert e1.stats["drains"] > 0
    assert e2.stats["drains"] == e1.stats["drains"]
    ring = e2.scheduler.cache["ring_k"]
    l, s, r, h, dh = e1.scheduler.cache["ring_k"].shape
    per_drain_unsharded = l * s * r * h * dh
    shard_vols = [np.prod(sh.data.shape) for sh in ring.addressable_shards]
    assert sum(shard_vols) == per_drain_unsharded
    assert all(v == per_drain_unsharded // tp for v in shard_vols)


@needs_mesh
def test_sharded_drain_kernel_matches_jnp_drain():
    # the drain kernel run per head shard (shard_map over the serving
    # placements) lands every staged row where the unsharded jnp
    # scatter does: a copy, so bit-exact
    from repro.distributed.sharding import serve_cache_shardings
    from repro.kvcache import paged as PG

    tp = min(4, jax.device_count())
    mesh = ParallelConfig.tensor(tp).build_mesh()
    cfg = get_config("stablelm-1.6b").reduced()
    n_slots, pages, ps, ring = 4, 3, 4, 4
    rng = np.random.default_rng(0)
    cache = PG.make_paged_kv(cfg.n_layers, n_slots * pages, ps, n_slots,
                             pages, cfg.n_kv_heads, cfg.resolved_head_dim,
                             ring_size=ring)
    for key in ("pages_k", "pages_v", "ring_k", "ring_v"):
        cache[key] = rng.standard_normal(cache[key].shape, np.float32)
    cache["page_table"] = rng.permutation(n_slots * pages).reshape(
        n_slots, pages).astype(np.int32)
    pos = np.stack([rng.permutation(pages * ps)[:ring]
                    for _ in range(n_slots)])
    cache["ring_pos"] = np.where(rng.random(pos.shape) < 0.7, pos,
                                 -1).astype(np.int32)
    cache["ring_fill"] = np.int32(ring)
    cache = {k: jax.numpy.asarray(v) for k, v in cache.items()}
    want = PG.drain_ring(dict(cache), use_kernel=False)
    shardings = serve_cache_shardings(cfg, mesh, cache)
    got = jax.jit(lambda c: PG.drain_ring(c, use_kernel=True,
                                          shardings=shardings))(
        {k: jax.device_put(v, shardings[k]) for k, v in cache.items()})
    assert got["pages_k"].sharding.is_equivalent_to(
        shardings["pages_k"], got["pages_k"].ndim)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)


@needs_mesh
def test_telemetry_readback_is_replicated():
    # satellite: monitor/stat carries must come back REPLICATED from the
    # jitted segment (replicate_for_readback) so host np.asarray reads a
    # whole layout at any mesh size
    tp = min(2, jax.device_count())
    eng, comps = _serve("stablelm-1.6b", tp, path="adaptive")
    mon = eng.scheduler.mon_state
    for leaf in jax.tree.leaves(mon):
        if hasattr(leaf, "sharding"):
            assert leaf.sharding.is_fully_replicated, leaf.sharding
    # and the per-request write counts the replicated readback feeds are
    # exactly the unsharded engine's (satellite: psum-free exact stats)
    ref_eng, ref_comps = _serve("stablelm-1.6b", 1, path="adaptive")
    assert [c.path_counts for c in comps] == \
        [c.path_counts for c in ref_comps]


@needs_mesh
def test_serve_cache_pspec_divisibility_rule():
    tp = min(2, jax.device_count())
    cfg = get_config("stablelm-1.6b").reduced()
    mesh = ParallelConfig.tensor(tp).build_mesh()
    ps = serve_cache_pspec(cfg, mesh, "pages_k", (2, 16, 4, 4, 16))
    assert ps[3] == "model"
    ps = serve_cache_pspec(cfg, mesh, "pages_k", (2, 16, 4, 3, 16))
    assert ps[3] is None                       # indivisible: replicate
    ps = serve_cache_pspec(cfg, mesh, "page_table", (4, 8))
    assert all(a is None for a in ps)


# ---------------------------------------------------------------------------
# one ParallelConfig drives both stacks
# ---------------------------------------------------------------------------


@needs_mesh
def test_parallel_config_drives_train_state_shardings():
    tp = min(2, jax.device_count())
    pc = ParallelConfig.tensor(tp)
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0), 32)
    shardings = pc.param_shardings(cfg, params)
    assert shardings is not None
    specs = {s.spec for s in jax.tree.leaves(shardings)}
    assert any("model" in jax.tree.leaves(tuple(sp)) for sp in specs
               if len(sp)), specs
    # and the serve side accepts the very same object (no translation)
    eng, _ = _serve("stablelm-1.6b", tp)
    assert eng.scheduler.plan.parallel == pc


# ---------------------------------------------------------------------------
# head-sharded kernel wrapper
# ---------------------------------------------------------------------------


@needs_mesh
def test_flash_decode_paged_sharded_matches_unsharded():
    from repro.kernels.flash_decode import (
        flash_decode_paged,
        flash_decode_paged_sharded,
    )

    tp = min(2, jax.device_count())
    mesh = ParallelConfig.tensor(tp).build_mesh()
    rng = np.random.default_rng(0)
    nl, b, c, hq, hkv, d, nb, ps, npg, r = 3, 2, 1, 4, 2, 8, 6, 4, 3, 4
    q = rng.standard_normal((b, c, hq, d)).astype(np.float32)
    pk = rng.standard_normal((nl, nb, ps, hkv, d)).astype(np.float32)
    pv = rng.standard_normal((nl, nb, ps, hkv, d)).astype(np.float32)
    blocks = rng.integers(0, nb, (b, npg)).astype(np.int32)
    ok = rng.random((b, c, npg * ps)) < 0.7
    rk = rng.standard_normal((nl, b, r, hkv, d)).astype(np.float32)
    rv = rng.standard_normal((nl, b, r, hkv, d)).astype(np.float32)
    rok = rng.random((b, r)) < 0.5
    ok[:, :, 0] = True                         # softmax needs >= 1 source
    layer = nl - 1
    ref = flash_decode_paged(q, pk, pv, layer, blocks, ok, rk, rv, rok,
                             interpret=True)
    got = flash_decode_paged_sharded(mesh, q, pk, pv, layer, blocks, ok, rk,
                                     rv, rok, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    with pytest.raises(ValueError, match="divide"):
        flash_decode_paged_sharded(
            mesh, q[:, :, :3], pk, pv, layer, blocks, ok, rk, rv, rok,
            interpret=True)


# ---------------------------------------------------------------------------
# error surface on a real multi-device platform
# ---------------------------------------------------------------------------


@needs_mesh
def test_mesh_x_lanes_rejected_at_engine_build():
    with pytest.raises(ValueError, match="kv_layout"):
        Engine.from_config(EngineConfig(
            arch="stablelm-1.6b", max_seq=32, kv_layout="lanes",
            parallel=ParallelConfig.tensor(min(2, jax.device_count()))))


@needs_mesh
def test_warm_reset_keeps_sharded_cache():
    tp = min(2, jax.device_count())
    eng, _ = _serve("stablelm-1.6b", tp, path="adaptive")
    eng.reset()
    arr = eng.scheduler.cache["pages_k"]
    assert arr.addressable_shards[0].data.shape[3] == arr.shape[3] // tp
