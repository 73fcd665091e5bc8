"""KV-cache write-path tests: staged ring overlay == direct writes, paged
pool bookkeeping, drain via the Pallas kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kvcache import (
    BlockPool,
    add_ring,
    drain_ring,
    gather_view,
    logical_to_physical,
    make_paged_kv,
    maybe_drain,
    pool_rows,
    scatter_token,
    strip_ring,
    view_mask,
    view_rows,
)
from repro.models import build_model


@pytest.mark.parametrize("arch", ["qwen2-7b", "h2o-danube-3-4b"])
def test_staged_ring_decode_equals_direct(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), 64)
    B, S, STEPS = 2, 24, 8
    tokens = jax.random.randint(jax.random.key(1), (B, S + STEPS), 0, cfg.vocab)

    _, cache_d = m.prefill(params, tokens[:, :S], 64)
    cd = cache_d
    for t in range(STEPS):
        lg_d, cd = m.decode_step(params, cd, tokens[:, S + t],
                                 jnp.full((B,), S + t, jnp.int32))

    _, cache_s = m.prefill(params, tokens[:, :S], 64)
    cs = add_ring(cache_s, 4)
    for t in range(STEPS):
        lg_s, cs = m.decode_step(params, cs, tokens[:, S + t],
                                 jnp.full((B,), S + t, jnp.int32))
        cs, _ = maybe_drain(cs)

    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_d),
                               atol=1e-4, rtol=1e-4)
    cs = drain_ring(cs, use_kernel=False)
    np.testing.assert_allclose(np.asarray(cs["k"]), np.asarray(cd["k"]),
                               atol=1e-5, rtol=1e-5)


def test_adaptive_mixed_paths_match_direct():
    cfg = get_config("stablelm-1.6b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), 64)
    B, S, STEPS = 4, 16, 6
    tokens = jax.random.randint(jax.random.key(1), (B, S + STEPS), 0, cfg.vocab)
    full = m.forward(params, tokens)
    _, cache = m.prefill(params, tokens[:, :S], 64)
    cs = add_ring(cache, 4)
    mask = jnp.asarray([False, True, False, True])  # per-sequence routing
    for t in range(STEPS):
        lg, cs = m.decode_step(params, cs, tokens[:, S + t],
                               jnp.full((B,), S + t, jnp.int32),
                               unload_mask=mask)
        cs, _ = maybe_drain(cs)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, S + STEPS - 1]),
                               atol=1e-4, rtol=1e-4)


def test_drain_with_kernel_matches_reference_drain():
    cfg = get_config("stablelm-1.6b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), 64)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S + 4), 0, cfg.vocab)
    _, cache = m.prefill(params, tokens[:, :S], 64)
    cs = add_ring(cache, 4)
    for t in range(4):
        _, cs = m.decode_step(params, cs, tokens[:, S + t],
                              jnp.full((B,), S + t, jnp.int32))
    a = drain_ring(cs, use_kernel=True)
    b = drain_ring(cs, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a["k"], np.float32),
                               np.asarray(b["k"], np.float32), atol=1e-6)


def test_strip_ring_removes_overlay():
    cfg = get_config("stablelm-1.6b").reduced()
    m = build_model(cfg)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: m.init_cache(2, 32, jnp.float32)),
    )
    ringed = add_ring(cache, 4)
    assert "ring_k" in ringed
    assert set(strip_ring(ringed)) == set(cache)


# ---------------------------------------------------------------------------
# paged pool
# ---------------------------------------------------------------------------


def test_paged_pool_insert_gather_roundtrip():
    """Token tiles written through the physical mapping come back, in
    logical order, through the gathered per-slot view."""
    pool = BlockPool(16)
    cache = make_paged_kv(n_layers=1, n_blocks=16, page_size=4, n_slots=3,
                          max_pages=4, h=2, dh=8)
    table = np.full((3, 4), -1, np.int32)
    for s in range(3):
        table[s, :3] = pool.alloc(s, 3)  # 10 rows -> 3 pages of 4
    cache["page_table"] = jnp.asarray(table)
    rng = np.random.RandomState(0)
    ref = np.zeros((3, 16, 2, 8), np.float32)
    for t in range(10):
        k = jnp.asarray(rng.randn(3, 2, 8), jnp.float32)
        dest = logical_to_physical(cache, jnp.full((3,), t, jnp.int32))
        cache["pages_k"] = scatter_token(cache["pages_k"], 0, dest, k)
        ref[:, t] = np.asarray(k)
    vm = view_mask(cache, jnp.full((3,), 9, jnp.int32))
    assert vm.tolist()[0] == [True] * 10 + [False] * 2 + [False] * 4
    kk = gather_view(cache["pages_k"][0], view_rows(cache))
    for b in range(3):
        np.testing.assert_allclose(np.asarray(kk[b, :10]), ref[b, :10],
                                   atol=1e-6)


def test_paged_destination_mapping_and_write_masking():
    pool = BlockPool(8)
    cache = make_paged_kv(n_layers=1, n_blocks=8, page_size=4, n_slots=2,
                          max_pages=4, h=1, dh=4)
    table = np.full((2, 4), -1, np.int32)
    table[0, 0] = pool.alloc(0, 1)[0]
    table[1, 0] = pool.alloc(1, 1)[0]
    cache["page_table"] = jnp.asarray(table)
    dest = logical_to_physical(cache, jnp.asarray([0, 0], jnp.int32))
    assert dest[0] != dest[1]                      # own block each
    assert (dest // 4).tolist() == [table[0, 0], table[1, 0]]
    # sentinel rows (retired slot / unallocated page) resolve out of range
    dead = logical_to_physical(cache, jnp.asarray([-1, 4], jnp.int32))
    assert dead.tolist() == [pool_rows(cache)] * 2
    before = np.asarray(cache["pages_k"][0])
    cache["pages_k"] = scatter_token(cache["pages_k"], 0, dead,
                                     jnp.ones((2, 1, 4), jnp.float32))
    np.testing.assert_array_equal(np.asarray(cache["pages_k"][0]), before)
