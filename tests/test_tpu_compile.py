"""Both serving kernels compile for a TPU v5e at stablelm-1.6b's widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described (``v5e:2x2``) but not attached, and refuses what the chip
would refuse (block shapes off the tiling, unaligned lane slices, too much
VMEM, a Mosaic call the partitioner would have to split). Interpret mode
checks none of that. The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library, and a skip
decided at import would give pytest-xdist workers different tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_decode import (
    flash_decode_paged,
    flash_decode_paged_sharded,
)
from repro.kernels.staged_scatter import staged_scatter

# stablelm-1.6b serving geometry: 32 heads (MHA) of 64, 8 slots, 16-token
# pages over 1024 positions, an 8-lane staging ring, 64-token prefill chunks
B, HQ, HKV, D = 8, 32, 32, 64
PAGE, PAGES, RING, CHUNK = 16, 64, 8, 64
N_BLOCKS = B * PAGES
LAYERS = 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(c, ring, shard_of):
    """Operands of the read kernel as serving passes them: the whole
    stacked pool and ring (every layer) and the layer index to read."""
    bf16 = jnp.bfloat16
    args = [_shape((B, c, HQ, D), bf16, shard_of("heads4")),
            _shape((LAYERS, N_BLOCKS, PAGE, HKV, D), bf16, shard_of("heads5")),
            _shape((LAYERS, N_BLOCKS, PAGE, HKV, D), bf16, shard_of("heads5")),
            _shape((), jnp.int32, shard_of("rep0")),
            _shape((B, PAGES), jnp.int32, shard_of("rep2")),
            _shape((B, c, PAGES * PAGE), jnp.bool_, shard_of("rep3"))]
    if ring:
        args += [_shape((LAYERS, B, RING, HKV, D), bf16, shard_of("heads5")),
                 _shape((LAYERS, B, RING, HKV, D), bf16, shard_of("heads5")),
                 _shape((B, RING), jnp.bool_, shard_of("rep2"))]
    return args


def _assert_kernel_compiles(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("c,ring", [
    (1, True),        # step decode over pool + staging ring
    (CHUNK, False),   # chunked prefill
    (9, True),        # speculative verify, k = 8
])
def test_flash_decode_paged_compiles_for_v5e(one_chip, c, ring):
    args = _paged_args(c, ring, lambda _: one_chip)
    _assert_kernel_compiles(
        lambda *a: flash_decode_paged(*a, interpret=False), args)


def test_flash_decode_paged_sharded_compiles_for_v5e_2x2(topo):
    """Head-sharded pool on four chips: one kernel per shard."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    specs = {"heads4": P(None, None, "model", None),
             "heads5": P(None, None, None, "model", None), "rep0": P(),
             "rep2": P(None, None), "rep3": P(None, None, None)}
    args = _paged_args(1, True, lambda k: NamedSharding(mesh, specs[k]))
    _assert_kernel_compiles(
        lambda *a: flash_decode_paged_sharded(mesh, *a, interpret=False),
        args)


@pytest.mark.parametrize("layered", [False, True])
def test_staged_scatter_compiles_for_v5e(one_chip, layered):
    """The ring drain: one layer, and every layer at once as ``drain_ring``
    maps it (``vmap`` over the layer axis)."""
    lead = (LAYERS,) if layered else ()
    args = [_shape(lead + (N_BLOCKS * PAGE, HKV * D), jnp.bfloat16, one_chip),
            _shape(lead + (B * RING, HKV * D), jnp.bfloat16, one_chip),
            _shape((B * RING,), jnp.int32, one_chip),
            _shape((B * RING,), jnp.bool_, one_chip)]

    def drain(dest, staging, rows, ok):
        return staged_scatter(dest, staging, rows, ok, interpret=False)

    if layered:
        def fn(dest, staging, rows, ok):
            return jax.vmap(lambda d, s: drain(d, s, rows, ok))(dest, staging)
    else:
        fn = drain
    _assert_kernel_compiles(fn, args)


def test_drain_ring_sharded_compiles_for_v5e_2x2(topo, monkeypatch):
    """The ring drain over a head-sharded pool on four chips, as serving
    places it (``serve_cache_shardings``): one ``staged_scatter`` per
    shard, every layer at once."""
    from repro.configs import get_config
    from repro.distributed.sharding import serve_cache_shardings
    from repro.kernels import ops
    from repro.kvcache import paged as PG

    # the kernel wrapper picks interpret mode on a CPU backend; the
    # compile here is for the described chip
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    cache = jax.eval_shape(lambda: PG.make_paged_kv(
        LAYERS, N_BLOCKS, PAGE, B, PAGES, HKV, D, dtype=jnp.bfloat16,
        ring_size=RING))
    shardings = serve_cache_shardings(get_config("stablelm-1.6b"), mesh,
                                      cache)
    assert shardings["pages_k"].spec[3] == "model"
    args = {k: _shape(v.shape, v.dtype, shardings[k])
            for k, v in cache.items()}
    _assert_kernel_compiles(
        lambda c: PG.drain_ring(c, use_kernel=True, shardings=shardings),
        [args])
