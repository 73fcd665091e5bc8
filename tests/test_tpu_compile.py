"""Both serving kernels compile for a TPU v5e at the benchmark's widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described (``v5e:2x2``) but not attached, and refuses what the chip
would refuse (block shapes off the tiling, unaligned lane slices, too much
VMEM, a Mosaic call the partitioner would have to split). Interpret mode
checks none of that. The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library, and a skip
decided at import would give pytest-xdist workers different tests.
"""
import os
from typing import NamedTuple

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


class Geometry(NamedTuple):
    """A serving geometry: slots, query and KV heads, head size, pages per
    slot and layers; every one has 16-token pages, an 8-lane staging ring
    and 64-token prefill chunks."""
    b: int
    hq: int
    hkv: int
    d: int
    pages: int
    layers: int

    @property
    def n_blocks(self):
        return self.b * self.pages


PAGE, RING, CHUNK = 16, 8, 64
GEOMETRIES = {
    # stablelm-1.6b: 32 heads (MHA) of 64, 8 slots over 1024 positions
    "stablelm-1.6b": Geometry(b=8, hq=32, hkv=32, d=64, pages=64, layers=24),
    # qwen2-7b-l14: 28 query and 4 KV heads (GQA 7:1) of 128, one pipeline
    # stage of 14 layers, the chat cell's 4 slots over 2048 positions
    "qwen2-7b-l14": Geometry(b=4, hq=28, hkv=4, d=128, pages=128, layers=14),
}
# stablelm-1.6b's geometry, for the tests that take one
G0 = GEOMETRIES["stablelm-1.6b"]


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


def _paged_args(g, c, ring, shard_of):
    """Operands of the read kernel as serving passes them: the whole
    stacked pool and ring (every layer), the layer index to read, and
    each slot's page bound after the table and mask."""
    bf16 = jnp.bfloat16
    pool = (g.layers, g.n_blocks, PAGE, g.hkv, g.d)
    args = [_shape((g.b, c, g.hq, g.d), bf16, shard_of("heads4")),
            _shape(pool, bf16, shard_of("heads5")),
            _shape(pool, bf16, shard_of("heads5")),
            _shape((), jnp.int32, shard_of("rep0")),
            _shape((g.b, g.pages), jnp.int32, shard_of("rep2")),
            _shape((g.b, c, g.pages * PAGE), jnp.bool_, shard_of("rep3")),
            _shape((g.b,), jnp.int32, shard_of("rep1"))]
    if ring:
        lanes = (g.layers, g.b, RING, g.hkv, g.d)
        args += [_shape(lanes, bf16, shard_of("heads5")),
                 _shape(lanes, bf16, shard_of("heads5")),
                 _shape((g.b, RING), jnp.bool_, shard_of("rep2"))]
    return args


def _assert_kernel_compiles(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _scalar_prefetch(fn, args):
    """Scalar-prefetch operands of each Pallas call ``fn`` traces to,
    searched through nested jaxprs (``shard_map``)."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["grid_mapping"].num_index_operands)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("c,ring", [
    (1, True),        # step decode over pool + staging ring
    (CHUNK, False),   # chunked prefill
    (CHUNK, True),    # mixed step: prefill chunks beside staged decodes
    (9, True),        # speculative verify, k = 8
])
def test_flash_decode_paged_compiles_for_v5e(one_chip, geometry, c, ring):
    """The page table, the layer and each slot's page bound are the
    kernel's three scalar-prefetch operands."""
    args = _paged_args(GEOMETRIES[geometry], c, ring, lambda _: one_chip)

    def fn(*a):
        return flash_decode_paged(*a, interpret=False)

    assert _scalar_prefetch(fn, args) == [3]
    _assert_kernel_compiles(fn, args)


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("c", [1, CHUNK])
def test_flash_decode_paged_sharded_compiles_for_v5e_2x2(topo, geometry, c):
    """Head-sharded pool on four chips: one kernel per shard, the page
    bound replicated beside the table."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    specs = {"heads4": P(None, None, "model", None),
             "heads5": P(None, None, None, "model", None), "rep0": P(),
             "rep1": P(None), "rep2": P(None, None),
             "rep3": P(None, None, None)}
    args = _paged_args(GEOMETRIES[geometry], c, True,
                       lambda k: NamedSharding(mesh, specs[k]))

    def fn(*a):
        return flash_decode_paged_sharded(mesh, *a, interpret=False)

    assert _scalar_prefetch(fn, args) == [3]
    _assert_kernel_compiles(fn, args)


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("layered", [False, True])
def test_staged_scatter_compiles_for_v5e(one_chip, layered, geometry):
    """The ring drain: one layer, and every layer at once as ``drain_ring``
    maps it (``vmap`` over the layer axis), at the pool's row width
    (stablelm-1.6b 2048, qwen2-7b-l14 512)."""
    g = GEOMETRIES[geometry]
    lead = (g.layers,) if layered else ()
    width = g.hkv * g.d
    args = [_shape(lead + (g.n_blocks * PAGE, width), jnp.bfloat16, one_chip),
            _shape(lead + (g.b * RING, width), jnp.bfloat16, one_chip),
            _shape((g.b * RING,), jnp.int32, one_chip),
            _shape((g.b * RING,), jnp.bool_, one_chip)]

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
        G0.layers, G0.n_blocks, PAGE, G0.b, G0.pages, G0.hkv, G0.d,
        dtype=jnp.bfloat16, ring_size=RING))
    shardings = serve_cache_shardings(get_config("stablelm-1.6b"), mesh,
                                      cache)
    assert shardings["pages_k"].spec[3] == "model"
    args = {k: _shape(v.shape, v.dtype, shardings[k])
            for k, v in cache.items()}
    _assert_kernel_compiles(
        lambda c: PG.drain_ring(c, use_kernel=True, shardings=shardings),
        [args])
