"""The paged pool stays in place through the layer scan.

``decode_step_paged`` and ``decode_chunk_paged`` carry the stacked pool
(and the staging ring) in the layer scan's carry and write each layer's
rows at ``[layer, row]``. Were the pool a scan ``xs``/``ys`` instead, every
layer of every step would slice its plane out of the stack and restack it:
a whole-pool copy per step that the compiled program pays on the chip.
These tests read the traced program, so they guard the layout on the CPU.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kvcache import paged as PG
from repro.models import build_model

N_SLOTS, N_BLOCKS, PAGE, MAX_PAGES, RING, CHUNK = 3, 10, 4, 4, 4, 5


def _layer_scan(jaxpr, n_layers):
    """The one ``scan`` eqn of ``n_layers`` iterations, searched through
    every nested jaxpr."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan" and eqn.params["length"] == n_layers:
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    assert len(found) == 1, f"expected one layer scan, found {len(found)}"
    return found[0]


def _trace(variant, ring, attention):
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), 32))
    cache = jax.eval_shape(lambda: PG.make_paged_kv(
        cfg.n_layers, N_BLOCKS, PAGE, N_SLOTS, MAX_PAGES,
        cfg.n_kv_heads or cfg.n_heads, cfg.resolved_head_dim,
        ring_size=RING if ring else 0))
    live = jnp.ones((N_SLOTS,), jnp.bool_)
    idx = jnp.zeros((N_SLOTS,), jnp.int32)
    if variant == "step":
        def fn(params, cache):
            return model.decode_step_paged(params, cache, idx, idx, live,
                                           attention=attention)
    else:
        toks = jnp.zeros((N_SLOTS, CHUNK), jnp.int32)

        def fn(params, cache):
            return model.decode_chunk_paged(params, cache, toks, idx,
                                            idx + 1, live,
                                            attention=attention)
    closed = jax.make_jaxpr(fn)(params, cache)
    return cfg.n_layers, cache, closed.jaxpr


@pytest.mark.parametrize("attention", ["reference", "fused"])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("variant", ["step", "chunk"])
def test_pool_rides_the_layer_scan_carry(variant, ring, attention):
    n_layers, cache, jaxpr = _trace(variant, ring, attention)
    scan = _layer_scan(jaxpr, n_layers)
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carry = [tuple(v.aval.shape)
             for v in scan.invars[n_consts:n_consts + n_carry]]
    xs = [tuple(v.aval.shape) for v in scan.invars[n_consts + n_carry:]]
    ys = [tuple(v.aval.shape) for v in scan.outvars[n_carry:]]

    planes = {"pages_k", "pages_v"} | ({"ring_k", "ring_v"} if ring else set())
    whole = {tuple(cache[k].shape) for k in planes}
    one_layer = {s[1:] for s in whole}
    for shape in xs + ys:
        assert shape not in whole | one_layer, (
            f"a pool or ring plane rides the layer scan's xs/ys: {shape}")
    for shape in whole:   # K and V planes, every layer, in the carry
        assert carry.count(shape) == 2, (shape, carry)
    assert (n_layers,) in xs   # the layer index each body reads
