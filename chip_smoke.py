"""Chip smoke test: serve stablelm-1.6b at its published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the tensor-parallel path on four chips

One chip: builds the engine through the front door
(``Engine.from_config(EngineConfig(arch="stablelm-1.6b", reduced=False))``,
random weights from a seed), serves 16 greedy requests on 8 slots (prompts
of 128 and 512 tokens, 64 new tokens each) with chunked prefill, the
adaptive write path and a staging ring smaller than a scan segment, and
checks that every request got its 64 in-vocab tokens, that KV writes were
staged and the ring drained inside the scan, that the fused attention
kernel and the drain kernel were selected without being asked for, and
that both Pallas kernels, compiled for this chip, match their jnp oracles
at these widths. It then serves the same requests through the reference
attention path and prints the greedy-token agreement, and compares one
64-token prefill step's logits from both paths with the same step in
float32: the fused path must be no further from float32 than the
reference path is.

``--chips 4`` runs only the mesh phase: the same traffic with
``ParallelConfig.tensor(4)`` (8 of the 32 heads per chip) against tensor
parallelism 1 on one chip of the same host, in this one process, and
prints the token agreement. It then checks what only the mesh runs: the
read kernel per head shard against its oracle, the ring drain per head
shard (``shard_map``) against the jnp scatter, and the tp=4 fused
prefill-step logits against float32, held to the same bound as on one
chip.

Progress goes to stdout; the last line is one JSON object naming the
device. Without a TPU the script fails before it serves anything.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "stablelm-1.6b"
N_REQUESTS, N_SLOTS, NEW_TOKENS = 16, 8, 64
PROMPT_LENS = (128, 512)
MAX_SEQ, PAGE, CHUNK, SEGMENT, RING = 1024, 16, 64, 16, 8
SEED = 0
# bf16 keeps 8 significant bits: one rounding step is 2^-8 of |x|. The
# kernel rounds the same quantities as the oracle (logits, probabilities,
# output) but sums in another order, so an output element may sit a few
# steps away where the probabilities round differently; a wrong page, head
# or mask misses by O(1).
KERNEL_ATOL, KERNEL_RTOL = 2 ** -5, 2 ** -5
# fused and reference logits each carry bf16 rounding noise of the same
# size; a bug (wrong page, head, mask or ring lane) multiplies the fused
# error, it does not nudge it. The floor (one bf16 step) covers a
# reference that happens to land unusually close.
LOGIT_ERR_RATIO, LOGIT_ERR_FLOOR = 2.0, 2 ** -8


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"ok: {what}", flush=True)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX found {devices[0].platform} devices; this script "
            f"never runs on the CPU")
    if len(devices) < n_chips:
        raise SmokeFailure(f"{n_chips} chips asked for, {len(devices)} found")
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    return devices


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its own events)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def requests(vocab: int):
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, vocab, size=PROMPT_LENS[i % 2]).tolist()
               for i in range(N_REQUESTS)]
    from repro.serve import SamplingParams

    return prompts, SamplingParams(temperature=0.0, max_tokens=NEW_TOKENS)


def engine_config(**kw):
    from repro.serve import EngineConfig

    return EngineConfig(
        arch=ARCH, reduced=False, init_seed=SEED, max_seq=MAX_SEQ,
        n_slots=N_SLOTS, page_size=PAGE, chunked=True, chunk_size=CHUNK,
        segment_len=SEGMENT, ring_size=RING, path="adaptive", **kw)


def serve(eng, prompts, params, label: str, clock: CompileClock):
    c0, t0 = clock.seconds, time.perf_counter()
    comps = eng.generate(prompts, params)
    wall = time.perf_counter() - t0
    tokens = [c.tokens for c in comps]
    vocab = eng.model.cfg.vocab
    print(f"{label}: served {len(comps)} requests, "
          f"{sum(len(t) for t in tokens)} tokens; compile "
          f"{clock.seconds - c0:.1f} s of {wall:.1f} s wall (includes "
          f"compilation: not a speed); stats {eng.stats}", flush=True)
    check(len(comps) == N_REQUESTS
          and all(len(t) == NEW_TOKENS for t in tokens),
          f"{label}: all {N_REQUESTS} requests got {NEW_TOKENS} tokens")
    check(all(((t >= 0) & (t < vocab)).all() for t in tokens),
          f"{label}: every token inside the vocab of {vocab}")
    return tokens


def agreement(a, b) -> dict:
    """Greedy streams diverge for good at their first differing token, so
    report both whole-stream equality and the mean matching prefix."""
    same = sum(bool(np.array_equal(x, y)) for x, y in zip(a, b))
    prefix = []
    for x, y in zip(a, b):
        diff = np.nonzero(x != y)[0]
        prefix.append((diff[0] if len(diff) else len(x)) / len(x))
    return {"identical_requests": f"{same}/{len(a)}",
            "mean_prefix_agreement": float(np.mean(prefix)),
            "first_token_agreement": float(np.mean(
                [x[0] == y[0] for x, y in zip(a, b)]))}


def kernel_checks(cfg, mesh=None) -> None:
    """Both Pallas kernels, compiled for this chip, at the served widths,
    against their jnp oracles. Under a ``mesh`` the read kernel runs as
    serving runs it there, one instance per head shard; the drain is
    checked by :func:`drain_check`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import (
        flash_decode_paged,
        flash_decode_paged_sharded,
        ref,
        staged_scatter,
    )

    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    n_blocks, pages = N_SLOTS * MAX_SEQ // PAGE, MAX_SEQ // PAGE
    keys = jax.random.split(jax.random.key(SEED + 1), 8)
    bf16 = jnp.bfloat16
    if mesh is None:
        read, where = flash_decode_paged, ""

        def put(x, heads):
            return x
    else:
        read = functools.partial(flash_decode_paged_sharded, mesh)
        where = f" per head shard on {mesh.shape['model']} chips"

        def put(x, heads):   # heads ride the second-to-last axis
            spec = P(*([None] * (x.ndim - 2)), "model", None) if heads else P()
            return jax.device_put(x, NamedSharding(mesh, spec))

    # the kernel reads one layer of the whole stacked pool and ring, as
    # serving passes them; check the last layer of two
    n_layers, layer = 2, 1
    pool_k = jax.random.normal(keys[0], (n_layers, n_blocks, PAGE, hkv, d),
                               bf16)
    pool_v = jax.random.normal(keys[1], (n_layers, n_blocks, PAGE, hkv, d),
                               bf16)
    blocks = jax.random.randint(keys[2], (N_SLOTS, pages), 0, n_blocks)
    for c, ring in ((1, True), (CHUNK, False)):
        q = jax.random.normal(keys[3], (N_SLOTS, c, hq, d), bf16)
        view_ok = jax.random.bernoulli(keys[4], 0.7,
                                       (N_SLOTS, c, pages * PAGE))
        extra = (None, None, None)
        if ring:
            ring_shape = (n_layers, N_SLOTS, RING, hkv, d)
            extra = (jax.random.normal(keys[5], ring_shape, bf16),
                     jax.random.normal(keys[6], ring_shape, bf16),
                     jax.random.bernoulli(keys[7], 0.5, (N_SLOTS, RING)))
        heads = (True, True, True, False, False, False, True, True, False)
        lay = jnp.asarray(layer, jnp.int32)
        args = [x if x is None else put(x, h) for x, h in
                zip((q, pool_k, pool_v, lay, blocks, view_ok) + extra, heads)]
        got = read(*args)
        want = jax.jit(ref.flash_decode_paged_ref)(
            q, pool_k, pool_v, lay, blocks, view_ok, *extra)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL)
        check(bool(ok) and np.isfinite(got).all(),
              f"flash_decode_paged{where} C={c} ring={ring} matches its "
              f"oracle (max abs diff {err:.3g}, atol=rtol={KERNEL_ATOL})")
    if mesh is not None:
        return

    rows_total, width = n_blocks * PAGE, hkv * d
    dest = jax.random.normal(keys[0], (rows_total, width), bf16)
    staging = jax.random.normal(keys[1], (N_SLOTS * RING, width), bf16)
    dst_row = jax.random.permutation(keys[2], rows_total)[:N_SLOTS * RING]
    valid = jax.random.bernoulli(keys[3], 0.6, (N_SLOTS * RING,))
    got = staged_scatter(dest, staging, dst_row.astype(jnp.int32), valid)
    want = jax.jit(ref.staged_scatter_ref)(dest, staging,
                                           dst_row.astype(jnp.int32), valid)
    check(bool(np.array_equal(np.asarray(got), np.asarray(want))),
          "staged_scatter equals its oracle exactly (it is a copy)")


def drain_check(cfg, mesh) -> None:
    """The staging-ring drain as serving runs it under ``mesh`` (one
    ``staged_scatter`` per head shard, ``shard_map`` over the cache
    placements), at the served pool geometry over two layers, against the
    jnp scatter on one chip: a copy, so exact."""
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import serve_cache_shardings
    from repro.kvcache import paged as PG

    pages, bf16 = MAX_SEQ // PAGE, jnp.bfloat16
    keys = jax.random.split(jax.random.key(SEED + 3), 8)
    cache = PG.make_paged_kv(2, N_SLOTS * pages, PAGE, N_SLOTS, pages,
                             cfg.n_kv_heads, cfg.resolved_head_dim,
                             dtype=bf16, ring_size=RING)
    for i, key in enumerate(("pages_k", "pages_v", "ring_k", "ring_v")):
        cache[key] = jax.random.normal(keys[i], cache[key].shape, bf16)
    cache["page_table"] = jax.random.permutation(
        keys[4], N_SLOTS * pages).reshape(N_SLOTS, pages).astype(jnp.int32)
    # distinct logical rows per slot, some lanes empty
    rows = jax.vmap(lambda k: jax.random.permutation(k, MAX_SEQ)[:RING])(
        jax.random.split(keys[5], N_SLOTS))
    live = jax.random.bernoulli(keys[6], 0.7, rows.shape)
    cache["ring_pos"] = jnp.where(live, rows, -1).astype(jnp.int32)
    cache["ring_fill"] = jnp.asarray(RING, jnp.int32)
    want = jax.jit(lambda c: PG.drain_ring(c, use_kernel=False))(cache)
    shardings = serve_cache_shardings(cfg, mesh, cache)
    got = jax.jit(lambda c: PG.drain_ring(c, use_kernel=True,
                                          shardings=shardings))(
        {k: jax.device_put(v, shardings[k]) for k, v in cache.items()})
    same = all(np.array_equal(np.asarray(got[k]), np.asarray(want[k]))
               for k in want)
    check(same and int(live.sum()) > 0,
          f"drain_ring per head shard on {mesh.shape['model']} chips "
          f"equals the jnp scatter exactly ({int(live.sum())} staged rows)")


def logit_check(model, weights, mesh=None) -> None:
    """One chunked-prefill step (64 tokens into each of 8 slots, logits at
    every position) through fused and reference attention in bf16, and
    through the reference in float32 at full matmul precision. Under a
    ``mesh`` the fused step runs there, placed as serving places it; the
    reference and float32 steps run on one chip."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.distributed import sharding as DS
    from repro.kvcache import paged as PG
    from repro.models import build_model

    cfg = model.cfg
    pages = CHUNK // PAGE
    tokens = jax.random.randint(jax.random.key(SEED + 2), (N_SLOTS, CHUNK),
                                0, cfg.vocab)
    start = jnp.zeros((N_SLOTS,), jnp.int32)
    n_valid = jnp.full((N_SLOTS,), CHUNK, jnp.int32)
    write = jnp.ones((N_SLOTS,), jnp.bool_)

    def step(m, params, attention, mesh=None):
        cache = PG.make_paged_kv(
            cfg.n_layers, N_SLOTS * pages, PAGE, N_SLOTS, pages,
            cfg.n_kv_heads, cfg.resolved_head_dim, dtype=m.cfg.dtype)
        cache["page_table"] = jnp.arange(
            N_SLOTS * pages, dtype=jnp.int32).reshape(N_SLOTS, pages)
        if mesh is not None:
            params = jax.device_put(
                params, DS.param_shardings(m.cfg, mesh, params))
            cache = jax.device_put(
                cache, DS.serve_cache_shardings(m.cfg, mesh, cache))
        fn = jax.jit(lambda p, c: m.decode_chunk_paged(
            p, c, tokens, start, n_valid, write, attention=attention,
            all_logits=True, mesh=mesh)[0])
        return np.asarray(fn(params, cache), np.float32)

    fused = step(model, weights, "fused", mesh)
    label = "fused" if mesh is None else f"fused tp={mesh.shape['model']}"
    reference = step(model, weights, "reference")
    m32 = build_model(dataclasses.replace(cfg, dtype="float32"))
    w32 = jax.tree.map(lambda x: x.astype(jnp.float32)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x,
                       weights)
    with jax.default_matmul_precision("highest"):
        exact = step(m32, w32, "reference")
    del w32

    def rel(x):
        return float(np.sqrt(np.mean((x - exact) ** 2))
                     / np.sqrt(np.mean(exact ** 2)))

    def top1(x, y):
        return float(np.mean(x.argmax(-1) == y.argmax(-1)))

    err_f, err_r = rel(fused), rel(reference)
    print(f"prefill-step logits vs float32 ({N_SLOTS}x{CHUNK} positions): "
          f"rel rms error {label} {err_f:.3g}, reference {err_r:.3g}; top-1 "
          f"agreement {label}/f32 {top1(fused, exact):.3f}, reference/f32 "
          f"{top1(reference, exact):.3f}, {label}/reference "
          f"{top1(fused, reference):.3f}", flush=True)
    check(np.isfinite(fused).all()
          and err_f <= max(LOGIT_ERR_RATIO * err_r, LOGIT_ERR_FLOOR),
          f"{label} logits no further from float32 than {LOGIT_ERR_RATIO}x "
          f"the reference's (or {LOGIT_ERR_FLOOR})")


def peak_bytes(devices) -> list:
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devices]
    print(f"peak_bytes_in_use: {peaks}", flush=True)
    return peaks


def one_chip(clock: CompileClock) -> None:
    import jax

    from repro.serve import AttentionConfig, Engine

    for var in ("REPRO_ATTENTION", "REPRO_DRAIN_KERNEL"):
        check(var not in os.environ, f"{var} is unset")
    eng = Engine.from_config(engine_config())
    cfg = eng.model.cfg
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab}", flush=True)
    check(cfg.name == ARCH and cfg.d_model == 2048 and cfg.n_layers == 24,
          "published widths (not the reduced smoke config)")
    sched = eng.scheduler
    print(f"resolved: attention={sched.attention} "
          f"drain_kernel={sched._drain_kernel} layout={eng.layout}",
          flush=True)
    check(sched.attention == "fused", "attention resolved to the fused kernel")
    check(sched._drain_kernel is True, "drain kernel selected")

    kernel_checks(cfg)

    prompts, params = requests(cfg.vocab)
    fused = serve(eng, prompts, params, "fused", clock)
    check(eng.stats["staged_writes"] > 0, "KV writes were staged")
    check(eng.stats["drains"] > 0, "the staging ring drained inside the scan")
    model, weights = eng.model, eng.params
    del eng, sched
    reference = serve(
        Engine.from_config(engine_config(attention=AttentionConfig(
            impl="reference", drain_kernel=False)), model, weights),
        prompts, params, "reference", clock)
    print(f"fused vs reference greedy tokens: "
          f"{agreement(fused, reference)}", flush=True)
    logit_check(model, weights)
    print(f"compile seconds (all phases): {clock.seconds:.1f}", flush=True)
    peak_bytes(jax.devices()[:1])


def four_chips(clock: CompileClock) -> None:
    import jax

    from repro.serve import Engine, ParallelConfig, build_model_and_params

    _, model, weights = build_model_and_params(ARCH, MAX_SEQ, seed=SEED,
                                               reduced=False)
    prompts, params = requests(model.cfg.vocab)
    single = serve(Engine.from_config(engine_config(), model, weights),
                   prompts, params, "tp=1 (one chip)", clock)
    eng = Engine.from_config(
        engine_config(parallel=ParallelConfig.tensor(4)), model, weights)
    sched = eng.scheduler
    mesh = sched.mesh
    print(f"resolved: mesh={dict(mesh.shape)} "
          f"attention={sched.attention} drain_kernel={sched._drain_kernel}",
          flush=True)
    check(sched.attention == "fused" and sched._drain_kernel is True,
          "fused attention and drain kernel under the mesh")
    sharded = serve(eng, prompts, params, "tp=4 (four chips)", clock)
    check(eng.stats["drains"] > 0, "the staging ring drained inside the scan")
    print(f"tp=4 vs tp=1 greedy tokens: {agreement(sharded, single)}",
          flush=True)
    del eng, sched
    kernel_checks(model.cfg, mesh)
    drain_check(model.cfg, mesh)
    logit_check(model, weights, mesh)
    print(f"compile seconds (all phases): {clock.seconds:.1f}", flush=True)
    peak_bytes(jax.devices()[:4])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tensor-parallel phase")
    args = ap.parse_args()
    try:
        devices = require_tpu(args.chips)
        from repro.launch.device import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        clock = CompileClock()
        (four_chips if args.chips == 4 else one_chip)(clock)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
