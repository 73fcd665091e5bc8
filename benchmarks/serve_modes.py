"""Serving write-mode + scheduler comparison through the real engines.

Two benchmark families:

* write modes (the framework-level analogue of Fig. 3): direct vs staged
  vs adaptive KV writes through ``ServeEngine``, each measured as the
  device-resident scan (``*_ms_per_step``) and the seed's per-step Python
  loop (``*_ref_ms_per_step``), speedup = ``*_scan_speedup``.
* continuous batching (``--batched`` / always part of ``run()``): the
  slot-scheduler (``BatchedServeEngine``, batch 8 over the paged pool)
  vs SEQUENTIAL per-request decode (the same scheduler pinned to one
  slot), same request stream. Reports tok/s for both, the speedup, and
  whether the outputs are bit-identical (they must be: batching is a
  throughput optimization, not a sampling change).
* chunked prefill (``--chunked``): mixed-phase scheduling (prompts
  prefilled in chunks INSIDE the decode scan) vs the admission-blocking
  engine at equal slot count, on a mixed long/short-prompt workload.
  Reports time-to-first-token (mean/p95) and tok/s for both, plus
  bit-identity against sequential decode.
* prefix caching + host tier (``--prefix``): cached-prefix vs cold TTFT
  on a shared-system-prompt workload (warm pool kept across passes via
  ``reset(keep_cache=True)``), and over-capacity admission from a pool
  half the working set with ``host_tier=True`` (device->host unloads,
  page-back by seniority) vs an ample pool. Both must be bit-identical.

CLI:  PYTHONPATH=src python benchmarks/serve_modes.py --batched --chunked \
          --prefix [--json out.json] [--slots 8] [--requests 16]
prints one JSON document (stable keys — CI gates it against the committed
``BENCH_serve.json`` baseline via ``benchmarks/check_regression.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import jax
import numpy as np

from repro.data import synthetic_requests
from repro.launch.device import enable_compile_cache
from repro.serve import (
    Engine,
    EngineConfig,
    MemoryConfig,
    ServeConfig,
    ServeEngine,
    SpecConfig,
    build_model_and_params,
)


def _time_generate(eng, prompt, n, reference):
    toks = eng.generate(prompt, n, reference=reference)
    jax.block_until_ready(toks)
    t0 = time.perf_counter()
    toks = eng.generate(prompt, n, reference=reference)
    jax.block_until_ready(toks)
    return (time.perf_counter() - t0) / n * 1e3


def _one_pass(eng, mk_queue, keep_cache=False):
    """One timed serve pass on a warm engine: (outputs, tok/s, ttft).
    ``ttft`` maps req_id -> seconds from serve start to the request's
    first emitted token. ``keep_cache=True`` resets serving state but
    keeps the block pool + device cache — registered prefix blocks stay
    hittable (the prefix-cache benchmark's warm arm)."""
    eng.reset(keep_cache=keep_cache)
    queue = mk_queue()
    t0 = time.perf_counter()
    outputs = eng.serve(queue)
    dt = time.perf_counter() - t0
    return outputs, sum(len(t) for t in outputs.values()) / dt, dict(eng.ttft)


def _ttft_floor(acc, ttft):
    """Fold one pass's TTFT dict into a per-request running minimum.
    Different requests hit their noise floor in different passes, so the
    per-request min is a strictly lower-variance estimator than keeping
    the single whole pass with the lowest mean — small absolute TTFTs
    (tens of ms) need that for the gated speedup ratios to be stable."""
    if acc is None:
        return dict(ttft)
    for r, t in ttft.items():
        acc[r] = min(acc[r], t)
    return acc


def _serve_timed(eng, mk_queue, repeats=5):
    """(outputs, tok/s, ttft) on a warm engine: one compile pass, then
    best-of-``repeats`` timed passes — background load only ever slows a
    pass down, so best-of is the low-variance estimator the 15% CI gate
    needs. The TTFT dict is the per-request minimum across passes,
    independently of the throughput pick."""
    eng.serve(mk_queue())
    best_tps, best_ttft, outputs = 0.0, None, None
    for _ in range(repeats):
        outputs, tps, ttft = _one_pass(eng, mk_queue)
        best_tps = max(best_tps, tps)
        best_ttft = _ttft_floor(best_ttft, ttft)
    return outputs, best_tps, best_ttft


def bench_batched(
    arch: str = "stablelm-1.6b",
    n_slots: int = 8,
    n_requests: int = 16,
    prompt_len: int = 16,
    max_new: int = 49,
    write_mode: str = "direct",
    segment_len: int = 16,
) -> dict:
    """Continuous batching vs sequential per-request decode (same model,
    same requests, same paged substrate — only the slot count differs)."""
    max_seq = prompt_len + max_new + 8
    cfg, model, params = build_model_and_params(arch, max_seq)
    mk_queue = lambda: synthetic_requests(  # noqa: E731
        n_requests, prompt_len, cfg.vocab, max_new, seed=11)

    def mk_engine(slots):
        return Engine.from_config(EngineConfig(
            max_seq=max_seq, n_slots=slots, segment_len=segment_len,
            path=write_mode, page_size=8,
        ), model, params)

    out_b, tps_b, _ = _serve_timed(mk_engine(n_slots), mk_queue)
    out_s, tps_s, _ = _serve_timed(mk_engine(1), mk_queue)
    identical = (
        set(out_b) == set(out_s)
        and all(np.array_equal(out_b[r], out_s[r]) for r in out_b)
    )
    return {
        "arch": arch,
        "write_mode": write_mode,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "tokens_per_request": max_new,
        "batched_tok_s": round(tps_b, 2),
        "sequential_tok_s": round(tps_s, 2),
        "batched_speedup": round(tps_b / tps_s, 3),
        "bit_identical": bool(identical),
    }


def _ttft_ms(ttft: dict) -> dict:
    vals = np.asarray(sorted(ttft.values())) * 1e3
    return {
        "mean": round(float(vals.mean()), 2),
        "p95": round(float(np.percentile(vals, 95)), 2),
    }


def _serve_timed_paired(eng_a, eng_b, mk_queue, repeats=5):
    """Best-of-``repeats`` for TWO engines with their passes INTERLEAVED
    (A, B, A, B, ...), so background-load swings hit both sides of the
    comparison — the gated ratio metrics stay stable even when absolute
    numbers drift."""
    eng_a.serve(mk_queue())
    eng_b.serve(mk_queue())
    results = []
    for eng in (eng_a, eng_b):
        results.append({"tps": 0.0, "ttft": None, "out": None, "eng": eng})
    for _ in range(repeats):
        for res in results:
            out, tps, ttft = _one_pass(res["eng"], mk_queue)
            res["out"] = out
            res["tps"] = max(res["tps"], tps)
            res["ttft"] = _ttft_floor(res["ttft"], ttft)
    a, b = results
    return (a["out"], a["tps"], a["ttft"]), (b["out"], b["tps"], b["ttft"])


def bench_chunked(
    arch: str = "stablelm-1.6b",
    n_slots: int = 4,
    n_requests: int = 24,
    long_prompt: int = 64,
    short_prompt: int = 8,
    max_new: int = 17,
    chunk_size: int = 32,
    segment_len: int = 4,
) -> dict:
    """Mixed-phase chunked prefill vs the admission-blocking engine, equal
    slot count, on a mixed long/short-prompt workload (every 4th request
    carries the long prompt — the stream the monolithic host-side prefill
    stalls on; 6 admission waves over 4 slots make the stall recurrent).
    Sequential decode (one slot, blocking) is the bit-parity oracle:
    chunking must change WHEN tokens appear, never WHICH."""
    max_seq = long_prompt + max_new + 8
    cfg, model, params = build_model_and_params(arch, max_seq)
    plens = [long_prompt] + [short_prompt] * 3
    mk_queue = lambda: synthetic_requests(  # noqa: E731
        n_requests, plens, cfg.vocab, max_new, seed=11)

    def mk_engine(slots, chunked):
        return Engine.from_config(EngineConfig(
            max_seq=max_seq, n_slots=slots, segment_len=segment_len,
            page_size=8, chunked=chunked, chunk_size=chunk_size,
        ), model, params)

    (out_c, tps_c, ttft_c), (out_b, tps_b, ttft_b) = _serve_timed_paired(
        mk_engine(n_slots, True), mk_engine(n_slots, False), mk_queue)
    out_s, _, _ = _serve_timed(mk_engine(1, False), mk_queue)
    identical = (
        set(out_c) == set(out_b) == set(out_s)
        and all(np.array_equal(out_c[r], out_s[r]) for r in out_c)
        and all(np.array_equal(out_b[r], out_s[r]) for r in out_b)
    )
    tc, tb = _ttft_ms(ttft_c), _ttft_ms(ttft_b)
    return {
        "arch": arch,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "long_prompt": long_prompt,
        "short_prompt": short_prompt,
        "chunk_size": chunk_size,
        "tokens_per_request": max_new,
        "chunked_tok_s": round(tps_c, 2),
        "blocking_tok_s": round(tps_b, 2),
        "chunked_ttft_ms": tc["mean"],
        "chunked_ttft_p95_ms": tc["p95"],
        "blocking_ttft_ms": tb["mean"],
        "blocking_ttft_p95_ms": tb["p95"],
        "ttft_speedup": round(tb["mean"] / tc["mean"], 3),
        "bit_identical": bool(identical),
    }


def bench_prefix(
    arch: str = "stablelm-1.6b",
    n_slots: int = 4,
    n_requests: int = 4,
    prefix_len: int = 64,
    suffix_len: int = 8,
    max_new: int = 9,
    chunk_size: int = 8,
    segment_len: int = 2,
    repeats: int = 7,
) -> dict:
    """Prefix-cached vs cold chunked serving on a shared-system-prompt
    workload (every request opens with the same ``prefix_len`` tokens).
    The warm arm serves one priming pass, then re-serves with
    ``reset(keep_cache=True)`` so the registered prefix blocks hit:
    admission shares them (refcount++) and the in-scan prefill cursor
    starts past the cached rows — TTFT collapses to the suffix. The cold
    arm is the identical engine without the cache; outputs must be
    bit-identical (a cache hit changes WHEN work happens, never WHICH
    tokens come out)."""
    plen = prefix_len + suffix_len
    max_seq = plen + max_new + 15
    cfg, model, params = build_model_and_params(arch, max_seq)
    mk_queue = lambda: synthetic_requests(  # noqa: E731
        n_requests, plen, cfg.vocab, max_new, seed=11,
        shared_prefix=prefix_len)

    def mk_engine(prefix_cache):
        return Engine.from_config(EngineConfig(
            max_seq=max_seq, n_slots=n_slots, segment_len=segment_len,
            page_size=8, chunked=True, chunk_size=chunk_size,
            memory=MemoryConfig(prefix_cache=prefix_cache),
        ), model, params)

    eng_w, eng_c = mk_engine(True), mk_engine(False)
    eng_w.serve(mk_queue())  # compile + prime: registers the prefix pages
    eng_c.serve(mk_queue())
    warm = {"tps": 0.0, "ttft": None, "out": None}
    cold = {"tps": 0.0, "ttft": None, "out": None}
    hit_rows = 0
    for _ in range(repeats):
        for res, eng, keep in ((warm, eng_w, True), (cold, eng_c, False)):
            out, tps, ttft = _one_pass(eng, mk_queue, keep_cache=keep)
            res["out"] = out
            res["tps"] = max(res["tps"], tps)
            res["ttft"] = _ttft_floor(res["ttft"], ttft)
        hit_rows = eng_w.scheduler.stats["prefix_hit_rows"]
    identical = (
        set(warm["out"]) == set(cold["out"])
        and all(np.array_equal(warm["out"][r], cold["out"][r])
                for r in warm["out"])
    )
    tw, tc = _ttft_ms(warm["ttft"]), _ttft_ms(cold["ttft"])
    return {
        "arch": arch,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "tokens_per_request": max_new,
        "prefix_tok_s": round(warm["tps"], 2),
        "cold_tok_s": round(cold["tps"], 2),
        "prefix_ttft_ms": tw["mean"],
        "prefix_ttft_p95_ms": tw["p95"],
        "cold_ttft_ms": tc["mean"],
        "cold_ttft_p95_ms": tc["p95"],
        "prefix_ttft_speedup": round(tc["mean"] / tw["mean"], 3),
        "prefix_hit_rows": int(hit_rows),
        "bit_identical": bool(identical),
    }


def bench_overcap(
    arch: str = "stablelm-1.6b",
    n_slots: int = 6,
    n_requests: int = 12,
    prompt_len: int = 8,
    max_new: int = 17,
    segment_len: int = 8,
    n_blocks: int = 9,
) -> dict:
    """Host-tier over-capacity admission: the same workload served from a
    pool HALF the concurrent working set (6 slots x 3 pages vs 9 blocks)
    with ``host_tier=True`` — the scheduler parks cold slots' blocks to
    the host store (device->host bulk unloads) and pages them back by
    admission seniority — vs an amply-provisioned pool. Outputs must be
    bit-identical and the over-capacity run must actually unload (the
    paper's reversible-offload move applied to capacity)."""
    max_seq = prompt_len + max_new + 7
    cfg, model, params = build_model_and_params(arch, max_seq)
    mk_queue = lambda: synthetic_requests(  # noqa: E731
        n_requests, prompt_len, cfg.vocab, max_new, seed=11)

    def mk_engine(blocks, host_tier):
        return Engine.from_config(EngineConfig(
            max_seq=max_seq, n_slots=n_slots, segment_len=segment_len,
            page_size=8, n_blocks=blocks,
            memory=MemoryConfig(host_tier=host_tier),
        ), model, params)

    eng_o = mk_engine(n_blocks, True)
    (out_o, tps_o, _), (out_a, tps_a, _) = _serve_timed_paired(
        eng_o, mk_engine(0, False), mk_queue)
    unloaded = eng_o.scheduler.stats["host_unloads"]
    identical = (
        set(out_o) == set(out_a)
        and all(np.array_equal(out_o[r], out_a[r]) for r in out_o)
    )
    return {
        "arch": arch,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "n_blocks": n_blocks,
        "tokens_per_request": max_new,
        "overcap_tok_s": round(tps_o, 2),
        "ample_tok_s": round(tps_a, 2),
        "host_unloaded_blocks": int(unloaded),
        "bit_identical": bool(identical),
    }


def bench_spec(
    arch: str = "stablelm-1.6b",
    n_slots: int = 4,
    n_requests: int = 8,
    prompt_len: int = 16,
    max_new: int = 49,
    k: int = 8,
    segment_len: int = 1,
) -> dict:
    """Speculative decoding (the third execution path, DESIGN.md §11) vs
    the plain decode path, same workload, greedy. The SELF-DRAFT arm
    (``SpecConfig(draft_arch=None)``): the target drafts for itself, so
    acceptance is ~1 by construction and the row pins the MECHANICS win
    — each verify round reads the paged pool once for k+1 committed
    tokens instead of k+1 scattered per-token reads, with the per-round
    plumbing (routing decision, monitor update, sampling dispatch, and
    the segment-boundary host readback) amortized the same way.
    ``segment_len=1`` pins the streaming configuration — tokens cross to
    the host as soon as they exist, so the per-segment dispatch cost
    sits on the per-token path and the comparison isolates exactly the
    overhead speculation amortizes (both arms pay the same per-segment
    cost; the spec arm commits up to k+1 tokens per segment).
    ``committed_per_step_ratio`` is the acceptance-weighted
    committed-tokens-per-target-step headline; the parity contract makes
    ``bit_identical`` a hard gate."""
    max_seq = prompt_len + max_new + 8
    cfg, model, params = build_model_and_params(arch, max_seq)
    mk_queue = lambda: synthetic_requests(  # noqa: E731
        n_requests, prompt_len, cfg.vocab, max_new, seed=11)

    def mk_engine(spec):
        return Engine.from_config(EngineConfig(
            max_seq=max_seq, n_slots=n_slots, segment_len=segment_len,
            page_size=8, spec=spec), model, params)

    eng_sp = mk_engine(SpecConfig(enabled=True, k=k))
    (out_sp, tps_sp, _), (out_ns, tps_ns, _) = _serve_timed_paired(
        eng_sp, mk_engine(None), mk_queue)
    st = eng_sp.scheduler.stats
    identical = (
        set(out_sp) == set(out_ns)
        and all(np.array_equal(out_sp[r], out_ns[r]) for r in out_sp)
    )
    return {
        "arch": arch,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "tokens_per_request": max_new,
        "k": k,
        "spec_tok_s": round(tps_sp, 2),
        "nonspec_tok_s": round(tps_ns, 2),
        "spec_speedup": round(tps_sp / tps_ns, 3),
        "committed_per_step_ratio": round(
            st["spec_committed"] / max(1, st["spec_rounds"]), 3),
        "acceptance_ratio": round(
            st["spec_accepted"] / max(1, st["spec_proposed"]), 3),
        "bit_identical": bool(identical),
    }


def bench_sharded(
    arch: str = "stablelm-1.6b",
    n_slots: int = 8,
    n_requests: int = 16,
    prompt_len: int = 16,
    max_new: int = 33,
    segment_len: int = 8,
) -> dict:
    """Mesh-sharded vs single-device serving, same workload (DESIGN.md
    §9), in this process over ``jax.devices()``: ``tp`` is the largest
    mesh that keeps whole KV heads per shard. On CPU, launch with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so virtual
    devices exist. Virtual devices share the same silicon, so the tok/s
    ratio measures partitioning OVERHEAD, not speedup — the gated claims
    are bit-identity and the absolute throughputs."""
    from repro.serve import ParallelConfig

    max_seq = prompt_len + max_new + 15
    cfg, model, params = build_model_and_params(arch, max_seq)
    n_dev = jax.device_count()
    tp = 1
    while tp * 2 <= n_dev and cfg.n_kv_heads % (tp * 2) == 0:
        tp *= 2
    mk_queue = lambda: synthetic_requests(  # noqa: E731
        n_requests, prompt_len, cfg.vocab, max_new, seed=11)

    def mk_engine(tp_):
        return Engine.from_config(EngineConfig(
            max_seq=max_seq, n_slots=n_slots, segment_len=segment_len,
            path="adaptive", page_size=8,
            parallel=ParallelConfig.tensor(tp_) if tp_ > 1 else None,
        ), model, params)

    (out_s, tps_s, _), (out_1, tps_1, _) = _serve_timed_paired(
        mk_engine(tp), mk_engine(1), mk_queue)
    identical = (
        set(out_s) == set(out_1)
        and all(np.array_equal(out_s[r], out_1[r]) for r in out_s)
    )
    return {
        "arch": arch,
        "devices": n_dev,
        "tp": tp,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "tokens_per_request": max_new,
        "sharded_tok_s": round(tps_s, 2),
        "single_device_tok_s": round(tps_1, 2),
        "bit_identical": bool(identical),
    }


def run() -> list:
    cfg, model, params = build_model_and_params("h2o-danube-3-4b", 96)
    prompt = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab)
    rows = []
    for mode in ("direct", "staged", "adaptive"):
        def fresh():
            # the dense per-request engine IS the thing measured here
            # (jitted scan vs the seed's per-step reference loop), so it
            # is constructed directly; _warn=False keeps the deprecation
            # shim quiet in benchmark output
            return ServeEngine(model, params, ServeConfig(
                max_seq=96, write_mode=mode, ring_size=8, page_size=8,
                hot_threshold=12,
            ), _internal=True)

        eng = fresh()
        dt = _time_generate(eng, prompt, 24, reference=False)
        rows.append((f"serve/{mode}_ms_per_step", dt, "ms"))
        total = eng.stats["direct_writes"] + eng.stats["staged_writes"]
        if total:
            rows.append((f"serve/{mode}_staged_frac",
                         eng.stats["staged_writes"] / total, "x"))

        dt_ref = _time_generate(fresh(), prompt, 24, reference=True)
        rows.append((f"serve/{mode}_ref_ms_per_step", dt_ref, "ms"))
        rows.append((f"serve/{mode}_scan_speedup", dt_ref / dt, "x"))

    # continuous batching (smaller stream than the CLI default: the suite
    # runner favors breadth over statistics)
    b = bench_batched(n_slots=4, n_requests=6, max_new=17, segment_len=8)
    rows.append(("serve/batched_tok_s", b["batched_tok_s"], "tok/s"))
    rows.append(("serve/sequential_tok_s", b["sequential_tok_s"], "tok/s"))
    rows.append(("serve/batched_speedup", b["batched_speedup"], "x"))
    rows.append(("serve/batched_bit_identical", float(b["bit_identical"]), "bool"))
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batched", action="store_true",
                    help="run the continuous-batching throughput comparison")
    ap.add_argument("--chunked", action="store_true",
                    help="run the chunked-prefill TTFT/throughput comparison "
                         "on its PINNED mixed long/short-prompt workload (the "
                         "CI-gated trajectory; --slots/--requests/--prompt-len/"
                         "--max-new/--write-mode apply to --batched only)")
    ap.add_argument("--prefix", action="store_true",
                    help="run the prefix-cache TTFT + host-tier over-capacity "
                         "comparisons on their PINNED workloads (CI-gated "
                         "trajectories like --chunked)")
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative-decoding (self-draft) vs "
                         "plain-decode comparison on its PINNED workload "
                         "(CI-gated trajectory like --chunked)")
    ap.add_argument("--sharded", action="store_true",
                    help="run the mesh-sharded vs single-device comparison "
                         "over this process's devices (tp = largest mesh "
                         "dividing the arch's KV heads; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--json", default=None, help="write the JSON report here")
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=49)
    ap.add_argument("--write-mode", default="direct",
                    choices=("direct", "staged", "adaptive"))
    args = ap.parse_args()

    if (args.batched or args.chunked or args.prefix or args.sharded
            or args.spec):
        # host-class fingerprint: check_regression.py gates the absolute
        # tok/s / TTFT metrics only when baseline and report come from the
        # same class (ratios + bit-identity are gated unconditionally)
        report = {"env": {"machine": platform.machine(),
                          "cpus": os.cpu_count()}}
        if args.batched:
            report["batched"] = bench_batched(
                arch=args.arch, n_slots=args.slots, n_requests=args.requests,
                prompt_len=args.prompt_len, max_new=args.max_new,
                write_mode=args.write_mode,
            )
        if args.chunked:
            report["chunked"] = bench_chunked(arch=args.arch)
        if args.prefix:
            report["prefix"] = bench_prefix(arch=args.arch)
            report["overcap"] = bench_overcap(arch=args.arch)
        if args.spec:
            report["spec"] = bench_spec(arch=args.arch)
        if args.sharded:
            report["sharded"] = bench_sharded(arch=args.arch)
    else:
        report = {name: {"value": val, "unit": unit}
                  for name, val, unit in run()}
    doc = json.dumps(report, indent=2)
    print(doc)
    if args.json:
        with open(args.json, "w") as f:
            f.write(doc + "\n")
    if args.batched and report["batched"]["batched_speedup"] < 1.0:
        sys.exit(1)
    if args.chunked and report["chunked"]["ttft_speedup"] < 1.0:
        sys.exit(1)
    if args.prefix and (
        report["prefix"]["prefix_ttft_speedup"] < 1.0
        or not report["prefix"]["bit_identical"]
        or report["overcap"]["host_unloaded_blocks"] == 0
        or not report["overcap"]["bit_identical"]
    ):
        sys.exit(1)
    if args.spec and (
        report["spec"]["committed_per_step_ratio"] < 1.5
        or report["spec"]["spec_speedup"] <= 1.0
        or not report["spec"]["bit_identical"]
    ):
        sys.exit(1)
    if args.sharded and (
        report["sharded"]["tp"] < 2
        or not report["sharded"]["bit_identical"]
    ):
        sys.exit(1)


if __name__ == "__main__":
    main()
