"""Open-loop SLO loadtest: adaptive routing + deadline scheduling vs the
fixed write paths under FIFO.

Drives three arms of the SAME bursty two-tenant workload (an
``interactive`` class with tight SLOs and a ``batch`` class with loose
ones, arrivals from a calm/burst Markov-modulated Poisson process)
through three engines:

* ``path=direct,   sched=fifo``            (fixed path, arrival order)
* ``path=staged,   sched=fifo``            (fixed path, arrival order)
* ``path=adaptive, sched=sched/deadline``  (the paper's routing + EDF)

Each arm runs under the VIRTUAL clock (``serve.traffic.harness``): the
segment cost model charges the paper's measured per-path write latencies,
so the timeline — and therefore every goodput/TTFT number below — is
fully deterministic: identical on any machine, any JAX version. That is
what lets CI hard-gate ``goodput_ratio`` with no host-class carve-out.

Gate (CI fails when violated):

* ``goodput_ratio`` = adaptive goodput / best fixed-FIFO goodput must
  stay > 1 (the subsystem's reason to exist) and within tolerance of the
  committed baseline.
* ``bit_identical`` — all three arms must emit the same tokens per
  request. Scheduling and routing move WHEN work happens, never WHAT is
  computed.

CLI::

    PYTHONPATH=src python benchmarks/loadtest.py [--smoke] \
        [--json out.json] [--merge-into BENCH_serve.json] \
        [--requests 48] [--seed 7]

prints one JSON document; ``--merge-into`` inserts/replaces the
``loadtest`` section of an existing report/baseline file in place.
``--smoke`` shrinks the workload for the CI lane; both modes exit
nonzero when the gate fails.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys

import numpy as np

from repro.launch.device import enable_compile_cache
from repro.serve import Engine, EngineConfig, build_model_and_params
from repro.serve.traffic import (
    MarkovModulatedArrivals,
    RequestClass,
    generate_workload,
    run_loadtest,
)

ARCH = "stablelm-1.6b"
MAX_SEQ = 96

# (label, write path, admission sched) — the two fixed-path FIFO
# baselines and the arm under test
ARMS = (
    ("direct_fifo", "direct", "fifo"),
    ("staged_fifo", "staged", "fifo"),
    ("adaptive", "adaptive", "sched/deadline"),
)


def build_workload(n: int, seed: int, vocab: int):
    """Bursty two-tenant mix. Rates are in VIRTUAL requests/sec, sized
    against the cost model's service rate (a few thousand req/s for this
    reduced model): calm traffic the engine absorbs, bursts that build a
    backlog deep enough for admission order to matter."""
    classes = [
        RequestClass("interactive", ttft_slo_s=1.0e-3, itl_slo_s=1.0e-3,
                     priority=1, prompt_len=(4, 12), max_tokens=6,
                     weight=3.0),
        RequestClass("batch", ttft_slo_s=20.0e-3, itl_slo_s=5.0e-3,
                     priority=0, prompt_len=(24, 48), max_tokens=12,
                     weight=1.0),
    ]
    process = MarkovModulatedArrivals(calm_rps=400.0, burst_rps=60000.0,
                                      mean_calm_s=2.0e-3,
                                      mean_burst_s=8.0e-3)
    return generate_workload(classes, process, n, vocab=vocab, seed=seed)


def bench_loadtest(n_requests: int = 48, seed: int = 7) -> dict:
    cfg0, model, params = build_model_and_params(ARCH, MAX_SEQ)
    workload = build_workload(n_requests, seed, vocab=cfg0.vocab)

    results = {}
    for label, path, sched in ARMS:
        eng = Engine.from_config(EngineConfig(
            arch=ARCH, max_seq=MAX_SEQ, n_slots=8, segment_len=8,
            chunked=True, chunk_size=16, page_size=8,
            path=path, sched=sched,
        ), model, params)
        results[label] = run_loadtest(eng, workload, virtual=True)

    outs = {label: r.outputs for label, r in results.items()}
    ref = outs["direct_fifo"]
    bit_identical = all(
        sorted(o) == sorted(ref)
        and all(np.array_equal(o[rid], ref[rid]) for rid in ref)
        for o in outs.values())

    adaptive = results["adaptive"]
    best_fixed = max(results["direct_fifo"].goodput_rps,
                     results["staged_fifo"].goodput_rps)
    row = {
        "arch": ARCH,
        "n_slots": 8,
        "chunk_size": 16,
        "n_requests": adaptive.n_requests,
        "n_interactive": adaptive.per_class["interactive"].n,
        "n_batch": adaptive.per_class["batch"].n,
        "seed": seed,
    }
    for label, r in results.items():
        row[f"{label}_goodput_rps"] = round(r.goodput_rps, 1)
        row[f"{label}_slo_met"] = r.n_slo_met
    # virtual milliseconds — deterministic, so the _ms gate is exact
    inter = adaptive.per_class["interactive"]
    row["adaptive_interactive_ttft_p95_ms"] = round(
        inter.ttft_p95_s * 1e3, 4)
    row["goodput_ratio"] = round(
        adaptive.goodput_rps / best_fixed, 3) if best_fixed > 0 else 0.0
    row["bit_identical"] = bool(bit_identical)
    return row


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for the CI lane")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json", default=None, help="write the JSON report here")
    ap.add_argument("--merge-into", default=None,
                    help="insert the loadtest section into this existing "
                         "report/baseline file in place")
    args = ap.parse_args()

    n = 32 if args.smoke else args.requests
    row = bench_loadtest(n_requests=n, seed=args.seed)
    report = {"env": {"machine": platform.machine(),
                      "cpus": os.cpu_count()},
              "loadtest": row}
    print(json.dumps(report, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            f.write(json.dumps(report, indent=2) + "\n")
    if args.merge_into:
        doc = {}
        if os.path.exists(args.merge_into):
            with open(args.merge_into) as f:
                doc = json.load(f)
        doc.setdefault("env", report["env"])
        doc["loadtest"] = row
        with open(args.merge_into, "w") as f:
            f.write(json.dumps(doc, indent=2) + "\n")
    failures = []
    if not row["bit_identical"]:
        failures.append("token streams differ across arms")
    if row["goodput_ratio"] <= 1.0:
        failures.append(
            f"adaptive+deadline goodput does not beat the fixed FIFO "
            f"arms (ratio {row['goodput_ratio']})")
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
