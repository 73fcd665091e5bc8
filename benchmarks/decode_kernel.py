"""Decode read-core microbenchmark: fused ``flash_decode_paged`` vs the
reference read path (page-table gather + ring concat + jnp SDPA).

Measures ONLY the attention read core — the thing the fused kernel
replaces — at a serving-representative paged shape (multi-slot step
decode plus a chunked mixed-phase slab), on live pool/ring/block-table
operands. Reports per-call wall time for both implementations and the
parity between them (``max_abs_diff`` against the jnp oracle must stay
at fp32 ulp level — ``parity_ok`` is the CI-gated correctness bit; see
DESIGN.md §7 for why the bound is ulps, not bits).

On CPU the kernel runs in INTERPRET mode (``backend: "cpu-interpret"``
in the row) — a validation lane, not a serving path, so the fused
timing there is an emulation cost, NOT the paper's claim; the
compiled-backend numbers are the ones that carry the fused >= reference
story. The gate therefore rides on the per-host ``*_ms`` trajectories
(same host class only) and ``parity_ok``, never on a cross-host ratio.

CLI::

    PYTHONPATH=src python benchmarks/decode_kernel.py \
        [--json out.json] [--merge-into BENCH_serve.json] [--repeats 20]

``--merge-into`` inserts/replaces the ``decode_kernel`` section of an
existing serve_modes report (or baseline) in place, so one combined
document flows into ``benchmarks/check_regression.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.launch.device import enable_compile_cache


def _bench(fn, args, repeats: int) -> float:
    """Best-of-``repeats`` wall ms for one jitted call (warm)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _operands(rng, b, c, hq, hkv, d, nb, ps, p, r):
    """Serving-shaped operands: a warm two-layer pool read at its last
    layer, a partially-filled ring, and a block table with the allocation
    raggedness real slots have."""
    n_layers = 2
    q = jnp.asarray(rng.randn(b, c, hq, d), jnp.float32)
    pk = jnp.asarray(rng.randn(n_layers, nb, ps, hkv, d), jnp.float32)
    pv = jnp.asarray(rng.randn(n_layers, nb, ps, hkv, d), jnp.float32)
    layer = jnp.asarray(n_layers - 1, jnp.int32)
    table = np.full((b, p), -1, np.int64)
    perm = rng.permutation(nb)
    n = 0
    for bi in range(b):  # slots at different fill depths
        k = 1 + (bi * (p - 1)) // max(b - 1, 1)
        table[bi, :k] = perm[n:n + k]
        n += k
    blocks = jnp.asarray(np.maximum(table, 0), jnp.int32)
    view_ok = jnp.asarray(
        np.repeat(table >= 0, ps, axis=1)[:, None, :]
        & (rng.rand(b, c, p * ps) > 0.1))
    ring_k = jnp.asarray(rng.randn(n_layers, b, r, hkv, d), jnp.float32)
    ring_v = jnp.asarray(rng.randn(n_layers, b, r, hkv, d), jnp.float32)
    ring_ok = jnp.asarray(np.arange(r)[None, :] < rng.randint(1, r + 1, (b, 1)))
    return q, pk, pv, layer, blocks, view_ok, ring_k, ring_v, ring_ok


def bench_decode_kernel(repeats: int = 20) -> dict:
    rng = np.random.RandomState(11)
    shape = dict(b=8, hq=8, hkv=4, d=64, nb=64, ps=8, p=8, r=8)

    fused = jax.jit(lambda *a: ops.flash_decode_paged(*a, impl="auto"))
    reference = jax.jit(ref.flash_decode_paged_ref)

    row = {
        "backend": jax.default_backend() + (
            "-interpret" if jax.default_backend() == "cpu" else ""),
        **shape,
    }
    worst = 0.0
    for phase, c in (("step", 1), ("chunk", 8)):
        args = _operands(rng, c=c, **shape)
        row[f"fused_{phase}_ms"] = round(_bench(fused, args, repeats), 3)
        row[f"reference_{phase}_ms"] = round(
            _bench(reference, args, repeats), 3)
        diff = float(jnp.max(jnp.abs(fused(*args) - reference(*args))))
        worst = max(worst, diff)
    row["max_abs_diff"] = worst
    # fp32 ulp-level bound with 10x margin (DESIGN.md §7); real kernel
    # bugs (wrong page, stale mask, dropped ring lane) miss by >= 1e-3
    row["parity_ok"] = bool(worst < 2e-6)
    return row


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, help="write the JSON report here")
    ap.add_argument("--merge-into", default=None,
                    help="insert the decode_kernel section into this "
                         "existing report/baseline file in place")
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args()

    row = bench_decode_kernel(repeats=args.repeats)
    report = {"env": {"machine": platform.machine(),
                      "cpus": os.cpu_count()},
              "decode_kernel": row}
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
        doc["decode_kernel"] = row
        with open(args.merge_into, "w") as f:
            f.write(json.dumps(doc, indent=2) + "\n")
    if not row["parity_ok"]:
        raise SystemExit(
            f"fused/reference parity broke: max_abs_diff={row['max_abs_diff']}")


if __name__ == "__main__":
    main()
