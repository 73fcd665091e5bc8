"""Benchmark harness entrypoint: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,engine,...]

Prints ``name,value,unit`` CSV rows (stable format for EXPERIMENTS.md).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.device import enable_compile_cache

SUITES = ("fig3", "engine", "policy_overhead", "moe_dispatch",
          "kernel_bench", "serve_modes")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    args = ap.parse_args()
    chosen = args.only.split(",") if args.only else list(SUITES)

    print("name,value,unit")
    failures = 0
    for name in chosen:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row, value, unit in mod.run():
                print(f"{row},{value:.4g},{unit}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}/ERROR,{type(e).__name__}: {e},-", file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(f"{failures} suite(s) failed")


if __name__ == "__main__":
    main()
