"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress, then one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last the ``checks`` compared against their limits, which
also close standard error. Exits non-zero, with no result, when JAX finds
no TPU or fewer chips than the cell needs.
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(t_process=T_PROCESS))
