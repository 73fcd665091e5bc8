"""Readings that set a cell's fixed numbers, made on the chip in one
process (set-up is paid once per run, compilation once per process):

    # the knee: the cell's traffic at several arrival rates, no reference,
    # each run stopped at the window's end
    python bench/calibrate.py --workload <cell> --rates 1,2,3 --seconds 20

    # the correctness limit: per seed, the program's widest logit gap (the
    # run's own check), and with --control the control's (the reference in
    # float8 e4m3 in the program's place, judged by the same comparison:
    # its result line reads correct false)
    python bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 12
    python bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 12 \\
        --control

    # one traced run, with a summary of the raw trace (planes, lines, the
    # longest operations and their statistics) written to a JSON file
    python bench/calibrate.py --workload <cell> --seeds 5 --seconds 10 \\
        --trace 1 --dump-trace out/trace.json

Each run follows a ``calibration ...`` line naming its rate or seed, and
prints its result line as ``bench/run.py`` does.
"""
import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path


ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness, trace as T  # noqa: E402


def dump_summary(path):
    reduce = T.reduce_trace

    def wrapped(events, **kw):
        planes = Counter((e[0], e[1]) for e in events)
        dev = [e for e in events if e[0].startswith("/device:")]
        longest = [[e[0], e[1], e[2][:300], e[3], e[4], e[5][:300]]
                   for e in sorted(dev, key=lambda e: -e[4])[:40]]
        names = Counter(T.op_name(e[2]) for e in dev)
        custom = {}
        for e in dev:
            if "custom-call" in e[2] and T.op_name(e[2]) not in custom:
                custom[T.op_name(e[2])] = [e[2][:2000], e[5][:2000]]
        Path(path).parent.mkdir(parents=True, exist_ok=True)
        Path(path).write_text(json.dumps({
            "planes_lines": [[p, ln, n] for (p, ln), n in planes.items()],
            "longest_device_events": longest,
            "device_names": names.most_common(80),
            "custom_calls": custom,
        }, indent=1))
        return reduce(events, **kw)

    T.reduce_trace = wrapped


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--dump-trace", default="")
    ap.add_argument("--control", action="store_true",
                    help="judge the float8 control in the program's place")
    ap.add_argument("--set", action="append", default=[],
                    help="override a number of the cell file: key=value")
    a = ap.parse_args()
    if a.dump_trace:
        dump_summary(a.dump_trace)
    seeds = [int(s) for s in a.seeds.split(",")]
    base = ["--workload", a.workload, "--seconds", str(a.seconds),
            "--trace", str(a.trace)]
    sets = {k: float(v) for k, v in (kv.split("=") for kv in a.set)}
    sets = {k: int(v) if v.is_integer() and k == "n_slots" else v
            for k, v in sets.items()}
    rc = 0
    if a.rates:
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            print(f"calibration rate={rate}", flush=True)
            rc |= harness.run(base + ["--seed", str(seeds[i % len(seeds)])],
                              t_process=time.perf_counter(),
                              cell_overrides=dict(sets, rate_rps=rate),
                              check=False)
        return rc
    for seed in seeds:
        print(f"calibration seed={seed}", flush=True)
        rc |= harness.run(base + ["--seed", str(seed)],
                          t_process=time.perf_counter(),
                          control=a.control, cell_overrides=sets)
    return rc


if __name__ == "__main__":
    sys.exit(main())
