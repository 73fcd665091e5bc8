"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps, kept with the benchmark so that every change computes these
numbers the same way.

An event is a tuple ``(plane, line, name, start_ns, dur_ns, meta)``;
``meta`` is the event's string-valued statistics joined by spaces (where
the HLO op and module names live). :func:`load_events` reads them from an
``.xplane.pb``; :func:`reduce_trace` works on any list of them, so it is
tested on constructed traces.

* The window is the host span named ``bench.span``.
* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:n`` plane, clipped to the window. Busy time is the union of
  their intervals, averaged over devices; the idle share is one minus busy
  over the window.
* A kernel's time is the summed duration of the operations whose own name
  (:func:`op_name`) is the kernel's.
* Each idle gap on the first device is labelled with the innermost
  benchmark host span (name starting ``bench.``) open at its midpoint.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN = "bench.span"
OPS_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """An operation's own name: device events carry the whole HLO
    instruction (``%flash_decode_paged.10 = bf16[...] custom-call(...)``);
    keep the name before `` = `` and drop the ``.N`` counters."""
    m = re.match(r"%?([^ =]+)", text)
    return re.sub(r"\.\d+", "", m.group(1) if m else text)


def load_events(trace_dir) -> List[tuple]:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            keep_meta = device and line.name == OPS_LINE
            for e in line.events:
                meta = ""
                if keep_meta:
                    meta = " ".join(f"{k}={v}" for k, v in e.stats)
                out.append((plane.name, line.name, e.name, int(e.start_ns),
                            int(e.duration_ns), meta))
    return out


def union_length(intervals: Iterable[Tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_of(intervals: Sequence[Tuple[int, int]], lo: int, hi: int):
    """Idle intervals of [lo, hi] not covered by ``intervals``."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: Sequence[tuple]) -> Dict[str, float]:
    """Exclusive time per operation name (:func:`op_name`): an
    operation that lies wholly inside another on the same line is
    subtracted from it."""
    by_name: Dict[str, float] = defaultdict(float)
    stack: List[list] = []   # [end, name, self]
    for s, d, name in sorted(((e[3], e[4], e[2]) for e in events),
                             key=lambda x: (x[0], -x[1])):
        while stack and (stack[-1][0] <= s or s + d > stack[-1][0]):
            _, nm, st = stack.pop()
            by_name[nm] += st
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, op_name(name), d])
    for _, nm, st in stack:
        by_name[nm] += st
    return by_name


def reduce_trace(events: Sequence[tuple], kernels: Sequence[str] = (),
                 top: int = 10) -> dict:
    spans = [e for e in events if e[2] == SPAN]
    if not spans:
        return None
    lo = spans[0][3]
    hi = lo + spans[0][4]
    per_dev: Dict[str, list] = defaultdict(list)
    for e in events:
        if e[0].startswith("/device:TPU") and e[1] == OPS_LINE:
            s, t = max(e[3], lo), min(e[3] + e[4], hi)
            if t > s:
                per_dev[e[0]].append((s, t - s, e[2], e[5]))
    if not per_dev:
        return None
    devs = sorted(per_dev)
    n = len(devs)
    busy = sum(union_length((s, s + d) for s, d, _, _ in per_dev[p])
               for p in devs) / n
    kernel_s = {}
    for k in kernels:
        t = sum(d for p in devs for s, d, name, meta in per_dev[p]
                if op_name(name) == k)
        kernel_s[k] = t / n / 1e9
    agg: Dict[str, float] = defaultdict(float)
    for p in devs:
        for name, t in self_times(
                [(p, OPS_LINE, nm, s, d, m) for s, d, nm, m in per_dev[p]]).items():
            agg[name] += t / n
    top_ops = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    host = [e for e in events if e[2].startswith("bench.") and e[2] != SPAN
            and not e[0].startswith("/device:")]
    labelled = []
    for s, t in gaps_of([(s, s + d) for s, d, _, _ in per_dev[devs[0]]], lo, hi):
        mid = (s + t) / 2
        open_ = [e for e in host if e[3] <= mid <= e[3] + e[4]]
        label = min(open_, key=lambda e: e[4])[2] if open_ else "outside bench spans"
        labelled.append((label, (t - s) / 1e9))
    labelled.sort(key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "idle_share": 1.0 - busy / (hi - lo),
        "kernel_s": kernel_s,
        "top_ops": [[nm, t / 1e9] for nm, t in top_ops],
        "gaps": [[nm, t] for nm, t in labelled[:top]],
        "devices": n,
    }
