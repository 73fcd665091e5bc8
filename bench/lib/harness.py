"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* ``bench/configs/<config>.json`` — model keys as published, plus the
  engine settings;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by the one
  generator in ``lib/traffic.py``;
* ``bench/cells/<workload>.json`` — what is fixed per cell: the slot count,
  the arrival rate, the correctness limit;
* ``bench/metrics/<metric>.py`` (or ``<prefix>.py`` for ``<prefix>.<suffix>``)
  — a reader ``read(ctx)`` returning the metric's value, or None when it
  finds nothing to read.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

import numpy as np

from . import serving as S
from .traffic import make_schedule, max_seq as traffic_max_seq, rng_for

ROOT = Path(__file__).resolve().parents[2]
TRACE_SECONDS = 3.0
DRAIN_TIMEOUT_S = 120.0
SAMPLE_TOKENS = 300        # served tokens the reference re-checks, at least
SAMPLE_MAX_REQUESTS = 8


class Refused(Exception):
    """A run that cannot measure: no result line, non-zero exit."""


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(root: Path, workload: str) -> SimpleNamespace:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = _by_name(bench["workloads"], workload, "workload")
    ce = _by_name(bench["configs"], wl["config"], "config")
    return SimpleNamespace(
        bench=bench, workload=wl,
        conf=json.loads((root / ce["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text()),
        cell=json.loads(
            (root / "bench" / "cells" / f"{workload}.json").read_text()))


def metrics_for(bench: dict, workload: str, trace: bool):
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(root: Path, name: str):
    """``bench/metrics/<name>.py``, else ``bench/metrics/<prefix>.py`` for a
    name ``<prefix>.<suffix>``."""
    d = root / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = d / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise Refused(f"no reader for metric {name!r} under {d}")


class CompileCount:
    """Backend compilations JAX reports (its own monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept, the
    small ones too, so a later run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / "bench" / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def rows_written(stats: dict) -> int:
    return (stats["prefill_writes"] + stats["direct_writes"]
            + stats["staged_writes"])


WARM_SEGMENTS = 2     # segments with tokens a long warm-up request is followed


def warm_up(engine, queue_cls, params_cls, n_slots, chunk, segment_len,
            sched):
    """Compile what the window will run, through ``serve_stream`` alone.

    First, admission and retirement of every group size up to the slot
    count, on one-chunk prompts: the whole group once through both segment
    kinds, each smaller group with one token, which it has after the first
    segment. Then every request of the cell's own schedule whose footprint
    outlasts what one segment can prefill (``chunk`` rows a step for
    ``segment_len`` steps), with its own prompt and output length, and one
    whose prompt ends inside that first segment and whose answer runs past
    it, ``n_slots`` at a time: each is followed until it has emitted tokens
    in ``WARM_SEGMENTS`` segments, so the memory the engine adds between
    segments, while the prompt is read and after, is asked for at the
    sizes the window will ask for. The stream is then left; the next
    ``serve_stream`` starts afresh."""
    def serve(prompts, lengths, follow=None):
        q = queue_cls()
        for p, n in zip(prompts, lengths):
            q.submit(p, params=params_cls(temperature=0.0, max_tokens=n))
        seen = {}
        stream = engine.serve_stream(q)
        try:
            for ev in stream:
                if len(ev.tokens):
                    seen.setdefault(ev.req_id, set()).add(
                        int(engine.stats["segments"]))
                if follow and len(seen) == len(prompts) and all(
                        len(s) >= follow for s in seen.values()):
                    break
        finally:
            stream.close()

    for g in range(n_slots, 0, -1):
        serve([np.full((chunk,), i + 1, np.int32) for i in range(g)],
              [segment_len + 2 if g == n_slots else 1] * g)
    first = chunk * segment_len
    long = sorted({(len(it.prompt), it.max_tokens) for it in sched.items
                   if len(it.prompt) + it.max_tokens - 1 > first})
    if long:
        # a prompt that ends inside the first segment and an answer that
        # runs past the rows it covered: memory added while decoding
        long.append((first - chunk // 2, 2 * segment_len))
    for k in range(0, len(long), n_slots):
        group = long[k:k + n_slots]
        serve([np.full((p,), 1, np.int32) for p, _ in group],
              [n for _, n in group], follow=WARM_SEGMENTS)


def pick_sample(logs, seed):
    """Finished requests the reference re-checks, drawn from the seed: the
    longest first, then others until some hundreds of served tokens."""
    finished = [i for i, lg in logs.items() if lg.t_done is not None]
    if not finished:
        return []
    pick = rng_for(seed, 3)
    longest = max(finished, key=lambda i: logs[i].plen + len(logs[i].tokens))
    rest = [i for i in finished if i != longest]
    rest = [rest[j] for j in pick.permutation(len(rest))]
    sample, n_tok = [longest], len(logs[longest].tokens)
    for i in rest:
        if n_tok >= SAMPLE_TOKENS or len(sample) >= SAMPLE_MAX_REQUESTS:
            break
        sample.append(i)
        n_tok += len(logs[i].tokens)
    return sample


def served_rows(logits_at, weights, conf, logs, sched, i, max_seq, out_max,
                quant=None):
    """(reference logits at the positions that chose request ``i``'s served
    tokens, the served tokens): the prompt and all but the last served
    token go in, one row per served token comes out."""
    lg, it = logs[i], sched.items[i]
    toks = np.asarray(lg.tokens, np.int32)
    seq = np.concatenate([it.prompt, toks[:-1]])
    ref = np.asarray(logits_at(weights, conf, seq, lg.plen - 1, max_seq,
                               out_max, quant))[: len(toks)]
    return ref, toks


CONTROL_QUANT = "fp8"     # the control's arithmetic: float8 e4m3, below bf16


def widest_gaps(logits_at, weights, conf, logs, sched, sample, max_seq,
                out_max, control=False):
    """The widest gap, over every served token of the sample, by which the
    served token's float32 logit lies below the float32 reference's best;
    with ``control``, also the widest gap of the token the reference in
    ``CONTROL_QUANT`` arithmetic puts first at the same positions (the
    control in the program's place). Returns (program, control or None)."""
    widest, widest_ctl = 0.0, (0.0 if control else None)
    for i in sample:
        ref, toks = served_rows(logits_at, weights, conf, logs, sched, i,
                                max_seq, out_max)
        rows = np.arange(len(toks))
        best = ref.max(-1)
        widest = max(widest, float((best - ref[rows, toks]).max()))
        if control:
            ctl, _ = served_rows(logits_at, weights, conf, logs, sched, i,
                                 max_seq, out_max, quant=CONTROL_QUANT)
            gap = best - ref[rows, ctl.argmax(-1)]
            widest_ctl = max(widest_ctl, float(gap.max()))
    return widest, widest_ctl


def run(argv=None, root: Optional[Path] = None, require_chip: bool = True,
        t_process: Optional[float] = None, out=None, err=None,
        cell_overrides: Optional[dict] = None,
        check: bool = True, control: bool = False) -> int:
    """One run; prints its result line. ``check=False`` (a rate sweep)
    skips the reference and stops at the window's end instead of following
    the requests due in it to completion. ``control=True`` judges the
    control in the program's place: the result's ``logit_gap`` is the
    control's, and the program's own is printed on an earlier line."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    root = Path(root or ROOT)
    try:
        c = load_cell(root, args.workload)
        c.cell.update(cell_overrides or {})
        result = _run(args, root, c, require_chip, t_process, out, err,
                      check, control)
    except Refused as e:
        print(f"bench: {e}", file=err, flush=True)
        return 2
    checks = result["checks"]
    for name, v in checks.items():
        print(f"check {name}: {v['value']} limit {v['limit']}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def _run(args, root, c, require_chip, t_process, out, err,
         check=True, control=False) -> dict:
    import jax

    wl = c.workload
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < int(wl["chips"])):
        raise Refused(f"cell {wl['name']} needs {wl['chips']} TPU chip(s); "
                      f"JAX found {len(devices)} {devices[0].platform} "
                      f"device(s)")
    cache = enable_cache(root)
    compiles = CompileCount()

    from repro.data.pipeline import RequestQueue
    from repro.models import build_model
    from repro.serve import Engine, EngineConfig, SamplingParams

    from ..reference.forward import logits_at
    from .model import model_config, shapes as shapes_of
    from .peaks import peaks_for
    from .weights import make_weights

    conf, traffic, cell = c.conf, c.traffic, c.cell
    eng = conf["engine"]
    cfg = model_config(conf)
    shapes = shapes_of(cfg)
    model = build_model(cfg)
    weights = make_weights(model, args.seed)
    jax.block_until_ready(weights)
    page = int(eng["page_size"])
    max_seq = traffic_max_seq(traffic, page)
    n_slots = int(cell["n_slots"])
    chunk, seg_len = int(eng["chunk_size"]), int(eng["segment_len"])
    engine = Engine.from_config(EngineConfig(
        max_seq=max_seq, n_slots=n_slots,
        n_blocks=n_slots * (max_seq // page), page_size=page,
        kv_layout="paged", chunked=True, chunk_size=chunk,
        segment_len=seg_len, ring_size=int(eng["ring_size"]),
        path=eng["path"], default_params=SamplingParams(temperature=0.0)),
        model, weights)
    sched = make_schedule(traffic, cell, args.seed, args.seconds, cfg.vocab)
    warm_up(engine, RequestQueue, SamplingParams, n_slots, chunk, seg_len,
            sched)
    print(f"set-up: {compiles.n} compilations ({compiles.seconds:.1f} s), "
          f"cache {cache}", file=out, flush=True)

    # ---------------- the measured window --------------------------------
    Queue = S.make_queue_class(RequestQueue)
    queue = Queue(sched, lambda it: SamplingParams(
        temperature=0.0, max_tokens=it.max_tokens), lambda: engine.stats)
    logs = {i: S.ReqLog(it.due_s, len(it.prompt), it.max_tokens)
            for i, it in enumerate(sched.items)}
    acct = S.Accounting(shapes, chunk, seg_len)
    trace_dir = root / "bench" / ".trace" / wl["name"]
    trace_at = (sched.window_start_s
                + max(0.0, (args.seconds - TRACE_SECONDS) / 2))
    w = SimpleNamespace(seg_start=None, seg_end=None, t_start=None,
                        t_end=None, c_start=0, c_end=0, s_start=None,
                        s_end=None, tr_seg0=None, tr_seg1=None,
                        tr_s0=None, tr_s1=None, tr_span=None, setup_s=None)
    snaps = {0: {k: 0 for k in engine.stats}}
    batch = SimpleNamespace(seg=0, emitted={}, out_tokens=0, prompt_tokens=0)
    per_batch = {}

    def close_batch():
        if batch.seg == 0:
            return
        prev = max(s for s in snaps if s < batch.seg)
        acct.flush(logs, queue.admitted_seg, batch.seg, batch.emitted,
                   rows_written(snaps[batch.seg]) - rows_written(snaps[prev]))
        per_batch[batch.seg] = (batch.out_tokens, batch.prompt_tokens)

    def boundary(seg, t):
        u = t - queue.t0
        stats = dict(engine.stats)
        snaps[seg] = stats
        if w.seg_start is None and u >= sched.window_start_s:
            w.seg_start, w.t_start, w.c_start, w.s_start = seg, t, compiles.n, stats
        elif (w.seg_start is not None and w.seg_end is None
              and u >= sched.window_end_s):
            w.seg_end, w.t_end, w.c_end, w.s_end = seg, t, compiles.n, stats
        if args.trace:
            if w.tr_seg0 is None and u >= trace_at:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                w.tr_span = jax.profiler.TraceAnnotation("bench.span")
                w.tr_span.__enter__()
                w.tr_seg0, w.tr_s0 = seg, stats
            elif (w.tr_seg0 is not None and w.tr_seg1 is None
                  and u >= trace_at + TRACE_SECONDS):
                w.tr_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                w.tr_seg1, w.tr_s1 = seg, stats

    def serve_window(stream):
        while True:
            with S.span("bench.serve_stream"):
                ev = next(stream, None)
            t = time.perf_counter()
            if w.setup_s is None:
                w.setup_s = queue.t0 - t_process
            if ev is None:
                close_batch()
                if w.seg_end is None and sched.kind == "open_loop":
                    # every request is served and none is due: the window
                    # closes at its nominal end
                    w.seg_end, w.c_end = batch.seg, compiles.n
                    w.s_end = dict(engine.stats)
                    w.t_end = max(t, queue.t0 + sched.window_end_s)
                    if w.seg_start is None:
                        w.seg_start, w.t_start = batch.seg, w.t_end
                        w.c_start, w.s_start = w.c_end, w.s_end
                return
            with S.span("bench.events"):
                seg = int(engine.stats["segments"])
                if seg != batch.seg:
                    close_batch()
                    if sched.kind == "batch" and w.seg_end is not None:
                        return
                    boundary(seg, t)
                    batch.seg, batch.emitted = seg, {}
                    batch.out_tokens = batch.prompt_tokens = 0
                    if (w.t_end is not None and sched.kind == "open_loop"
                            and (not check or t > w.t_end + DRAIN_TIMEOUT_S)):
                        return
                log = logs[ev.req_id]
                n = len(ev.tokens)
                if n:
                    if log.t_first is None:
                        log.t_first, log.first_seg = t, seg
                        batch.prompt_tokens += log.plen
                    log.tokens.extend(int(x) for x in ev.tokens)
                    batch.emitted[ev.req_id] = (
                        batch.emitted.get(ev.req_id, 0) + n)
                    batch.out_tokens += n
                if ev.done:
                    log.t_done, log.done_seg = t, seg
                    queue.finished(ev.req_id)

    stream = engine.serve_stream(queue)
    try:
        serve_window(stream)
    finally:
        stream.close()
        if w.tr_seg0 is not None and w.tr_seg1 is None:
            w.tr_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            w.tr_seg1, w.tr_s1 = batch.seg, dict(engine.stats)
    if w.seg_end is None:
        raise Refused("the window never closed: the schedule ran out first "
                      "(the backlog is too small for the run length)")
    lag = np.asarray(queue.release_lag) * 1e3
    print(f"generator: {len(lag)} requests released, late by p50 "
          f"{np.percentile(lag, 50):.3f} ms, p99 {np.percentile(lag, 99):.3f} "
          f"ms, max {lag.max():.3f} ms; {queue.waits} waits for an arrival",
          file=out, flush=True)

    dev = devices[0]
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    print(f"memory: peak {peak_bytes} of {mem.get('bytes_limit')} bytes",
          file=out, flush=True)
    del stream, engine
    gc.collect()

    # ---------------- what the window measured ---------------------------
    if sched.kind == "open_loop":
        measured = [i for i, lg in logs.items()
                    if sched.window_start_s <= lg.due < sched.window_end_s]
    else:
        measured = [i for i, lg in logs.items() if lg.first_seg is not None
                    and w.seg_start < lg.first_seg <= w.seg_end]
    done = [i for i in measured if logs[i].t_done is not None]
    failed = len(measured) - len(done) if sched.kind == "open_loop" else 0
    window_batches = [s for s in per_batch if w.seg_start < s <= w.seg_end]
    trace = None
    if args.trace:
        from .trace import load_events, reduce_trace
        trace = reduce_trace(load_events(trace_dir),
                             kernels=("flash_decode_paged",))
        shutil.rmtree(trace_dir, ignore_errors=True)
    span_work = None
    if args.trace and w.tr_seg1 is not None:
        off = [s for s in acct.mismatched if w.tr_seg0 < s <= w.tr_seg1 + 1]
        if not off:
            span_work = acct.total(w.tr_seg0 + 1, w.tr_seg1)
    stats_window = {k: w.s_end[k] - w.s_start[k] for k in w.s_end}
    stats_span = ({k: w.tr_s1[k] - w.tr_s0[k] for k in w.tr_s1}
                  if w.tr_s1 is not None else None)
    ctx = SimpleNamespace(
        setup_s=w.setup_s,
        ttft_s=[logs[i].t_first - (queue.t0 + logs[i].due) for i in done],
        tpot_s=[(logs[i].t_done - logs[i].t_first) / (len(logs[i].tokens) - 1)
                for i in done if len(logs[i].tokens) > 1],
        window_tokens=sum(per_batch[s][0] + per_batch[s][1]
                          for s in window_batches),
        window_elapsed=w.t_end - w.t_start,
        window_compiles=w.c_end - w.c_start,
        stats_window=stats_window, stats_span=stats_span,
        span_work=span_work, trace=trace, shapes=shapes,
        peaks=peaks_for(dev.device_kind) if dev.platform == "tpu" else None)
    if sched.kind == "open_loop":
        started = sorted((i for i in measured if logs[i].t_first is not None),
                         key=lambda i: logs[i].due)
        med = [float(np.median([logs[i].t_first - queue.t0 - logs[i].due
                                for i in part])) * 1e3
               if len(part) else float("nan")
               for part in np.array_split(started, 3)]
        finished = sum(1 for lg in logs.values() if lg.t_done is not None
                       and w.t_start <= lg.t_done <= w.t_end)
        out_tok = sum(per_batch[s][0] for s in window_batches)
        print(f"window: {len(measured)} requests due, {len(started)} started, "
              f"{len(done)} done; median ttft by due-time third "
              f"{med[0]:.1f} / {med[1]:.1f} / {med[2]:.1f} ms; "
              f"{finished / ctx.window_elapsed:.4f} req/s and "
              f"{out_tok / ctx.window_elapsed:.1f} output tok/s finished in "
              f"{ctx.window_elapsed:.3f} s", file=out, flush=True)
    else:
        print(f"window: {ctx.window_tokens} tokens in {ctx.window_elapsed:.3f} s",
              file=out, flush=True)
    metrics = {}
    for m in metrics_for(c.bench, wl["name"], bool(args.trace)):
        v = reader(root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---------------- correctness against the plain reference ------------
    vocab = cfg.vocab
    bad = [i for i in done if len(logs[i].tokens) != logs[i].max_tokens
           or not all(0 <= x < vocab for x in logs[i].tokens)]
    sample = pick_sample(logs, args.seed) if check else []
    n_tok = sum(len(logs[i].tokens) for i in sample)
    out_max = int(traffic["output_tokens"]["max"])
    widest, widest_ctl = widest_gaps(logits_at, weights, conf, logs, sched,
                                     sample, max_seq, out_max, control)
    if control:
        print(f"program: logit_gap {widest!r}", file=out, flush=True)
        widest = widest_ctl
    limit = float(cell["logit_gap_limit"])
    checks = {
        "logit_gap": {"value": widest, "limit": limit},
        "malformed_requests": {"value": len(bad), "limit": 0},
    }
    print(f"reference: {len(sample)} finished requests, {n_tok} served tokens "
          f"re-checked in float32", file=out, flush=True)
    correct = bool(widest <= limit and not bad and sample)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(wl["chips"]), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(measured),
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["top_ops"],
                               "idle_gaps": trace["gaps"]}
    result["checks"] = checks
    return result
