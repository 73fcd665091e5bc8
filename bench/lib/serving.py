"""The open-loop driver: serves a seeded schedule through the program's
public entry, ``Engine.serve_stream(queue)``, and times every request on
the benchmark's side of the event stream.

``TimedQueue`` is a ``RequestQueue`` owned by the benchmark. Admission
asks it for its length at every scan segment; it then releases each
request whose due time the wall clock has passed. When every released
request has finished and none is due yet, it sleeps until the next due
time, so ``serve_stream`` never sees an idle engine with an empty queue,
never returns, and never resets mid-window.

``Accounting`` rebuilds, segment by segment, which prompt rows and which
decode positions each request processed, from what the stream shows
(admissions, first tokens, tokens per segment) and the engine's documented
schedule (a prefilling slot takes one ``chunk_size`` chunk a step; a
decoding slot one token a step). The rebuilt row counts are checked
against the engine's own write counters; a segment that disagrees marks
the count unusable instead of guessing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

from .flops import Shapes, Work, decode_steps, prefill_steps
from .traffic import Schedule

def span(name: str):
    """A host span in the profiler's trace (free when no trace is taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def make_queue_class(base):
    """``TimedQueue`` over the program's ``RequestQueue`` (passed in, so
    this module imports nothing of the program)."""

    class TimedQueue(base):
        def __init__(self, schedule: Schedule, params_for, engine_stats,
                     clock=time.perf_counter):
            super().__init__(clock=clock)
            self.schedule = schedule
            self.params_for = params_for
            self.engine_stats = engine_stats
            self.next_index = 0
            self.t0: Optional[float] = None
            self.outstanding = 0
            self.release_lag: List[float] = []
            self.due_abs: Dict[int, float] = {}
            self.admitted_seg: Dict[int, int] = {}
            self.waits = 0

        def start(self) -> None:
            self.t0 = self.clock()

        def pending(self) -> bool:
            return self.next_index < len(self.schedule.items)

        def release(self) -> None:
            if self.t0 is None:
                self.start()
            items = self.schedule.items
            now = self.clock()
            while self.pending() and self.t0 + items[self.next_index].due_s <= now:
                item = items[self.next_index]
                due = self.t0 + item.due_s
                rid = self.submit(item.prompt, params=self.params_for(item),
                                  arrival_s=due)
                self.due_abs[rid] = due
                self.release_lag.append(now - due)
                self.outstanding += 1
                self.next_index += 1

        def __len__(self) -> int:
            self.release()
            if (base.__len__(self) == 0 and self.outstanding == 0
                    and self.pending()):
                # idle engine, nothing due: wait for the next arrival
                with span("bench.arrival_wait"):
                    wake = self.t0 + self.schedule.items[self.next_index].due_s
                    time.sleep(max(0.0, wake - self.clock()))
                self.waits += 1
                self.release()
            return base.__len__(self)

        def pop_at(self, i: int):
            req = super().pop_at(i)
            self.admitted_seg[req.req_id] = int(self.engine_stats()["segments"])
            return req

        def finished(self, rid: int) -> None:
            self.outstanding -= 1

    return TimedQueue


@dataclasses.dataclass
class ReqLog:
    due: float
    plen: int
    max_tokens: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_first: Optional[float] = None
    first_seg: Optional[int] = None
    t_done: Optional[float] = None
    done_seg: Optional[int] = None
    prefilled: int = 0        # prompt rows processed (accounting)
    accounted: int = 0        # output tokens accounted (accounting)


class Accounting:
    """Per-segment work, rebuilt from the stream (see module docstring).

    ``flush`` is called once the events of a segment are all in, with the
    tokens each request gained there; segments in between that emitted
    nothing (every slot still in its prompt) are rebuilt with it."""

    def __init__(self, shapes: Shapes, chunk: int, segment_len: int):
        self.m = shapes
        self.chunk = chunk
        self.seg_rows = chunk * segment_len
        self.work: Dict[int, Work] = {}
        self.seen_seg = 0
        self.mismatched: List[int] = []

    def flush(self, logs: Dict[int, ReqLog], admitted: Dict[int, int],
              batch_seg: int, emitted: Dict[int, int],
              rows_written: int) -> None:
        rows = 0
        for seg in range(self.seen_seg + 1, batch_seg + 1):
            w = Work()
            last = seg == batch_seg
            for rid, log in logs.items():
                a = admitted.get(rid)
                if a is None or a >= seg:
                    continue
                if log.done_seg is not None and log.done_seg < seg:
                    continue
                k = emitted.get(rid, 0) if last else 0
                dec, first = 0, 0
                if log.prefilled < log.plen:
                    lo = log.prefilled
                    hi = min(log.plen, lo + self.seg_rows)
                    w.add(prefill_steps(self.m, lo, hi, self.chunk))
                    log.prefilled = hi
                    if hi == log.plen:
                        w.logit_rows += 1        # the first token
                        dec, first = max(0, k - 1), log.plen
                else:
                    dec, first = k, log.plen + log.accounted - 1
                if dec:
                    w.add(decode_steps(self.m, first, dec))
                log.accounted += k
            self.work[seg] = w
            rows += w.rows
        if rows != rows_written:
            self.mismatched.append(batch_seg)
        self.seen_seg = batch_seg

    def total(self, first_seg: int, last_seg: int) -> Work:
        out = Work()
        for seg in range(first_seg, last_seg + 1):
            if seg in self.work:
                out.add(self.work[seg])
        return out
