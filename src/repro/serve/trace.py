"""Tracing of the serving loop: host spans on the profiler's clock and a
bounded record of every scan segment.

Spans are ``jax.profiler.TraceAnnotation``s, so in a profile they share
the timeline of the device operations; without a profile they cost one
no-op call. The scheduler opens one span per phase per segment, never one
per token or slot, and never keeps one open across a ``yield``:

* ``engine.retire``: free the slots whose requests finished;
* ``engine.admit``: take waiting requests and write their slot state;
* ``engine.topup``: page parked slots back in and extend page tables for
  the next segment;
* ``engine.dispatch``: choose the segment program and enqueue it;
* ``engine.readback``: wait for its results and fold them into the host
  state;
* ``engine.emit``: build the stream's events.

Durations use ``time.perf_counter``: they stay wall time when the engine
clock is virtual.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time
from typing import Deque, NamedTuple

import jax

RECORDS = 1024     # segment records kept; the oldest are dropped first


class SegmentRecord(NamedTuple):
    """One scan segment, as the host saw it.

    seg       the engine's segment count once this segment was counted
              (``stats["segments"]``)
    kind      the program that ran: ``"mixed"``, ``"decode"`` or ``"spec"``
    device_s  wall seconds from dispatch to readback complete: the
              segment's device time plus the enqueue and the copy back
    host_s    wall seconds the engine spent since the previous readback
              retiring, admitting, topping up and emitting (time the
              stream's consumer held it at a ``yield`` is not in it; time
              admission waited on the queue is)
    """

    seg: int
    kind: str
    device_s: float
    host_s: float


class SegmentLog:
    """The engine's host phases and its last ``RECORDS`` segment records."""

    def __init__(self, maxlen: int = RECORDS):
        self.records: Deque[SegmentRecord] = collections.deque(maxlen=maxlen)
        self._host_s = 0.0

    @contextlib.contextmanager
    def phase(self, name: str):
        """A host span whose wall time counts toward the next record's
        ``host_s``."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self._host_s += time.perf_counter() - t

    def record(self, seg: int, kind: str, device_s: float) -> None:
        self.records.append(SegmentRecord(seg, kind, device_s, self._host_s))
        self._host_s = 0.0

    def clear(self) -> None:
        self.records.clear()
        self._host_s = 0.0


def phase(name: str):
    """Run a method of an object that has a ``segment_log`` as one host
    phase (:meth:`SegmentLog.phase`)."""
    def wrap(method):
        @functools.wraps(method)
        def traced(self, *args, **kwargs):
            with self.segment_log.phase(name):
                return method(self, *args, **kwargs)
        return traced
    return wrap
