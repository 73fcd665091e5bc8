"""The one traffic generator: turns a mix file (``bench/traffic/<name>.json``)
and a cell file (``bench/cells/<workload>.json``) into a seeded schedule.

Every seed replays one trace of lengths and arrival gaps, drawn once from
the mix's own ``mix_seed`` and always in the same order, so a spread across
seeds measures the system and not the draw. ``--seed`` draws the token ids
(and the weights).

Mix kinds:

* ``open_loop`` — independent users: Poisson arrivals at the cell's
  ``rate_rps``, released on schedule whether or not earlier requests have
  finished. The first ``ramp_s`` seconds fill the system; requests due in
  the following ``seconds`` are the measured ones.
* ``batch`` — an offline backlog: every request is due at 0, and the queue
  holds more than the run can serve (``backlog_per_s`` requests per second
  of run).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

from .arrivals import lengths, poisson_gaps


@dataclasses.dataclass(frozen=True)
class Item:
    due_s: float            # seconds after the schedule starts
    prompt: np.ndarray      # int32 token ids
    max_tokens: int         # output tokens, the first included


@dataclasses.dataclass(frozen=True)
class Schedule:
    items: List[Item]
    window_start_s: float   # the measured window, schedule time
    window_end_s: float
    kind: str


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy Generator for any whole-number seed (64 bits and beyond)."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def max_seq(traffic: dict, page_size: int) -> int:
    """Longest request the mix can send, in whole pages."""
    n = int(traffic["prompt_tokens"]["max"]) + int(traffic["output_tokens"]["max"])
    return -(-n // page_size) * page_size


def make_schedule(traffic: dict, cell: dict, seed: int, seconds: float,
                  vocab: int) -> Schedule:
    ramp = float(traffic.get("ramp_s", 0.0))
    total = ramp + float(seconds)
    kind = traffic["kind"]
    mix = rng_for(int(traffic["mix_seed"]))
    if kind == "open_loop":
        if traffic["arrivals"] != "poisson":
            raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
        rate = float(cell["rate_rps"])
        n = int(math.ceil(rate * total))
        gaps = poisson_gaps(n, rate, mix)
    elif kind == "batch":
        n = int(math.ceil(float(traffic["backlog_per_s"]) * total))
        gaps = np.zeros((n,))
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    plens = lengths(traffic["prompt_tokens"], n, mix)
    olens = lengths(traffic["output_tokens"], n, mix)
    dues = np.cumsum(gaps)
    ids = rng_for(seed, 2)
    items = []
    for due, p, o in zip(dues, plens, olens):
        if kind == "open_loop" and due >= total:
            break
        items.append(Item(float(due),
                          ids.integers(0, vocab, size=int(p)).astype(np.int32),
                          int(o)))
    return Schedule(items, ramp, total, kind)
