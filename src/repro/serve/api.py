"""The serving front door: one ``Engine`` facade over every scheduler.

``Engine.from_config(EngineConfig(...))`` subsumes the legacy
``ServeEngine`` / ``BatchedServeEngine`` split: cache layout, scheduling
mode (blocking vs chunked prefill), and the write path/policy pair are
CONFIG, not class choice — the offload/unload machinery stays pluggable
behind one stable request/response surface (the paper's two-path
contract, served through the ``repro.core.paths`` registry).

Requests are ``(prompt, SamplingParams)`` pairs; results are
:class:`Completion` objects carrying per-request telemetry — TTFT,
finish reason, and the write-path split (direct / staged / prefill
counts) the request's KV writes took. ``Engine.stream`` yields tokens as
scan segments retire them; ``Engine.generate`` drains to completion.

>>> eng = Engine.from_config(EngineConfig(arch="stablelm-1.6b", max_seq=64))
>>> [c] = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=8))
>>> c.tokens, c.ttft_s, c.path_counts
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Dict, Iterator, List, Optional, Sequence, Tuple,
                    Union)

import jax
import numpy as np

from ..core.paths import (AttentionConfig, MemoryConfig, ParallelConfig,
                          SpecConfig, normalize_groups)
from ..models.sampling import SamplingParams
from .scheduler import BatchConfig, BatchedServeEngine
from .trace import SegmentRecord

__all__ = [
    "AttentionConfig",
    "Completion",
    "Engine",
    "EngineConfig",
    "MemoryConfig",
    "ParallelConfig",
    "SpecConfig",
    "StreamEvent",
    "build_model_and_params",
]


def build_model_and_params(arch: str, max_seq: int, *, seed: int = 0,
                           reduced: bool = True):
    """(cfg, model, params) for a registered architecture — the one
    model-construction block the examples/benchmarks/CLIs share.

    Random weights from ``seed``. Serving keeps no float32 masters: the
    config's ``param_dtype`` is set to its compute dtype, as a served
    bf16 checkpoint is, so every parameter (the MoE router and the SSM
    conv included) is stored rounded to it. That halves the bytes of
    float32 masters: a published-width stablelm-1.6b fits one 16 GB chip
    only this way. Reduced configs compute in float32, so for them
    nothing changes."""
    from ..configs import get_config
    from ..models import build_model

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(seed), max_seq)


@dataclasses.dataclass
class EngineConfig:
    """Everything ``Engine.from_config`` needs — model choice, scheduler
    shape, write path/policy, and sampling defaults — in one place.

    ``path``/``policy`` name entries in the ``repro.core.paths`` /
    ``repro.core.policy`` registries (capability-negotiated against
    ``kv_layout``/``chunked`` at construction). ``default_params``
    applies to requests submitted without ``SamplingParams``, and its
    temperature also backfills requests whose own temperature is left
    ``None`` (see ``repro.models.sampling.resolve``).

    Cross-cutting features come GROUPED (DESIGN.md §9 migration table):
    ``attention=AttentionConfig(impl=..., drain_kernel=...)`` picks the
    paged decode read implementation and the drain kernel (a bare string
    is shorthand for ``AttentionConfig(impl=...)``),
    ``memory=MemoryConfig(prefix_cache=..., host_tier=...,
    skip_ahead_limit=...)`` the capacity features, and
    ``parallel=ParallelConfig(...)`` the serving mesh — the SAME spec
    that drives the train stack (``distributed.sharding``). The PR-7
    flat-kwarg deprecation shims are gone. Everything is negotiated
    through ONE entry point, ``core.paths.validate``, at engine build.

    ``sched`` names a registered admission policy
    (``repro.serve.traffic.sched``: ``"fifo"``, ``"sched/priority"``,
    ``"sched/deadline"``, ``"sched/preempt"``) — the order the scheduler
    scans the waiting queue in; see ``BatchConfig``.
    """

    max_seq: int
    arch: Optional[str] = None        # None when (model, params) are passed
    reduced: bool = True
    init_seed: int = 0
    # scheduler shape
    n_slots: int = 8
    segment_len: int = 16
    chunked: bool = False
    chunk_size: int = 8
    kv_layout: str = "auto"           # auto | paged | lanes
    # write path + decision plane (registry names)
    path: str = "direct"
    policy: Optional[str] = None
    sched: str = "fifo"
    page_size: int = 8
    n_blocks: int = 0
    ring_size: int = 8
    hot_threshold: int = 4
    attention: Any = None             # AttentionConfig (str = impl shorthand)
    # sampling
    default_params: Optional[SamplingParams] = None
    eos_id: Optional[int] = None
    sample_seed: int = 0
    # grouped sub-configs (the API; see class docstring)
    memory: Optional[MemoryConfig] = None
    parallel: Optional[ParallelConfig] = None
    # speculative decoding (the third execution path, DESIGN.md §11):
    # SpecConfig(draft_arch=..., k=..., enabled=True) turns decode
    # segments into draft-then-verify rounds — negotiated in
    # core.paths.validate (paged layout only; the draft must share the
    # target's vocab, draft_arch=None self-drafts with the target)
    spec: Optional[SpecConfig] = None

    def __post_init__(self):
        normalize_groups(self)

    def batch_config(self) -> BatchConfig:
        d = self.default_params
        return BatchConfig(
            max_seq=self.max_seq,
            n_slots=self.n_slots,
            segment_len=self.segment_len,
            page_size=self.page_size,
            n_blocks=self.n_blocks,
            ring_size=self.ring_size,
            hot_threshold=self.hot_threshold,
            greedy=(d is None or d.temperature is None
                    or d.temperature == 0.0),
            eos_id=self.eos_id,
            attention=self.attention,
            kv_layout=self.kv_layout,
            sample_seed=self.sample_seed,
            chunked=self.chunked,
            chunk_size=self.chunk_size,
            path=self.path,
            policy=self.policy,
            sched=self.sched,
            memory=self.memory,
            parallel=self.parallel,
            spec=self.spec,
            default_params=d,
        )


@dataclasses.dataclass
class Completion:
    """One finished request, with its telemetry.

    tokens        the emitted stream (np.int32, includes the prefill
                  token)
    params        the request's RESOLVED SamplingParams
    ttft_s        seconds from request ARRIVAL (queue-entry timestamp,
                  the queue clock's stamp) to the first emitted token —
                  queueing delay included, the open-loop SLO quantity.
                  Requests injected without going through
                  ``RequestQueue.submit`` fall back to seconds from serve
                  start.
    finish_reason ``"stop"`` (stop-token hit) or ``"length"`` (budget)
    path_counts   how this request's KV writes were routed:
                  {"direct", "staged", "prefill"} (prefill = bulk rows
                  pinned to the offload path)
    """

    req_id: int
    tokens: np.ndarray
    params: SamplingParams
    ttft_s: float
    finish_reason: str
    path_counts: Dict[str, int]

    @property
    def n_tokens(self) -> int:
        return int(self.tokens.shape[0])


@dataclasses.dataclass
class StreamEvent:
    """One streaming update: the tokens a request gained in the latest
    scan segment, plus its :class:`Completion` once it finishes."""

    req_id: int
    tokens: np.ndarray                 # the NEW tokens this event
    done: bool
    completion: Optional[Completion] = None


class Engine:
    """The one serving front door (see module docstring).

    Construct via :meth:`from_config`; the underlying continuous-batching
    scheduler (slots, paged pool / lanes, write-path machinery) is an
    implementation detail reachable at ``engine.scheduler`` for tests and
    benchmarks that need the internals.

    ``segment_records`` is the engine's own timing of its scan segments:
    a tuple of ``repro.serve.trace.SegmentRecord`` (segment count, program
    kind ``"mixed"``/``"decode"``/``"spec"``, wall seconds from dispatch to
    readback complete, host seconds spent since the previous readback),
    the newest ``repro.serve.trace.RECORDS`` of them, cleared on
    :meth:`reset`. In a profile (``jax.profiler``) the same phases are the
    host spans ``engine.retire``, ``engine.admit``, ``engine.topup``,
    ``engine.dispatch``, ``engine.readback`` and ``engine.emit``.
    """

    def __init__(self, model, params, cfg: EngineConfig):
        self.cfg = cfg
        self.model = model
        self.params = params
        self.scheduler = BatchedServeEngine(
            model, params, cfg.batch_config(), _internal=True)

    # ------------------------------------------------------------------
    @classmethod
    def from_config(cls, cfg: EngineConfig, model=None, params=None) -> "Engine":
        """Build the engine from config alone (``cfg.arch`` names a
        registered architecture) or around an existing (model, params)
        pair."""
        if model is None:
            if cfg.arch is None:
                raise ValueError(
                    "EngineConfig.arch is required when no model is passed")
            _, model, params = build_model_and_params(
                cfg.arch, cfg.max_seq, seed=cfg.init_seed,
                reduced=cfg.reduced)
        elif params is None:
            raise ValueError("passing model without params")
        return cls(model, params, cfg)

    # ------------------------------------------------------------------
    @property
    def layout(self) -> str:
        return self.scheduler.layout

    @property
    def stats(self) -> Dict[str, int]:
        return self.scheduler.stats

    @property
    def ttft(self) -> Dict[int, float]:
        return self.scheduler.ttft

    @property
    def segment_records(self) -> Tuple[SegmentRecord, ...]:
        return tuple(self.scheduler.segment_log.records)

    def reset(self, keep_cache: bool = False) -> None:
        """Fresh serving state; compiled segment functions are retained.
        ``keep_cache=True`` keeps the block pool + device cache so
        registered prefix blocks stay reusable (warm re-serve)."""
        self.scheduler.reset(keep_cache=keep_cache)

    # ------------------------------------------------------------------
    def _make_queue(self, prompts: Sequence, params, media):
        from ..data.pipeline import RequestQueue

        n = len(prompts)
        if params is None or isinstance(params, SamplingParams):
            plist = [params] * n
        else:
            plist = list(params)
            if len(plist) != n:
                raise ValueError(
                    f"{len(plist)} SamplingParams for {n} prompts")
        mlist = [None] * n if media is None else list(media)
        if len(mlist) != n:
            raise ValueError(f"{len(mlist)} media entries for {n} prompts")
        q = RequestQueue()
        for prompt, p, m in zip(prompts, plist, mlist):
            q.submit(prompt, media=m,
                     params=p or self.cfg.default_params or SamplingParams())
        return q

    def _completion(self, rid: int) -> Completion:
        eng = self.scheduler
        tokens = np.asarray(eng.outputs[rid], np.int32)
        params = eng.req_params[rid]
        stop = set(params.stop_token_ids)
        if self.cfg.eos_id is not None:
            stop.add(self.cfg.eos_id)
        reason = ("stop" if len(tokens) and int(tokens[-1]) in stop
                  else "length")
        d, s, p = (int(x) for x in eng.req_writes[rid])
        # arrival-based TTFT: absolute first-token instant minus the
        # queue-stamped arrival (both on the engine clock's timeline);
        # serve-relative fallback when either stamp is missing
        if rid in eng.first_token_t and rid in eng.req_arrival:
            ttft = float(eng.first_token_t[rid] - eng.req_arrival[rid])
        else:
            ttft = float(eng.ttft.get(rid, 0.0))
        return Completion(
            req_id=rid,
            tokens=tokens,
            params=params,
            ttft_s=ttft,
            finish_reason=reason,
            path_counts={"direct": d, "staged": s, "prefill": p},
        )

    # ------------------------------------------------------------------
    def stream(self, prompts: Sequence, params: Union[
            SamplingParams, Sequence[Optional[SamplingParams]], None] = None,
            media: Optional[Sequence] = None,
            max_segments: int = 100_000) -> Iterator[StreamEvent]:
        """Serve ``prompts`` and yield :class:`StreamEvent`s as scan
        segments emit tokens (requests stream concurrently; each event
        carries one request's new tokens). The final event for a request
        has ``done=True`` and its :class:`Completion`.
        """
        queue = self._make_queue(prompts, params, media)
        yield from self.serve_stream(queue, max_segments=max_segments)

    def serve_stream(self, queue, max_segments: int = 100_000,
                     ) -> Iterator[StreamEvent]:
        """`stream` over an explicit ``RequestQueue`` (power API: mixed
        media, pre-built synthetic workloads)."""
        eng = self.scheduler
        if eng.outputs:
            eng.reset()
        if eng._t_serve0 is None:
            # TTFT baseline = serve start (matches scheduler.serve):
            # admission prefill and compile time count toward the first
            # wave's TTFT instead of reading as 0.0
            eng._t_serve0 = eng._now()
        sent: Dict[int, int] = {}
        finished: set = set()

        def events(done_flags):
            # report in request order for determinism; done-ness comes
            # from the slot state (retirement happens next loop turn)
            done_now = {eng._slot_req[s]
                        for s in range(eng.cfg.n_slots)
                        if eng._occupied[s] and bool(done_flags[s])}
            out = []
            for rid in sorted(eng.outputs):
                if rid in finished:
                    continue
                new = eng.outputs[rid][sent.get(rid, 0):]
                is_done = rid in done_now
                if new or is_done:
                    sent[rid] = len(eng.outputs[rid])
                    completion = None
                    if is_done:
                        finished.add(rid)
                        completion = self._completion(rid)
                    out.append(StreamEvent(
                        req_id=rid,
                        tokens=np.asarray(new, np.int32),
                        done=is_done,
                        completion=completion,
                    ))
            return out

        for _ in range(max_segments):
            eng.retire_done()
            eng.admit(queue)
            if not any(eng._occupied):
                if len(queue) == 0:
                    return
                raise RuntimeError(
                    "queue head unadmittable with an empty engine "
                    "(request larger than pool capacity?)")
            live = ~np.asarray(eng.slots.done) & np.asarray(eng._occupied)
            if live.any():
                enabled = eng._topup_blocks()
                if not (live & enabled).any():
                    raise RuntimeError(
                        "every live slot stalled on block top-up: the pool "
                        "is too small for the admitted working set")
                eng.run_segment(enabled)
            # built whole before the first yield: no span stays open
            # while the consumer holds the stream
            with eng.segment_log.phase("engine.emit"):
                batch = events(np.asarray(eng.slots.done))
            yield from batch
        raise RuntimeError(f"stream() exceeded {max_segments} segments")

    # ------------------------------------------------------------------
    def generate(self, prompts: Sequence, params: Union[
            SamplingParams, Sequence[Optional[SamplingParams]], None] = None,
            media: Optional[Sequence] = None) -> List[Completion]:
        """Serve ``prompts`` to completion; returns one
        :class:`Completion` per prompt, in submission order."""
        done = {ev.req_id: ev.completion
                for ev in self.stream(prompts, params, media) if ev.done}
        return [done[rid] for rid in sorted(done)]

    def serve(self, queue, max_segments: int = 100_000,
              ) -> Dict[int, np.ndarray]:
        """Drain an explicit ``RequestQueue``; returns {req_id: tokens}
        (the legacy scheduler surface, kept for benchmarks/tests)."""
        return self.scheduler.serve(queue, max_segments=max_segments)

    def completions(self) -> Dict[int, Completion]:
        """Completions for every request served so far (post ``serve``)."""
        return {rid: self._completion(rid)
                for rid in self.scheduler.outputs}
