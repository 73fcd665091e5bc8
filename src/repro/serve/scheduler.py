"""Continuous-batching serve scheduler over the paged KV pool.

The repo's serving layer decoded one request (batch) at a time; this module
turns it into a slot-based continuous-batching system — the setting where
the paper's decision machinery actually earns its keep: a fixed array of
serving SLOTS decodes in lock-step inside ONE jitted ``lax.scan``, each
slot at its own position in its own request, so the DecisionModule sees a
genuinely interleaved multi-tenant write stream (per-slot destination
blocks in a SHARED physical pool) instead of a single flow.

Architecture (DESIGN.md §4–§5):

* **SlotState** — per-slot phase / token / position / done-flag /
  remaining-budget / sample-key / request-id / prompt-length, all
  fixed-shape int/bool arrays living in the scan carry. Retirement is
  IN-scan: a slot whose emitted token hits EOS or whose budget is spent
  flips ``done`` and from the next step neither writes KV (its physical
  destination resolves to the drop sentinel) nor updates the
  page-frequency monitor.
* **Mixed-phase segments** (``chunked=True``, paged layout) — prompts are
  NOT prefilled at admission: a request is admitted immediately with
  ``phase=PREFILL`` and a chunk cursor at 0, its prompt parked in a padded
  device-side buffer. Inside the scan each slot processes a
  [chunk_size]-token slab per step — prefill slots consume the next prompt
  chunk, decode slots their single sampled token — and a slot flips
  PREFILL→DECODE in-scan when its cursor crosses the prompt length
  (emitting its first token from the last prompt position's logits).
  Prefill writes are bulk/contiguous and phase-tagged ``PHASE_BULK`` so
  the decision plane pins them to the offload path; scattered decode
  writes stay adaptive. This dissolves the host-side prefill
  serialization: long prompts no longer stall the other slots' decode.
* **Admission** — BETWEEN scan segments, on the host: the FIFO
  ``RequestQueue`` is scanned in submission order and a request that does
  not fit (``BlockPool`` can't cover its next allocation) is SKIPPED in
  favor of later ones that do — it keeps its queue position and is
  admitted as soon as blocks free up, so relative order among
  admissible-when-eligible requests is preserved (no head-of-line
  blocking). With ``chunked=True`` block allocation is per-chunk: a slot
  holds only the pages the NEXT segment can touch, topped up between
  segments (a long prompt never reserves its whole footprint at
  admission; a slot whose top-up fails simply stalls for one segment).
* **KV writes** — every decode-time write resolves through the page table
  to a physical pool row; direct writes scatter straight in, staged writes
  ride the per-slot ring overlay and drain in bulk through
  ``core.ring.scatter_rows``. The monitor's region universe is the
  physical BLOCK id.

Two cache layouts:

* ``paged``  — dense non-SWA DecoderLM family: the paged pool + ring
  overlay (all three write modes, in-scan chunked prefill).
* ``lanes``  — every other family (SSM / hybrid / MoE / enc-dec / VLM /
  SWA): the model's own cache pytree with batch = n_slots; admission
  overwrites a retired slot's lane wholesale (every cache leaf carries
  batch on axis 1 — the repo-wide convention). Direct mode only, same
  scheduler machinery. ``chunked=True`` here runs the prompt through
  ``model.chunk_prefill`` chunk-by-chunk at admission (host side, same
  chunk size, bit-identical to whole-prompt prefill) — the in-scan mixed
  phase needs the paged pool's row addressing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.decision import replicate_for_readback
from ..core.paths import (AttentionConfig, MemoryConfig, ParallelConfig,
                          normalize_groups, validate)
from ..core.types import PHASE_BULK, PHASE_SCATTERED, make_write_batch
from ..distributed import sharding as DS
from ..data.pipeline import RequestQueue
from ..kvcache import paged as PG
from ..models import sampling as SMP
from ..models.sampling import SamplingParams, SlotParams
from ..models.transformer import DecoderLM, direct_kv_write
from .trace import SegmentLog, phase

# Slot phases (values of SlotState.phase). DONE is not a phase: the `done`
# flag retires a slot out of both phases.
PHASE_PREFILL = 0
PHASE_DECODE = 1

# a segment's device counters, read back once: direct and staged KV rows,
# drains, prefill rows, then the read's walk (``read_walk``)
N_STATS = 6


def paged_capable(model) -> bool:
    """Can this model serve from the paged pool? Linear-addressed dense
    ``DecoderLM`` only: SWA's ring addressing IS its window bound, and the
    VLM grouped scan lacks the mask plumbing (DESIGN.md §Arch-applicability)."""
    return (isinstance(model, DecoderLM)
            and not model.is_vlm
            and not model.cfg.sliding_window)


def read_walk(context: jnp.ndarray, active: jnp.ndarray, page_size: int,
              max_pages: int) -> jnp.ndarray:
    """One step's read of the paged pool, in one layer: [pages the read's
    grid visits, the pages among them holding context of an active query].
    The grid (``flash_decode_paged``, and the reference view alike) walks
    every page of every slot's table; ``context`` [n_slots] is the rows a
    slot's queries attend to this step."""
    live = jnp.minimum((context + page_size - 1) // page_size, max_pages)
    return jnp.stack([jnp.int32(active.shape[0] * max_pages),
                      jnp.sum(jnp.where(active, live, 0))])


class SlotState(NamedTuple):
    """Fixed slot array — the whole scheduler state inside the scan carry.

    phase:     int32[S] PHASE_PREFILL (consuming prompt chunks) or
               PHASE_DECODE (sampling); meaningful only while not done
    token:     int32[S] last emitted token (next decode step's input)
    pos:       int32[S] next logical row to write: the chunk cursor while
               prefilling, the decode position afterwards
    done:      bool[S]  retired (or never admitted) — inactive slots
    remaining: int32[S] tokens the slot may still emit
    key:       uint32[S, 2] per-slot PRNG key data (sampled decode)
    req_id:    int32[S] owning request id (-1 = empty)
    plen:      int32[S] prompt length (the PREFILL→DECODE flip point)

    Per-request sampling parameters (``repro.models.sampling``) ride in
    the same carry so every decode step samples each slot under its own
    request's knobs:

    temperature: f32[S]; top_k: i32[S]; top_p: f32[S];
    stop: i32[S, MAX_STOP_TOKENS] stop-token table (-1 padded, includes
    the engine eos_id)

    dkey: uint32[S, 2] draft-proposal PRNG key data (speculative
    decoding, DESIGN.md §11). A SEPARATE chain from ``key``: the draft
    samples its k proposals off ``dkey`` while the verify pass consumes
    ``key``, so the target chain's split sequence never depends on how
    many proposals the draft made. Zeros when spec is off.
    """

    phase: jnp.ndarray
    token: jnp.ndarray
    pos: jnp.ndarray
    done: jnp.ndarray
    remaining: jnp.ndarray
    key: jnp.ndarray
    req_id: jnp.ndarray
    plen: jnp.ndarray
    temperature: jnp.ndarray
    top_k: jnp.ndarray
    top_p: jnp.ndarray
    stop: jnp.ndarray
    dkey: jnp.ndarray

    @property
    def sampling(self) -> SlotParams:
        return SlotParams(temperature=self.temperature, top_k=self.top_k,
                          top_p=self.top_p, stop=self.stop)


def make_slots(n_slots: int) -> SlotState:
    sp = SMP.make_slot_params(n_slots)
    return SlotState(
        phase=jnp.full((n_slots,), PHASE_DECODE, jnp.int32),
        token=jnp.zeros((n_slots,), jnp.int32),
        pos=jnp.zeros((n_slots,), jnp.int32),
        done=jnp.ones((n_slots,), jnp.bool_),
        remaining=jnp.zeros((n_slots,), jnp.int32),
        key=jnp.zeros((n_slots, 2), jnp.uint32),
        req_id=jnp.full((n_slots,), -1, jnp.int32),
        plen=jnp.zeros((n_slots,), jnp.int32),
        temperature=sp.temperature,
        top_k=sp.top_k,
        top_p=sp.top_p,
        stop=sp.stop,
        dkey=jnp.zeros((n_slots, 2), jnp.uint32),
    )


@dataclasses.dataclass
class BatchConfig:
    """Continuous-batching engine configuration.

    ``max_seq`` bounds prompt_len + max_new per request; ``n_blocks = 0``
    sizes the pool for zero contention (n_slots * pages-per-slot).
    ``chunked`` admits prompts immediately and prefills them in
    ``chunk_size``-token chunks inside the decode scan (paged layout; the
    lanes layout chunk-prefills at admission instead).

    ``path`` / ``policy`` name a registered ``repro.core.paths.WritePath``
    and ``RoutingPolicy`` (capability-negotiated at construction);
    ``write_mode`` is the legacy alias — the built-in path names coincide
    with the old mode strings, and ``path`` wins when both are set.
    ``default_params`` supplies engine-wide ``SamplingParams`` defaults
    for requests that carry none; ``greedy`` is the legacy temperature
    default (0.0 when True, 1.0 when False) for params that leave
    ``temperature`` unset.

    ``attention`` picks the paged read implementation: ``"fused"`` (the
    ``flash_decode_paged`` kernel: page-table walk + ring overlay + SDPA
    in one pass), ``"reference"`` (jnp gather + concat — the kernel's
    parity oracle), or ``"auto"`` (negotiated through
    ``core.paths.resolve_attention``: fused wherever the kernel compiles
    natively, reference on CPU). ``drain_kernel=None`` likewise
    auto-selects the ``staged_scatter`` drain kernel (on by default
    off-CPU; ``REPRO_DRAIN_KERNEL`` overrides).

    Cross-cutting features come GROUPED (DESIGN.md §9):
    ``memory=MemoryConfig(...)`` holds ``prefix_cache`` (refcounted
    shared-prefix block reuse, paged + chunked only), ``host_tier`` (the
    second memory tier: pool-short slots are PARKED device->host and
    paged back in on demand, admission-seniority ordered) and
    ``skip_ahead_limit`` (the admission starvation bound);
    ``attention=AttentionConfig(...)`` the read implementation + drain
    kernel (a bare string is accepted as ``AttentionConfig(impl=...)``);
    ``parallel=ParallelConfig(...)`` the serving mesh (pool + ring
    head-sharded, decision plane replicated — DESIGN.md §9). The PR-7
    flat-kwarg deprecation shims are gone; the whole set is negotiated
    through ``core.paths.validate`` when the engine is built.

    ``sched`` names a registered admission policy
    (``repro.serve.traffic.sched``): ``"fifo"`` (default — byte-identical
    to the historic scheduler), ``"sched/priority"``,
    ``"sched/deadline"`` (EDF over ``Request.deadline_s``), or
    ``"sched/preempt"`` (priority + preempt-and-requeue through the host
    tier; needs ``memory.host_tier``). Policies permute only the
    admission SCAN ORDER — outputs stay bit-identical because per-request
    PRNG keys derive from the request id, not the slot or the order.
    """

    max_seq: int
    n_slots: int = 8
    segment_len: int = 16
    write_mode: str = "direct"
    page_size: int = 8
    n_blocks: int = 0
    ring_size: int = 8
    hot_threshold: int = 4
    greedy: bool = True
    eos_id: Optional[int] = None
    attention: Any = None        # AttentionConfig (str = impl shorthand)
    kv_layout: str = "auto"      # auto | paged | lanes
    sample_seed: int = 0
    chunked: bool = False
    chunk_size: int = 8
    path: Optional[str] = None
    policy: Optional[str] = None
    sched: str = "fifo"
    default_params: Optional[SamplingParams] = None
    # grouped sub-configs (the API; see class docstring)
    memory: Optional[MemoryConfig] = None
    parallel: Optional[ParallelConfig] = None
    # speculative decoding (the third execution path, DESIGN.md §11):
    # SpecConfig(draft_arch, k, enabled) — negotiated in
    # core.paths.validate (paged layout only, vocab-matched draft)
    spec: Any = None

    def __post_init__(self):
        normalize_groups(self)


class BatchedServeEngine:
    """Slot-based continuous-batching serving engine.

    >>> eng = BatchedServeEngine(model, params, BatchConfig(max_seq=128))
    >>> outputs = eng.serve(queue)          # {req_id: np.ndarray tokens}
    """

    def __init__(self, model, params, cfg: BatchConfig, *,
                 _internal: bool = False):
        if not _internal:
            raise TypeError(
                "constructing BatchedServeEngine directly was removed "
                "after its deprecation window (PR 4); build engines "
                "through repro.serve.Engine.from_config(...)")
        self.model = model
        self.params = params
        self.cfg = cfg

        # ONE negotiation pass (core.paths.validate): layout resolution,
        # memory/attention/path capability checks, and every mesh
        # interaction — loud errors, then the engine consumes only the
        # resolved plan
        mcfg = model.cfg
        plan = validate(
            cfg, paged_capable=paged_capable(model),
            n_heads=getattr(mcfg, "n_heads", None),
            n_kv_heads=getattr(mcfg, "n_kv_heads", None),
            arch_name=mcfg.name,
            vocab=getattr(mcfg, "vocab", None))
        self.plan = plan
        layout = plan.layout
        self.layout = layout
        self.mesh = plan.mesh
        self._prefix_cache = plan.memory.prefix_cache
        self._host_tier = plan.memory.host_tier
        self._skip_limit = plan.memory.skip_ahead_limit
        self._drain_kernel = plan.drain_kernel
        self._sched = plan.sched

        ps = cfg.page_size
        self.max_pages = plan.max_pages
        self.n_blocks = plan.n_blocks
        self.path, self.decision = plan.path, plan.decision
        self.uses_ring = self.path.uses_ring
        self.mon_state = self.decision.init_state()
        self.attention = plan.attention

        if layout == "paged":
            shape = jax.eval_shape(lambda: model.init_cache(1, cfg.max_seq))
            l, _, _, h, dh = shape["k"].shape
            self.pool = PG.BlockPool(self.n_blocks)
            self.cache = PG.make_paged_kv(
                l, self.n_blocks, ps, cfg.n_slots, self.max_pages, h, dh,
                dtype=shape["k"].dtype,
                ring_size=cfg.ring_size if self.uses_ring else 0,
            )
        else:
            self.pool = None
            self.cache = model.init_cache(cfg.n_slots, cfg.max_seq)
        self.slots = make_slots(cfg.n_slots)
        # device-side prompt buffer for in-scan chunked prefill
        self._in_scan_prefill = cfg.chunked and layout == "paged"
        self.prompts = (jnp.zeros((cfg.n_slots, cfg.max_seq), jnp.int32)
                        if self._in_scan_prefill else None)

        # speculative decoding (the third path, DESIGN.md §11): the
        # draft model proposes k tokens per live slot inside the spec
        # segment; the target verifies them in ONE chunked step
        self.spec = plan.spec
        self._spec_enabled = plan.spec.enabled
        self.draft_model = None
        self.draft_params = None
        self.draft_cache = None
        self._draft_positional = True
        self._draft_pos: List[int] = [-1] * cfg.n_slots
        if self._spec_enabled:
            self._init_draft(plan.draft_cfg)

        # host-side shadows (device round-trips happen once per segment)
        self._occupied = [False] * cfg.n_slots
        self._slot_req: List[int] = [-1] * cfg.n_slots
        self._slot_plen: List[int] = [0] * cfg.n_slots
        self._slot_max_new: List[int] = [0] * cfg.n_slots
        self._slot_pages: List[int] = [0] * cfg.n_slots
        # admission seniority (the host-tier victim order), prefix-cache
        # bookkeeping (per-slot page hashes + registration cursor), and
        # the host-tier park state (parked slots' blocks live host-side)
        self._slot_seq: List[int] = [-1] * cfg.n_slots
        self._adm_seq = 0
        self._slot_hashes: List[List[int]] = [[] for _ in range(cfg.n_slots)]
        self._slot_reg: List[int] = [0] * cfg.n_slots
        self._parked: List[bool] = [False] * cfg.n_slots
        self._host_store: Dict[int, tuple] = {}
        # bounded skip-ahead: how many admission rounds the current queue
        # head has been passed over (past the limit it reserves blocks)
        self._skip_rid = -1
        self._skip_count = 0
        self._base_key = jax.random.key(cfg.sample_seed)
        self.outputs: Dict[int, List[int]] = {}
        self.ttft: Dict[int, float] = {}
        # per-request telemetry: resolved SamplingParams and write-path
        # counts [direct, staged, prefill] (the Completion payload)
        self.req_params: Dict[int, SamplingParams] = {}
        self.req_writes: Dict[int, np.ndarray] = {}
        self._t_serve0: Optional[float] = None
        # the engine clock: all scheduler timestamps (ttft, first_token_t)
        # come off it. The loadtest harness swaps in a
        # serve.traffic.VirtualClock and sets ``time_model`` (a
        # per-segment-stats -> seconds cost model) so run_segment advances
        # virtual time instead of letting wall time pass.
        self.clock: Callable[[], float] = time.perf_counter
        self.time_model: Optional[Callable[[dict], float]] = None
        # open-loop accounting: per-request arrival instant (stamped at
        # admission from Request.arrival_s — the queue's clock), the
        # instant admission took it from the queue, and the ABSOLUTE
        # first-token instant on the engine clock; ``ttft`` stays
        # serve-relative (first token - serve start, the legacy value)
        self.req_arrival: Dict[int, float] = {}
        self.req_admit: Dict[int, float] = {}
        self.first_token_t: Dict[int, float] = {}
        # host phases and per-segment records (``serve.trace``)
        self.segment_log = SegmentLog()
        # preempt-and-requeue (sched/preempt): the original Request
        # objects (requeued verbatim on preemption) and each preempted
        # request's saved execution state (KV bytes host-side + slot
        # scalars + PRNG key) keyed by request id until it resumes
        self._requests: Dict[int, Any] = {}
        self._preempted: Dict[int, dict] = {}
        self._slot_priority: List[int] = [0] * cfg.n_slots
        self.stats = {
            "direct_writes": 0, "staged_writes": 0, "drains": 0,
            "prefill_writes": 0, "segments": 0, "admitted": 0, "retired": 0,
            "prefix_hit_rows": 0, "cow_copies": 0,
            "host_unloads": 0, "host_pageins": 0,
            "preemptions": 0, "preempt_resumes": 0,
            # the paged read's walk, per layer, summed over steps: pages
            # its grid visits, and those holding an active query's context
            "read_pages_walked": 0, "read_pages_live": 0,
            # speculative-decoding telemetry (SpecStats, core.types):
            # committed/rounds is the acceptance-weighted committed
            # tokens per target verify step — the headline BENCH metric
            "spec_proposed": 0, "spec_accepted": 0,
            "spec_committed": 0, "spec_rounds": 0,
        }
        # compiled segment variants keyed by STATIC sampler mode
        # (greedy/sampled/filtered — repro.models.sampling); _segment_fn /
        # _mixed_fn hold the last-used variant
        self._segment_fns: Dict[str, Callable] = {}
        self._mixed_fns: Dict[str, Callable] = {}
        self._spec_fns: Dict[str, Callable] = {}
        self._segment_fn: Optional[Callable] = None
        self._mixed_fn: Optional[Callable] = None
        self._spec_fn: Optional[Callable] = None
        self._prefill_fns: Dict[Any, Callable] = {}
        self._draft_prefill_fns: Dict[int, Callable] = {}
        # mesh-sharded serving (DESIGN.md §9): place params + the paged
        # pool under the plan's mesh; everything else replicates
        self._cache_shardings = None
        if self.mesh is not None:
            self._shard_device_state()

    def _shard_device_state(self) -> None:
        """Place device state under ``self.mesh``: parameters by the train
        stack's rules (``param_shardings``), the paged pool / staging ring
        by ``serve_cache_shardings`` — data planes split on the HEAD axis,
        page table + ring metadata replicated so the host decision plane
        is identical on every shard and drains stay shard-local. The
        jitted segments pick the placements up from their inputs (GSPMD
        propagation) and re-pin them on output."""
        mesh = self.mesh
        self.params = jax.device_put(
            self.params, DS.param_shardings(self.model.cfg, mesh,
                                            self.params))
        self._cache_shardings = DS.serve_cache_shardings(
            self.model.cfg, mesh, self.cache)
        self.cache = {k: jax.device_put(v, self._cache_shardings[k])
                      for k, v in self.cache.items()}

    def _init_draft(self, dcfg) -> None:
        """Build the draft side of the speculative path: draft model +
        params (SHARED with the target when the resolved draft is the
        target's own arch — self-draft, ``SpecConfig(draft_arch=None)``),
        plus a DENSE per-slot draft cache (lanes layout: batch on axis 1
        of every leaf). The draft cache is derived data — admission,
        preemption-resume and chunked PREFILL→DECODE flips just mark the
        slot stale (``_draft_pos = -1``) and ``_sync_draft`` re-prefills
        it from the committed tokens before the next spec segment."""
        if dcfg.name == self.model.cfg.name and dcfg == self.model.cfg:
            self.draft_model = self.model
            self.draft_params = self.params
        else:
            from ..models.model import build_model
            self.draft_model = build_model(dcfg)
            self.draft_params = self.draft_model.init(
                jax.random.key(17), self.cfg.max_seq)
        # positional drafts (dense/moe KV lanes) roll back by cursor
        # arithmetic; recurrent drafts (ssm) need state snapshots
        self._draft_positional = dcfg.family in ("dense", "moe")
        self.draft_cache = self.draft_model.init_cache(
            self.cfg.n_slots, self.cfg.max_seq)
        self._draft_pos = [-1] * self.cfg.n_slots

    def reset(self, keep_cache: bool = False) -> None:
        """Fresh serving state (cache, slots, pool, monitor, outputs) with
        the compiled segment functions retained — benchmark/test runs can
        re-serve without paying compilation again. ``keep_cache=True``
        keeps the pool and device cache instead (every slot must already
        be retired): registered prefix blocks survive on the evictable
        list, so the next pass serves WARM — the prefix-cache benchmark's
        cached-TTFT arm."""
        cfg = self.cfg
        if keep_cache:
            if any(self._occupied):
                raise RuntimeError(
                    "reset(keep_cache=True) needs a drained engine "
                    "(live slots still hold pool blocks)")
        elif self.layout == "paged":
            self.pool = PG.BlockPool(self.n_blocks)
            l, _, ps, h, dh = self.cache["pages_k"].shape
            self.cache = PG.make_paged_kv(
                l, self.n_blocks, ps, cfg.n_slots, self.max_pages, h, dh,
                dtype=self.cache["pages_k"].dtype,
                ring_size=cfg.ring_size if self.uses_ring else 0,
            )
        else:
            self.cache = self.model.init_cache(cfg.n_slots, cfg.max_seq)
        if self.mesh is not None and not keep_cache:
            self._shard_device_state()
        self.slots = make_slots(cfg.n_slots)
        if self._in_scan_prefill:
            self.prompts = jnp.zeros((cfg.n_slots, cfg.max_seq), jnp.int32)
        self.mon_state = self.decision.init_state()
        self._occupied = [False] * cfg.n_slots
        self._slot_req = [-1] * cfg.n_slots
        self._slot_plen = [0] * cfg.n_slots
        self._slot_max_new = [0] * cfg.n_slots
        self._slot_pages = [0] * cfg.n_slots
        self._slot_seq = [-1] * cfg.n_slots
        self._adm_seq = 0
        self._slot_hashes = [[] for _ in range(cfg.n_slots)]
        self._slot_reg = [0] * cfg.n_slots
        self._parked = [False] * cfg.n_slots
        self._host_store = {}
        self._skip_rid = -1
        self._skip_count = 0
        self.outputs = {}
        self.ttft = {}
        self.req_params = {}
        self.req_writes = {}
        self._t_serve0 = None
        self.req_arrival = {}
        self.req_admit = {}
        self.first_token_t = {}
        self.segment_log.clear()
        self._requests = {}
        self._preempted = {}
        self._slot_priority = [0] * cfg.n_slots
        if self._spec_enabled:
            self.draft_cache = self.draft_model.init_cache(
                cfg.n_slots, cfg.max_seq)
            self._draft_pos = [-1] * cfg.n_slots
        self.stats = {k: 0 for k in self.stats}

    # ------------------------------------------------------------------
    # segments: the jitted inner loops
    # ------------------------------------------------------------------
    def _build_segment(self, mode: str) -> Callable:
        """Pure-decode segment: every live slot samples one token per step
        (the steady state; also the only segment the non-chunked engine
        runs). ``mode`` statically specializes the sampler to the live
        slots' params (a pure-greedy batch pays exactly the argmax step)."""
        model, cfg = self.model, self.cfg
        paged = self.layout == "paged"
        ring = paged and self.uses_ring
        ps, nb, mp = cfg.page_size, self.n_blocks, self.max_pages
        decision = self.decision
        attn = self.attention
        dk = self._drain_kernel
        mesh, cache_shardings = self.mesh, self._cache_shardings

        def step(params, enabled, plan, carry, _):
            cache, st, mon, stats, swrites = carry
            active = ~st.done & enabled
            walk = (read_walk(st.pos + 1, active, ps, mp)
                    if paged else jnp.zeros((2,), jnp.int32))
            if paged:
                dest = PG.logical_to_physical(
                    cache, jnp.where(active, st.pos, -1))
                region = jnp.minimum(dest // ps, nb - 1)
            else:
                region = (jnp.arange(cfg.n_slots) * mp
                          + jnp.clip(st.pos // ps, 0, mp - 1))
            unload, mon, _ = decision(
                mon, make_write_batch(region), active=active)
            n_u = jnp.sum(unload.astype(jnp.int32))
            drained = jnp.zeros((), jnp.bool_)
            if ring:
                cache, drained = PG.maybe_drain(
                    cache, use_kernel=dk,
                    incoming_pos=jnp.where(active, st.pos, -1),
                    shardings=cache_shardings)
                logits, cache = model.decode_step_paged(
                    params, cache, st.token, st.pos, active,
                    unload_mask=unload, attention=attn, plan=plan,
                    mesh=mesh)
            elif paged:
                logits, cache = model.decode_step_paged(
                    params, cache, st.token, st.pos, active,
                    attention=attn, plan=plan, mesh=mesh)
            else:
                # retired slots never write: redirect their scatter rows
                # to the out-of-range drop sentinel (SSM recurrent state
                # has no KV scatter — its lane updates are slot-private
                # and overwritten wholesale at admission)
                def masked_writer(kc, vc, k_new, v_new, rows):
                    return direct_kv_write(
                        kc, vc, k_new, v_new,
                        jnp.where(active, rows, kc.shape[1]))

                logits, cache = model.decode_step(
                    params, cache, st.token, st.pos, kv_writer=masked_writer)
            # per-request sampling: every slot under its own params, its
            # own key chain (repro.models.sampling contract)
            nxt, key = SMP.sample_tokens(logits, st.key, st.sampling,
                                         mode=mode)
            nxt = jnp.where(active, nxt, st.token)
            remaining = st.remaining - active.astype(jnp.int32)
            ended = (remaining <= 0) | SMP.hits_stop(nxt, st.stop)
            st = st._replace(
                token=nxt,
                pos=st.pos + active.astype(jnp.int32),
                done=st.done | (active & ended),
                remaining=remaining,
                key=key,
            )
            stats = stats + jnp.concatenate([jnp.stack([
                jnp.sum(active.astype(jnp.int32)) - n_u,
                n_u,
                drained.astype(jnp.int32),
                jnp.zeros((), jnp.int32),
            ]), walk])
            swrites = swrites + jnp.stack([
                (active & ~unload).astype(jnp.int32),
                unload.astype(jnp.int32),
                jnp.zeros_like(st.pos),
            ], axis=1)
            emit = jnp.where(active, nxt, -1)
            return (cache, st, mon, stats, swrites), (emit, active)

        def segment_decode(params, cache, st, mon, enabled):
            # page-table products are segment-invariant (allocation is
            # host-side, between segments): derive them ONCE here, outside
            # the scan, instead of once per step per layer
            plan = PG.step_plan(cache) if paged else None
            stats0 = jnp.zeros((N_STATS,), jnp.int32)
            sw0 = jnp.zeros((cfg.n_slots, 3), jnp.int32)
            (cache, st, mon, stats, swrites), (emits, acts) = lax.scan(
                lambda c, x: step(params, enabled, plan, c, x),
                (cache, st, mon, stats0, sw0),
                None,
                length=cfg.segment_len,
            )
            if ring:
                # segment boundary: the host may retire slots and free
                # their blocks next — the ring must not hold entries that
                # would later drain into reallocated blocks
                cache = PG.drain_ring(cache, use_kernel=dk,
                                      shardings=cache_shardings)
            if mesh is not None:
                # pin the pool/ring back to their head shards and the
                # telemetry carries to replicated — the segment boundary
                # is the readback point (DESIGN.md §9)
                cache = PG.constrain(cache, cache_shardings)
                st, mon, stats, swrites, emits, acts = (
                    replicate_for_readback(
                        (st, mon, stats, swrites, emits, acts), mesh))
            return cache, st, mon, stats, swrites, emits, acts

        return jax.jit(segment_decode)

    def _build_mixed_segment(self, mode: str) -> Callable:
        """Mixed-phase segment (chunked, paged layout): each step every
        live slot processes a [chunk_size]-token slab — the next prompt
        chunk (PREFILL) or its one decode token (DECODE, column 0) — and a
        slot flips PREFILL→DECODE in-scan when its cursor crosses plen,
        emitting its first token from the last prompt position's logits.
        Prefill writes are phase-tagged PHASE_BULK: the decision plane
        pins them to the offload/direct path; scattered decode writes keep
        adaptive routing."""
        model, cfg = self.model, self.cfg
        ring = self.uses_ring
        ps, nb, c = cfg.page_size, self.n_blocks, cfg.chunk_size
        mp = self.max_pages
        decision = self.decision
        attn = self.attention
        dk = self._drain_kernel
        mesh, cache_shardings = self.mesh, self._cache_shardings

        def step(params, prompts, enabled, plan, carry, _):
            cache, st, mon, stats, swrites = carry
            active = ~st.done & enabled
            is_pf = active & (st.phase == PHASE_PREFILL)
            # token slab: prefill slots read the device prompt buffer at
            # their chunk cursor; decode slots put their token in column 0
            offs = jnp.arange(c, dtype=jnp.int32)[None, :]
            idx = jnp.clip(st.pos[:, None] + offs, 0, prompts.shape[1] - 1)
            pf_toks = jnp.take_along_axis(prompts, idx, axis=1)
            dec_toks = jnp.pad(st.token[:, None], ((0, 0), (0, c - 1)))
            tokens = jnp.where(is_pf[:, None], pf_toks, dec_toks)
            n_valid = jnp.where(is_pf,
                                jnp.minimum(c, st.plen - st.pos),
                                active.astype(jnp.int32))
            qvalid = offs < n_valid[:, None]
            rows = st.pos[:, None] + offs
            # decision plane: ONE flattened phase-tagged batch per step —
            # bulk prefill rows are pinned offload, decode rows adaptive
            dest_all = PG.logical_to_physical_many(
                cache, jnp.where(qvalid, rows, -1))
            region = jnp.minimum(dest_all // ps, nb - 1)
            phase_tag = jnp.where(
                is_pf[:, None] & qvalid, PHASE_BULK, PHASE_SCATTERED)
            unload_flat, mon, _ = decision(
                mon,
                make_write_batch(region.reshape(-1),
                                 phase=phase_tag.reshape(-1)),
                active=qvalid.reshape(-1))
            unload = (unload_flat.reshape(cfg.n_slots, c)[:, 0]
                      & active & ~is_pf)
            n_u = jnp.sum(unload.astype(jnp.int32))
            n_dec = jnp.sum((active & ~is_pf).astype(jnp.int32))
            n_pf = jnp.sum((qvalid & is_pf[:, None]).astype(jnp.int32))
            walk = read_walk(st.pos + n_valid, active, ps, mp)
            drained = jnp.zeros((), jnp.bool_)
            if ring:
                cache, drained = PG.maybe_drain(
                    cache, use_kernel=dk,
                    incoming_pos=jnp.where(active & ~is_pf, st.pos, -1),
                    shardings=cache_shardings)
                logits, cache = model.decode_chunk_paged(
                    params, cache, tokens, st.pos, n_valid, active,
                    unload_mask=unload, attention=attn, plan=plan,
                    mesh=mesh)
            else:
                logits, cache = model.decode_chunk_paged(
                    params, cache, tokens, st.pos, n_valid, active,
                    attention=attn, plan=plan, mesh=mesh)
            finishing = is_pf & (st.pos + n_valid >= st.plen)
            emitting = (active & ~is_pf) | finishing
            # the first token after the prompt is the prefill ARGMAX in
            # both engines and both sampling modes (parity with the
            # non-chunked engine's admission-time t0)
            t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled, new_key = SMP.sample_tokens(logits, st.key,
                                                 st.sampling, mode=mode)
            dec = active & ~is_pf
            # prefill steps consume no key: the per-request split
            # sequence stays identical to the non-chunked engine
            nxt = jnp.where(dec, sampled, t0)
            key = jnp.where(dec[:, None], new_key, st.key)
            nxt = jnp.where(emitting, nxt, st.token)
            remaining = st.remaining - emitting.astype(jnp.int32)
            ended = (remaining <= 0) | SMP.hits_stop(nxt, st.stop)
            st = st._replace(
                phase=jnp.where(finishing, PHASE_DECODE, st.phase),
                token=nxt,
                pos=st.pos + n_valid,
                done=st.done | (emitting & ended),
                remaining=remaining,
                key=key,
            )
            stats = stats + jnp.concatenate([jnp.stack(
                [n_dec - n_u, n_u, drained.astype(jnp.int32), n_pf]), walk])
            swrites = swrites + jnp.stack([
                (dec & ~unload).astype(jnp.int32),
                unload.astype(jnp.int32),
                jnp.where(is_pf, n_valid, 0),
            ], axis=1)
            emit = jnp.where(emitting, nxt, -1)
            return (cache, st, mon, stats, swrites), (emit, emitting)

        def segment_mixed(params, cache, st, mon, prompts, enabled):
            # per-segment hoist of page-table products (see _build_segment)
            plan = PG.step_plan(cache)
            stats0 = jnp.zeros((N_STATS,), jnp.int32)
            sw0 = jnp.zeros((cfg.n_slots, 3), jnp.int32)
            (cache, st, mon, stats, swrites), (emits, ems) = lax.scan(
                lambda cry, x: step(params, prompts, enabled, plan, cry, x),
                (cache, st, mon, stats0, sw0),
                None,
                length=cfg.segment_len,
            )
            if ring:
                cache = PG.drain_ring(cache, use_kernel=dk,
                                      shardings=cache_shardings)
            if mesh is not None:
                cache = PG.constrain(cache, cache_shardings)
                st, mon, stats, swrites, emits, ems = (
                    replicate_for_readback(
                        (st, mon, stats, swrites, emits, ems), mesh))
            return cache, st, mon, stats, swrites, emits, ems

        return jax.jit(segment_mixed)

    def _build_spec_segment(self, mode: str) -> Callable:
        """Speculative segment (the third path, DESIGN.md §11): per scan
        step every live slot's DRAFT proposes k tokens off its own dense
        lanes cache, then the target verifies all k in ONE
        ``decode_chunk_paged`` call — a k-token verify IS a chunked step
        with logits at every column. ``spec_verify`` accepts the longest
        draft prefix the target agrees with (greedy: exact argmax match,
        so greedy spec streams stay bit-identical to non-spec greedy;
        sampled: rejection sampling, distributionally exact) and commits
        a+1 tokens — the accepted run plus the target's own token at the
        first disagreement (a bonus token on full acceptance). The verify
        chunk's contiguous rows are phase-tagged PHASE_BULK, so the
        decision plane sees them exactly like prefill-chunk bulk writes
        and pins them to the offload/direct path. Rollback is free:
        stale rows past the committed position are overwritten before any
        read (positional drafts + the target's paged pool), and
        recurrent (SSM) drafts gather the snapshot matching their
        committed-token count (``kvcache.paged.select_snapshot``)."""
        model, cfg = self.model, self.cfg
        k = self.spec.k
        dmodel = self.draft_model
        positional = self._draft_positional
        ps, nb, mp = cfg.page_size, self.n_blocks, self.max_pages
        decision = self.decision
        attn = self.attention
        mesh, cache_shardings = self.mesh, self._cache_shardings

        def step(params, dpar, enabled, plan, carry, _):
            cache, dcache, st, mon, stats, sstats, swrites = carry
            active = ~st.done & enabled

            # retired/stalled slots never write their draft lanes
            def masked_writer(kc, vc, k_new, v_new, rows):
                return direct_kv_write(
                    kc, vc, k_new, v_new,
                    jnp.where(active, rows, kc.shape[1]))

            # --- draft: k proposals (+1 catch-up step so the draft's own
            # cache covers a fully-accepted round, incl. the bonus token)
            d_tok = st.token
            dkey = st.dkey
            d_toks, d_logits = [], []
            dc = dcache
            snaps = None if positional else [dc]
            for j in range(k + 1):
                dl, dc = dmodel.decode_step(
                    dpar, dc, d_tok, st.pos + j, kv_writer=masked_writer)
                if j < k:
                    d_tok, dkey = SMP.sample_tokens(
                        dl, dkey, st.sampling, mode=mode)
                    d_toks.append(d_tok)
                    d_logits.append(dl)
                if snaps is not None:
                    snaps.append(dc)
            d_tokens = jnp.stack(d_toks, axis=1)          # [S, k]
            d_log = jnp.stack(d_logits, axis=1)           # [S, k, V]

            # --- verify: ONE chunked target step over [t_cur, d_1..d_k],
            # capped to the slot's remaining budget (== its remaining
            # allocated rows: pos + remaining - 1 is the last allocated
            # row, the same footprint bound the non-spec engines write)
            chunk = jnp.concatenate([st.token[:, None], d_tokens], axis=1)
            n_valid = jnp.where(
                active, jnp.minimum(k + 1, st.remaining), 0)
            walk = read_walk(st.pos + n_valid, active, ps, mp)
            offs = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            qvalid = offs < n_valid[:, None]
            rows = st.pos[:, None] + offs
            dest = PG.logical_to_physical_many(
                cache, jnp.where(qvalid, rows, -1))
            region = jnp.minimum(dest // ps, nb - 1)
            # bulk-tagged contiguous verify rows: the decision plane pins
            # them to the offload/direct path (and its monitor heats),
            # exactly like the mixed segment's prefill chunks
            _, mon, _ = decision(
                mon,
                make_write_batch(
                    region.reshape(-1),
                    phase=jnp.full((region.size,), PHASE_BULK, jnp.int32)),
                active=qvalid.reshape(-1))
            t_logits, cache = model.decode_chunk_paged(
                params, cache, chunk, st.pos, n_valid, active,
                attention=attn, plan=plan, all_logits=True, mesh=mesh)
            commit, n_commit, n_acc, key = SMP.spec_verify(
                t_logits, d_tokens, d_log, st.key, st.sampling, mode=mode)
            n_commit = jnp.where(active, n_commit, 0)

            # --- commit: truncate the round at the slot's budget and at
            # the first stop token (the stop itself is emitted, matching
            # the non-spec engines)
            within = offs < n_commit[:, None]
            is_stop = jnp.any(
                commit[:, :, None] == st.stop[:, None, :], axis=-1)
            stops_before = (jnp.cumsum(is_stop.astype(jnp.int32), axis=1)
                            - is_stop.astype(jnp.int32))
            emit_mask = (within & (stops_before == 0)
                         & (offs < st.remaining[:, None]) & active[:, None])
            n_emit = jnp.sum(emit_mask.astype(jnp.int32), axis=1)
            remaining = st.remaining - n_emit
            ended = (remaining <= 0) | jnp.any(emit_mask & is_stop, axis=1)
            last = jnp.take_along_axis(
                commit, jnp.clip(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            nxt = jnp.where(active & (n_emit > 0), last, st.token)

            # draft rollback: positional drafts rewind by cursor (stale
            # rows are rewritten before any read); recurrent drafts
            # gather the per-slot snapshot at their committed count
            if snaps is not None:
                dc = PG.select_snapshot(PG.snapshot_stack(snaps), n_emit)

            st = st._replace(
                token=nxt,
                pos=st.pos + n_emit,
                done=st.done | (active & ended),
                remaining=remaining,
                key=key,
                dkey=dkey,
            )
            n_active = jnp.sum(active.astype(jnp.int32))
            z = jnp.zeros((), jnp.int32)
            stats = stats + jnp.concatenate([
                jnp.stack([jnp.sum(n_valid), z, z, z]), walk])
            sstats = sstats + jnp.stack([
                k * n_active,
                jnp.sum(jnp.minimum(jnp.where(active, n_acc, 0), n_emit)),
                jnp.sum(n_emit),
                n_active,
            ])
            swrites = swrites + jnp.stack([
                n_valid, jnp.zeros_like(n_valid), jnp.zeros_like(n_valid),
            ], axis=1)
            emit = jnp.where(emit_mask, commit, -1)
            return ((cache, dc, st, mon, stats, sstats, swrites),
                    (emit, emit_mask))

        def segment_spec(params, dpar, cache, dcache, st, mon, enabled):
            plan = PG.step_plan(cache)
            stats0 = jnp.zeros((N_STATS,), jnp.int32)
            sst0 = jnp.zeros((4,), jnp.int32)
            sw0 = jnp.zeros((cfg.n_slots, 3), jnp.int32)
            ((cache, dcache, st, mon, stats, sstats, swrites),
             (emits, ems)) = lax.scan(
                lambda c, x: step(params, dpar, enabled, plan, c, x),
                (cache, dcache, st, mon, stats0, sst0, sw0),
                None,
                length=cfg.segment_len,
            )
            if mesh is not None:
                cache = PG.constrain(cache, cache_shardings)
                st, mon, stats, sstats, swrites, emits, ems = (
                    replicate_for_readback(
                        (st, mon, stats, sstats, swrites, emits, ems),
                        mesh))
            return (cache, dcache, st, mon, stats, sstats, swrites,
                    emits, ems)

        return jax.jit(segment_spec)

    # ------------------------------------------------------------------
    # admission / retirement / allocation (host, between segments)
    # ------------------------------------------------------------------
    def _pages_needed(self, plen: int, max_new: int) -> int:
        # decode writes rows plen .. plen+max_new-2 (the final emitted
        # token is never consumed, so its KV is never written)
        return max(1, -(-(plen + max_new - 1) // self.cfg.page_size))

    def _segment_cover_pages(self, pos: int, prefilling: bool,
                             plen: int, max_new: int) -> int:
        """Pages covering the worst-case rows the NEXT segment can write
        for a slot at ``pos`` — THE per-chunk allocation formula, shared by
        admission (`_admission_plan`) and between-segment top-up
        (`_topup_blocks`). A prefilling slot advances up to
        ``segment_len * chunk_size`` rows (a mid-segment PREFILL→DECODE
        flip advances strictly less), a decoding slot ``segment_len`` —
        times ``k + 1`` when speculative decoding is on, since every
        draft-then-verify round can commit up to ``k + 1`` rows; both
        are capped by the footprint ``plen + max_new - 1`` (the final
        emitted token's KV is never written)."""
        cfg = self.cfg
        cap = plen + max_new - 1
        dec_rows = (self.spec.k + 1) if self._spec_enabled else 1
        adv = cfg.segment_len * (cfg.chunk_size if prefilling else dec_rows)
        rows = min(pos + adv, max(cap, plen))
        return max(1, -(-rows // cfg.page_size))

    # -- host tier: park (device->host unload) / unpark (page back in) --

    def _park(self, victim: int) -> None:
        """Unload ALL of ``victim``'s blocks to the host store and stall
        the slot: one bulk device->host gather (the staging machinery's
        PHASE_BULK lane in reverse), page-table entries cleared, pool
        references dropped. Shared/registered blocks are copied, not
        stolen — other holders keep them; the victim's restore builds
        private replacements from the host copy."""
        blocks = list(self.pool.held(victim))
        n = self._slot_pages[victim]
        host_k, host_v = PG.unload_blocks(self.cache, blocks)
        self._host_store[victim] = (n, host_k, host_v)
        self.cache["page_table"] = self.cache["page_table"].at[
            victim].set(-1)
        self.pool.free_slot(victim)
        self._slot_pages[victim] = 0
        self._parked[victim] = True
        self.stats["host_unloads"] += len(blocks)

    def _unpark(self, s: int) -> bool:
        """Page a parked slot back in: fresh blocks, one bulk
        host->device restore, page table repointed. False when the pool
        (even after reclaiming strictly-younger slots) can't cover it."""
        n, host_k, host_v = self._host_store[s]
        got = self._alloc_reclaiming(s, n, self._slot_seq[s])
        if got is None:
            return False
        self.cache = PG.load_blocks(self.cache, got, host_k, host_v)
        self.cache["page_table"] = self.cache["page_table"].at[
            s, :n].set(jnp.asarray(got))
        self._slot_pages[s] = n
        del self._host_store[s]
        self._parked[s] = False
        self.stats["host_pageins"] += n
        return True

    def _reclaim(self, need: int, requester_seq: int) -> bool:
        """Make >= ``need`` blocks available by parking live slots
        STRICTLY YOUNGER than ``requester_seq`` (youngest first). The
        seniority order is the anti-livelock invariant: the oldest live
        slot is never a victim, so it always makes progress, finishes,
        and frees its blocks for everyone behind it. ``requester_seq=-1``
        (admission) may displace any live slot."""
        if not self._host_tier:
            return False
        done = np.asarray(self.slots.done)
        victims = [s for s in range(self.cfg.n_slots)
                   if self._occupied[s] and not bool(done[s])
                   and not self._parked[s]
                   and self._slot_seq[s] > requester_seq]
        victims.sort(key=lambda s: -self._slot_seq[s])  # youngest first
        for v in victims:
            if self.pool.n_available >= need:
                break
            self._park(v)
        return self.pool.n_available >= need

    def _alloc_reclaiming(self, slot: int, n: int,
                          requester_seq: int) -> Optional[np.ndarray]:
        """``pool.alloc`` with the host-tier fallback: on failure, unload
        younger slots' blocks to the host until the request fits (or no
        eligible victim remains)."""
        got = self.pool.alloc(slot, n)
        if got is not None or not self._host_tier:
            return got
        if self._reclaim(n, requester_seq):
            return self.pool.alloc(slot, n)
        return None

    def _register_pages(self, s: int, pos_s: int) -> None:
        """Publish ``s``'s fully-written full-prompt pages under their
        content hashes (between segments: the ring is drained, so pool
        content is final). First registrant wins; the cursor is monotone
        so each page registers at most once."""
        ps = self.cfg.page_size
        limit = min(len(self._slot_hashes[s]), self._slot_plen[s] // ps)
        reach = min(min(pos_s, self._slot_plen[s]) // ps, limit)
        if reach <= self._slot_reg[s]:
            return
        held = self.pool.held(s)
        for p in range(self._slot_reg[s], reach):
            self.pool.register(s, held[p], self._slot_hashes[s][p])
        self._slot_reg[s] = reach

    @phase("engine.topup")
    def _topup_blocks(self) -> np.ndarray:
        """The between-segment memory manager: page parked slots back in,
        extend live chunked slots' page tables to cover the rows the NEXT
        segment can write, and register finished prefix pages. Slots are
        visited in admission-seniority order (oldest first) so host-tier
        reclaim composes with top-up without livelock. Returns the
        enabled mask — a slot whose blocks can't be covered stalls
        (parked or top-up-starved) for one segment instead of
        deadlocking."""
        cfg = self.cfg
        enabled = np.ones((cfg.n_slots,), bool)
        if not self._in_scan_prefill and not self._host_tier:
            return enabled
        pos = np.asarray(self.slots.pos)
        phase = np.asarray(self.slots.phase)
        done = np.asarray(self.slots.done)
        live = [s for s in range(cfg.n_slots)
                if self._occupied[s] and not bool(done[s])]
        live.sort(key=lambda s: self._slot_seq[s])  # oldest first
        for s in live:
            if self._parked[s]:
                if not self._unpark(s):
                    enabled[s] = False
                    continue
                # fall through: a restored chunked slot still needs its
                # page table extended for the rows the NEXT segment writes
            if not self._in_scan_prefill:
                continue
            if self._prefix_cache:
                self._register_pages(s, int(pos[s]))
            want = self._segment_cover_pages(
                int(pos[s]), phase[s] == PHASE_PREFILL,
                self._slot_plen[s], self._slot_max_new[s])
            have = self._slot_pages[s]
            if want > have:
                got = self._alloc_reclaiming(
                    s, want - have, self._slot_seq[s])
                if got is None:
                    enabled[s] = False
                    continue
                self.cache["page_table"] = self.cache["page_table"].at[
                    s, have:want].set(jnp.asarray(got))
                self._slot_pages[s] = want
        for s in live:
            # an older slot's reclaim may have parked a slot this loop
            # already enabled — parked slots never run (their page-table
            # rows are cleared)
            if self._parked[s]:
                enabled[s] = False
        return enabled

    def _prefill(self, prompts: jnp.ndarray, max_seq: int, media):
        """Jitted batched prefill, cached per (max_seq, media?) — jit
        re-specializes per (group size, prompt_len) shape on its own.
        Admission batches every same-length prompt into ONE prefill call;
        per-row results are bit-identical to solo prefills, so grouping is
        invisible to the decode stream."""
        key = (max_seq, media is not None)
        fn = self._prefill_fns.get(key)
        if fn is None:
            if media is None:
                fn = jax.jit(
                    lambda p, t: self.model.prefill(p, t, max_seq))
            else:
                fn = jax.jit(
                    lambda p, t, m: self.model.prefill(p, t, max_seq, media=m))
            self._prefill_fns[key] = fn
        args = (self.params, prompts) if media is None else (
            self.params, prompts, media)
        return fn(*args)

    def _chunk_prefill_host(self, prompts: jnp.ndarray, max_seq: int, media):
        """Whole-prompt prefill done in ``chunk_size``-token pieces through
        ``model.chunk_prefill`` (lanes layout under ``chunked=True``).
        Bit-identical to ``model.prefill`` — exercised across every arch by
        the config-matrix parity test. Runs eagerly: chunk boundaries are
        static Python values (ring addressing branches on them), so a jit
        per (chunk, start) pair would buy nothing at admission frequency."""
        g, plen = prompts.shape
        cache = self.model.init_cache(g, max_seq)
        # enc-dec (Whisper): the audio encoder runs on the FIRST chunk and
        # its cross-KV is reused from the cache on later ones; the VLM
        # family's gated cross layers consume media on every chunk
        media_once = hasattr(self.model, "encode")
        logits = None
        for s0 in range(0, plen, self.cfg.chunk_size):
            chunk = prompts[:, s0:s0 + self.cfg.chunk_size]
            m = None if (media_once and s0 > 0) else media
            logits, cache = self.model.chunk_prefill(
                self.params, cache, chunk, s0, media=m)
        return logits, cache

    def _sync_draft(self) -> None:
        """Bring every live slot's draft lane up to its committed
        position (host, between segments). The draft cache is DERIVED
        data: admission, preemption-resume and chunked PREFILL→DECODE
        flips just mark a slot stale (``_draft_pos = -1``) and this
        re-prefills its lane from the committed tokens — prompt plus the
        emitted prefix whose KV the target has written (``pos - plen``
        tokens; the LAST emitted token's KV is never written, same as
        the target's own footprint rule). Inside a spec segment the
        draft then stays in lock-step with ``pos`` by construction."""
        pos = np.asarray(self.slots.pos)
        done = np.asarray(self.slots.done)
        for s in range(self.cfg.n_slots):
            if (not self._occupied[s] or self._parked[s]
                    or bool(done[s])):
                continue
            p = int(pos[s])
            if self._draft_pos[s] == p:
                continue
            rid = self._slot_req[s]
            req = self._requests[rid]
            plen = self._slot_plen[s]
            toks = np.concatenate([
                np.asarray(req.prompt[:plen], np.int32),
                np.asarray(self.outputs[rid][:p - plen], np.int32)])
            # jit per committed length: recurrent drafts fold the WHOLE
            # sequence into their state, so padding to a fixed bucket
            # would corrupt it — exact-length prefills, cached per shape
            fn = self._draft_prefill_fns.get(p)
            if fn is None:
                fn = jax.jit(lambda pr, t: self.draft_model.prefill(
                    pr, t, self.cfg.max_seq))
                self._draft_prefill_fns[p] = fn
            _, lane = fn(self.draft_params,
                         jnp.asarray(toks, jnp.int32)[None, :])
            self.draft_cache = jax.tree.map(
                lambda big, small: big.at[:, s].set(small[:, 0]),
                self.draft_cache, lane)
            self._draft_pos[s] = p

    def _resolve_params(self, req) -> SamplingParams:
        """The request's effective SamplingParams: request > engine
        default > legacy ``greedy`` flag (for an unset temperature)."""
        return SMP.resolve(req.params, self.cfg.default_params,
                           self.cfg.greedy)

    def _admit_sampling(self, slot_arr, reqs, plist) -> dict:
        """Per-slot sampling-state updates for a group admission: the
        resolved param fields and each request's PRNG key (explicit seed
        or the legacy (sample_seed, req_id) derivation). Key derivation
        is ONE vmapped dispatch per admission — per-request Python
        dispatches would dominate a small reduced-model serve pass."""
        keys = jax.random.key_data(jax.vmap(
            lambda i: jax.random.fold_in(self._base_key, i)
        )(jnp.asarray([r.req_id for r in reqs], jnp.int32)))
        seeded = [(i, p.seed) for i, p in enumerate(plist)
                  if p.seed is not None]
        if seeded:
            # explicit seeds are the rare case: per-request derive_key
            # keeps ONE definition of the seed->key mapping (the common
            # unseeded path above stays a single vmapped dispatch)
            rows = jnp.asarray([i for i, _ in seeded], jnp.int32)
            skeys = jnp.stack([SMP.derive_key(self._base_key, 0, s)
                               for _, s in seeded])
            keys = keys.at[rows].set(jax.random.key_data(skeys))
        stop = np.asarray(
            [SMP.stop_table(p, self.cfg.eos_id) for p in plist], np.int32)
        st = self.slots
        out = dict(
            key=st.key.at[slot_arr].set(keys),
            temperature=st.temperature.at[slot_arr].set(jnp.asarray(
                [p.temperature for p in plist], jnp.float32)),
            top_k=st.top_k.at[slot_arr].set(jnp.asarray(
                [p.top_k for p in plist], jnp.int32)),
            top_p=st.top_p.at[slot_arr].set(jnp.asarray(
                [p.top_p for p in plist], jnp.float32)),
            stop=st.stop.at[slot_arr].set(jnp.asarray(stop)),
        )
        if self._spec_enabled:
            # the draft-proposal chain: a SEPARATE per-request derivation
            # (base key folded with a spec salt, then the request id) so
            # draft sampling never perturbs the target chain
            dbase = jax.random.fold_in(self._base_key, 0x5BEC)
            dkeys = jax.random.key_data(jax.vmap(
                lambda i: jax.random.fold_in(dbase, i)
            )(jnp.asarray([r.req_id for r in reqs], jnp.int32)))
            out["dkey"] = st.dkey.at[slot_arr].set(dkeys)
        return out

    def _now(self) -> float:
        """Current instant on the engine clock (wall ``perf_counter`` by
        default; the loadtest harness's VirtualClock in virtual mode)."""
        return self.clock()

    def _record_first_tokens(self, rids) -> None:
        now = self._now()
        if self._t_serve0 is None:
            self._t_serve0 = now
        for rid in rids:
            self.ttft.setdefault(rid, now - self._t_serve0)
            self.first_token_t.setdefault(rid, now)

    def _admission_plan(self, slot: int, req) -> Optional[dict]:
        """Reserve ``req``'s first blocks for ``slot`` — the one place
        admission touches the pool.

        Non-chunked: the whole footprint, fresh. Chunked + prefix_cache:
        walk the prompt's chained page hashes, SHARE every leading block
        already registered (refcount++, no prefill for those rows), then
        allocate fresh blocks for the rest of the first segment's cover.
        A fully-cached prompt still re-runs its LAST row (the t0 logits
        must be produced), which would write into the final shared block
        — that block is CoW'd here, before any write can reach it. On
        any shortfall every reservation is rolled back and None returned
        (the request keeps its queue position)."""
        cfg = self.cfg
        if self.pool is None:
            return {"blocks": None, "cached_rows": 0,
                    "hashes": [], "n_shared": 0}
        if not self._in_scan_prefill:
            blocks = self._alloc_reclaiming(
                slot, self._pages_needed(req.prompt_len, req.max_new), -1)
            if blocks is None:
                return None
            return {"blocks": blocks, "cached_rows": 0,
                    "hashes": [], "n_shared": 0}
        plen = req.prompt_len
        shared: List[int] = []
        hashes: List[int] = []
        if self._prefix_cache:
            hashes = PG.prefix_page_hashes(req.prompt, cfg.page_size)
            for h in hashes:
                b = self.pool.lookup(h)
                if b is None:
                    break
                shared.append(b)
        cached_rows = len(shared) * cfg.page_size
        cow_src = None
        if shared and cached_rows >= plen:
            cached_rows = plen - 1
            cow_src = shared[-1]
        first = self._segment_cover_pages(cached_rows, True, plen,
                                          req.max_new)
        blocks = list(shared)
        for b in shared:
            self.pool.share(slot, b)

        def rollback():
            for b in reversed(blocks):
                self.pool.release_block(slot, b)
            return None

        if cow_src is not None:
            new_b = self.pool.cow(slot, cow_src)
            if new_b is None:
                return rollback()
            self.cache = PG.copy_block(self.cache, cow_src, new_b)
            blocks[-1] = new_b
            self.stats["cow_copies"] += 1
        fresh_n = first - len(shared)
        if fresh_n > 0:
            got = self._alloc_reclaiming(slot, fresh_n, -1)
            if got is None:
                return rollback()
            blocks.extend(int(x) for x in got)
        self.stats["prefix_hit_rows"] += cached_rows
        return {"blocks": np.asarray(blocks, np.int32),
                "cached_rows": cached_rows, "hashes": hashes,
                "n_shared": len(shared)}

    def _admit_chunked(self, slots: List[int], reqs: List[Any],
                       plans: List[dict]) -> None:
        """Chunked (paged) admission: NO prefill — park the prompt in the
        device buffer, point the page table at the first per-chunk blocks
        (shared prefix blocks first, in page order), and hand the slot to
        the scan in PREFILL phase with its cursor past the cached rows."""
        cfg = self.cfg
        slot_arr = jnp.asarray(slots, jnp.int32)
        padded = np.zeros((len(reqs), cfg.max_seq), np.int32)
        for i, r in enumerate(reqs):
            padded[i, : r.prompt_len] = r.prompt
        self.prompts = self.prompts.at[slot_arr].set(jnp.asarray(padded))
        table = np.full((len(reqs), self.max_pages), -1, np.int32)
        for i, plan in enumerate(plans):
            table[i, : len(plan["blocks"])] = plan["blocks"]
        self.cache["page_table"] = self.cache["page_table"].at[
            slot_arr].set(jnp.asarray(table))
        plist = [self._resolve_params(r) for r in reqs]
        st = self.slots
        self.slots = st._replace(
            phase=st.phase.at[slot_arr].set(PHASE_PREFILL),
            token=st.token.at[slot_arr].set(0),
            pos=st.pos.at[slot_arr].set(jnp.asarray(
                [p["cached_rows"] for p in plans], jnp.int32)),
            done=st.done.at[slot_arr].set(False),
            remaining=st.remaining.at[slot_arr].set(
                jnp.asarray([p.max_tokens for p in plist], jnp.int32)),
            req_id=st.req_id.at[slot_arr].set(
                jnp.asarray([r.req_id for r in reqs], jnp.int32)),
            plen=st.plen.at[slot_arr].set(
                jnp.asarray([r.prompt_len for r in reqs], jnp.int32)),
            **self._admit_sampling(slot_arr, reqs, plist),
        )
        for slot, req, p, plan in zip(slots, reqs, plist, plans):
            self._occupied[slot] = True
            self._slot_req[slot] = req.req_id
            self._slot_plen[slot] = req.prompt_len
            self._slot_max_new[slot] = p.max_tokens
            self._slot_pages[slot] = len(plan["blocks"])
            self._slot_seq[slot] = self._adm_seq
            self._adm_seq += 1
            self._slot_hashes[slot] = plan["hashes"]
            self._slot_reg[slot] = plan["n_shared"]
            self._slot_priority[slot] = req.priority
            self._requests[req.req_id] = req
            self.req_arrival[req.req_id] = req.arrival_s
            self.outputs[req.req_id] = []
            self.req_params[req.req_id] = p
            self.req_writes[req.req_id] = np.zeros((3,), np.int64)
            self._draft_pos[slot] = -1
        self.stats["admitted"] += len(reqs)

    def _admit_group(self, slots: List[int], reqs: List[Any],
                     plans: List[dict]) -> None:
        """Admit a group of same-prompt-length requests with ONE batched
        prefill + ONE insert + ONE slot-state update."""
        cfg = self.cfg
        blocks = [p["blocks"] for p in plans]
        g, plen = len(reqs), reqs[0].prompt_len
        ps = cfg.page_size
        prompts = jnp.asarray(np.stack([r.prompt for r in reqs]), jnp.int32)
        media = None
        if reqs[0].media is not None:
            media = jnp.asarray(np.stack([r.media for r in reqs]))
        slot_arr = jnp.asarray(slots, jnp.int32)

        if self.layout == "paged":
            logits, pc = self._prefill(prompts, plen, media)
            cache = self.cache
            l, nbp = cache["pages_k"].shape[0], PG.pool_rows(cache)
            rows = np.arange(plen)
            phys = np.concatenate(
                [b[rows // ps] * ps + rows % ps for b in blocks])
            phys = jnp.asarray(phys, jnp.int32)
            for pk, src in (("pages_k", "k"), ("pages_v", "v")):
                flat = cache[pk].reshape((l, nbp) + cache[pk].shape[3:])
                vals = pc[src][:, :, :plen]  # [L, g, plen, H, Dh]
                flat = flat.at[:, phys].set(
                    vals.reshape((l, g * plen) + vals.shape[3:]))
                cache[pk] = flat.reshape(cache[pk].shape)
            padded = np.full((g, self.max_pages), -1, np.int32)
            for i, b in enumerate(blocks):
                padded[i, : len(b)] = b
            cache["page_table"] = cache["page_table"].at[slot_arr].set(
                jnp.asarray(padded))
            regions = np.concatenate([b[rows // ps] for b in blocks])
        else:
            if self.cfg.chunked:
                logits, pc = self._chunk_prefill_host(
                    prompts, cfg.max_seq, media)
            else:
                logits, pc = self._prefill(prompts, cfg.max_seq, media)
            self.cache = jax.tree.map(
                lambda big, small: big.at[:, slot_arr].set(small),
                self.cache, pc,
            )
            regions = np.concatenate([
                s * self.max_pages + np.arange(plen) // ps for s in slots])
        # prefill writes are dense/contiguous -> offload path; they still
        # heat the page counters (the paper's frequency monitor sees every
        # write that lands in a region)
        self.mon_state = self.decision.heat(self.mon_state, regions)

        plist = [self._resolve_params(r) for r in reqs]
        t0s = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        rem = np.asarray([p.max_tokens - 1 for p in plist], np.int32)
        stop_rows = np.asarray(
            [SMP.stop_table(p, cfg.eos_id) for p in plist], np.int32)
        done0 = (rem <= 0) | np.any(stop_rows == t0s[:, None], axis=1)
        st = self.slots
        self.slots = st._replace(
            phase=st.phase.at[slot_arr].set(PHASE_DECODE),
            token=st.token.at[slot_arr].set(jnp.asarray(t0s)),
            pos=st.pos.at[slot_arr].set(plen),
            done=st.done.at[slot_arr].set(jnp.asarray(done0)),
            remaining=st.remaining.at[slot_arr].set(jnp.asarray(rem)),
            req_id=st.req_id.at[slot_arr].set(
                jnp.asarray([r.req_id for r in reqs], jnp.int32)),
            plen=st.plen.at[slot_arr].set(plen),
            **self._admit_sampling(slot_arr, reqs, plist),
        )
        for slot, req, p, t0, b in zip(slots, reqs, plist, t0s, blocks):
            self._occupied[slot] = True
            self._slot_req[slot] = req.req_id
            self._slot_plen[slot] = req.prompt_len
            self._slot_max_new[slot] = p.max_tokens
            self._slot_pages[slot] = 0 if b is None else len(b)
            self._slot_seq[slot] = self._adm_seq
            self._adm_seq += 1
            self._slot_priority[slot] = req.priority
            self._requests[req.req_id] = req
            self.req_arrival[req.req_id] = req.arrival_s
            self.outputs[req.req_id] = [int(t0)]
            self.req_params[req.req_id] = p
            # admission-time prefill rows are bulk/offload writes
            self.req_writes[req.req_id] = np.asarray(
                [0, 0, req.prompt_len], np.int64)
            self._draft_pos[slot] = -1
        self._record_first_tokens([r.req_id for r in reqs])
        self.stats["admitted"] += g

    def _retire(self, slots: List[int]) -> None:
        for slot in slots:
            if self.pool is not None and not self._parked[slot]:
                self.pool.free_slot(slot)
            self._requests.pop(self._slot_req[slot], None)
            self._occupied[slot] = False
            self._slot_req[slot] = -1
            self._slot_plen[slot] = 0
            self._slot_max_new[slot] = 0
            self._slot_pages[slot] = 0
            self._slot_seq[slot] = -1
            self._slot_hashes[slot] = []
            self._slot_reg[slot] = 0
            self._slot_priority[slot] = 0
            self._parked[slot] = False
            self._host_store.pop(slot, None)
            self._draft_pos[slot] = -1
        if self.pool is not None and slots:
            self.cache["page_table"] = self.cache["page_table"].at[
                jnp.asarray(slots, jnp.int32)].set(-1)
        self.stats["retired"] += len(slots)

    # -- preempt-and-requeue (sched/preempt, via the host tier) --------

    def preempt_slot(self, victim: int, queue: RequestQueue) -> None:
        """Evict a RUNNING slot and put its request back at the queue
        head: KV blocks unload to the host store (the same bulk
        device->host gather as ``_park``), the slot's execution scalars —
        phase / last token / position / remaining budget / PRNG key data
        — are snapshotted host-side, and the slot retires on device. On
        resume (``_try_resume``) everything is restored verbatim into
        whichever slot is free, so the continued token stream is
        bit-identical to an unpreempted run: the saved key is re-loaded,
        never re-derived, and prefill steps never consumed key splits in
        the first place.

        Unlike ``_park`` (which stalls a slot in place, keeping it
        occupied), preemption FREES the slot for a higher-priority
        request; the victim re-enters through normal admission with its
        ORIGINAL admission seniority, so the host-tier reclaim order is
        unchanged by the detour."""
        if not self._host_tier:
            raise RuntimeError(
                "preempt_slot needs memory.host_tier (the victim's KV "
                "parks in the host store)")
        done = np.asarray(self.slots.done)
        if (not self._occupied[victim] or self._parked[victim]
                or bool(done[victim])):
            raise ValueError(
                f"slot {victim} is not a preemptible running slot")
        rid = self._slot_req[victim]
        st = self.slots
        blocks = list(self.pool.held(victim))
        host_k, host_v = PG.unload_blocks(self.cache, blocks)
        self._preempted[rid] = dict(
            n_pages=self._slot_pages[victim],
            host_k=host_k, host_v=host_v,
            phase=int(st.phase[victim]), token=int(st.token[victim]),
            pos=int(st.pos[victim]), remaining=int(st.remaining[victim]),
            key=np.asarray(st.key[victim]),
            dkey=np.asarray(st.dkey[victim]),
            seq=self._slot_seq[victim], plen=self._slot_plen[victim],
            max_new=self._slot_max_new[victim],
            hashes=self._slot_hashes[victim], reg=self._slot_reg[victim],
        )
        self.cache["page_table"] = self.cache["page_table"].at[
            victim].set(-1)
        self.pool.free_slot(victim)
        self.slots = st._replace(
            done=st.done.at[victim].set(True),
            req_id=st.req_id.at[victim].set(-1))
        self._occupied[victim] = False
        self._slot_req[victim] = -1
        self._slot_plen[victim] = 0
        self._slot_max_new[victim] = 0
        self._slot_pages[victim] = 0
        self._slot_seq[victim] = -1
        self._slot_hashes[victim] = []
        self._slot_reg[victim] = 0
        self._slot_priority[victim] = 0
        queue.requeue(self._requests[rid])
        self.stats["preemptions"] += 1
        self.stats["host_unloads"] += len(blocks)

    def _try_resume(self, slot: int, req) -> bool:
        """Restore a preempted request into ``slot``: fresh blocks, one
        bulk host->device load, slot scalars (incl. the saved PRNG key)
        written back verbatim. Sampling-param arrays are recomputed from
        the request's RESOLVED params — deterministic, so re-deriving
        them is exact. False when the pool can't cover the KV footprint
        (the request keeps its queue position)."""
        rid = req.req_id
        snap = self._preempted[rid]
        n = snap["n_pages"]
        got = self._alloc_reclaiming(slot, n, -1)
        if got is None:
            return False
        self.cache = PG.load_blocks(self.cache, got,
                                    snap["host_k"], snap["host_v"])
        self.cache["page_table"] = self.cache["page_table"].at[
            slot, :n].set(jnp.asarray(got))
        if self._in_scan_prefill:
            padded = np.zeros((self.cfg.max_seq,), np.int32)
            padded[: req.prompt_len] = req.prompt
            self.prompts = self.prompts.at[slot].set(jnp.asarray(padded))
        p = self.req_params[rid]
        stop_row = jnp.asarray(SMP.stop_table(p, self.cfg.eos_id),
                               jnp.int32)
        st = self.slots
        self.slots = st._replace(
            phase=st.phase.at[slot].set(snap["phase"]),
            token=st.token.at[slot].set(snap["token"]),
            pos=st.pos.at[slot].set(snap["pos"]),
            done=st.done.at[slot].set(False),
            remaining=st.remaining.at[slot].set(snap["remaining"]),
            key=st.key.at[slot].set(jnp.asarray(snap["key"])),
            req_id=st.req_id.at[slot].set(rid),
            plen=st.plen.at[slot].set(snap["plen"]),
            temperature=st.temperature.at[slot].set(p.temperature),
            top_k=st.top_k.at[slot].set(p.top_k),
            top_p=st.top_p.at[slot].set(p.top_p),
            stop=st.stop.at[slot].set(stop_row),
            dkey=st.dkey.at[slot].set(jnp.asarray(snap["dkey"])),
        )
        # the draft cache is DERIVED data: resume just marks the slot
        # stale and _sync_draft re-prefills it from the committed tokens
        self._draft_pos[slot] = -1
        self._occupied[slot] = True
        self._slot_req[slot] = rid
        self._slot_plen[slot] = snap["plen"]
        self._slot_max_new[slot] = snap["max_new"]
        self._slot_pages[slot] = n
        self._slot_seq[slot] = snap["seq"]  # original seniority
        self._slot_hashes[slot] = snap["hashes"]
        self._slot_reg[slot] = snap["reg"]
        self._slot_priority[slot] = req.priority
        del self._preempted[rid]
        self.stats["preempt_resumes"] += 1
        self.stats["host_pageins"] += n
        return True

    def _maybe_preempt(self, queue: RequestQueue) -> None:
        """The sched/preempt trigger, run before each admission scan:
        while every slot is busy and some WAITING request outranks the
        lowest-priority running slot STRICTLY (strictness is the
        anti-ping-pong guard: equal priorities never displace each
        other), evict that slot. One eviction frees one slot, so at most
        one victim per admission pass — the freed slot is immediately
        filled by the priority-ordered scan below."""
        if not getattr(self._sched, "preempts", False) or len(queue) == 0:
            return
        if any(not o for o in self._occupied):
            return
        done = np.asarray(self.slots.done)
        running = [s for s in range(self.cfg.n_slots)
                   if self._occupied[s] and not self._parked[s]
                   and not bool(done[s])]
        if not running:
            return
        victim = min(running, key=lambda s: (self._slot_priority[s],
                                             -self._slot_seq[s]))
        wait_pri = max(queue.at(i).priority for i in range(len(queue)))
        if wait_pri > self._slot_priority[victim]:
            self.preempt_slot(victim, queue)

    @phase("engine.admit")
    def admit(self, queue: RequestQueue) -> int:
        """Admit waiting requests into free slots, scanning the queue in
        the admission policy's order (``cfg.sched``; FIFO = submission
        order, the default). A request whose blocks can't be covered
        RIGHT NOW is skipped in favor of later ones in scan order that
        fit — it keeps its queue position and is admitted once blocks
        free up (completion-order fairness without head-of-line
        blocking). Skip-ahead is BOUNDED: after the SCAN-ORDER head
        request has been passed over ``skip_ahead_limit`` admission
        rounds in a row, scanning stops at it so freed blocks accumulate
        for it instead of being siphoned by a steady stream of smaller
        requests (starvation guard). Preempted requests re-entering the
        queue resume their saved state instead of re-planning admission.
        Same-prompt-length requests admitted together share one batched
        prefill. Returns #admitted."""
        self._maybe_preempt(queue)
        picks: List[tuple] = []  # (slot, req, plan)
        free = [s for s in range(self.cfg.n_slots) if not self._occupied[s]]
        order = self._sched.order(queue)
        popped: List[int] = []  # original scan indices already admitted
        n_resumed = 0
        for k, oi in enumerate(order):
            if not free:
                break
            # translate the pass-start index to the queue's current
            # position: every already-popped earlier index shifts it left
            qi = oi - sum(1 for p in popped if p < oi)
            req = queue.at(qi)
            if req.prompt_len + req.max_new > self.cfg.max_seq:
                raise ValueError(
                    f"request {req.req_id}: prompt_len+max_new "
                    f"{req.prompt_len + req.max_new} > max_seq {self.cfg.max_seq}"
                )
            if self.pool is not None:
                total = self._pages_needed(req.prompt_len, req.max_new)
                if total > self.pool.n_blocks:
                    raise ValueError(
                        f"request {req.req_id} needs {total} blocks; "
                        f"pool holds {self.pool.n_blocks}")
            if req.req_id in self._preempted:
                if self._try_resume(free[0], req):
                    free.pop(0)
                    queue.pop_at(qi)
                    popped.append(oi)
                    n_resumed += 1
                    if k == 0:
                        self._skip_rid, self._skip_count = -1, 0
                    continue
                plan = None
            else:
                plan = self._admission_plan(free[0], req)
            if plan is None:
                if k == 0:
                    if self._skip_rid != req.req_id:
                        self._skip_rid, self._skip_count = req.req_id, 0
                    self._skip_count += 1
                    if self._skip_count > self._skip_limit:
                        break  # head reserves every block freed from here on
                continue  # doesn't fit now: let later requests try
            if k == 0:
                self._skip_rid, self._skip_count = -1, 0
            picks.append((free.pop(0), queue.pop_at(qi), plan))
            popped.append(oi)
        now = self._now()
        for _, req, _ in picks:
            self.req_admit.setdefault(req.req_id, now)
        if self._in_scan_prefill:
            if picks:
                self._admit_chunked([p[0] for p in picks],
                                    [p[1] for p in picks],
                                    [p[2] for p in picks])
        else:
            # group same-length prompts into one prefill dispatch each
            groups: Dict[int, List[tuple]] = {}
            for p in picks:
                groups.setdefault(p[1].prompt_len, []).append(p)
            for members in groups.values():
                self._admit_group([m[0] for m in members],
                                  [m[1] for m in members],
                                  [m[2] for m in members])
        return len(picks) + n_resumed

    # ------------------------------------------------------------------
    # the serve loop
    # ------------------------------------------------------------------
    def _mixed_phase_pending(self) -> bool:
        """Does the NEXT segment need the mixed-phase step? Only when a
        live slot is still prefilling — phases only flip PREFILL→DECODE
        inside a segment, so a pure-decode start stays pure."""
        if not self._in_scan_prefill:
            return False
        phase = np.asarray(self.slots.phase)
        done = np.asarray(self.slots.done)
        return bool(np.any(~done & (phase == PHASE_PREFILL)
                           & np.asarray(self._occupied)))

    def run_segment(self, enabled: Optional[np.ndarray] = None) -> np.ndarray:
        """One jitted scan segment + ONE host readback. Returns the bool
        [segment_len, n_slots] emission matrix (which steps emitted).
        ``enabled`` (bool[n_slots], optional) stalls slots whose per-chunk
        block top-up failed. Appends the segment's record to
        ``segment_log``."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            kind, out = self._dispatch(enabled)
        with jax.profiler.TraceAnnotation("engine.readback"):
            acts = self._readback(kind, *out)
        self.segment_log.record(self.stats["segments"], kind,
                                time.perf_counter() - t0)
        return acts

    def _dispatch(self, enabled: Optional[np.ndarray]):
        """Enqueue the segment program the slots need: (its kind, its
        device outputs other than the new engine state)."""
        if enabled is None:
            enabled = np.ones((self.cfg.n_slots,), bool)
        enabled_j = jnp.asarray(enabled)
        # static sampler specialization: the cheapest variant covering
        # the OCCUPANTS' params (a slot forced into a richer variant than
        # its own params need produces identical tokens — the variants
        # differ only in traced work, never in results)
        mode = SMP.required_mode(
            [self.req_params[self._slot_req[s]]
             for s in range(self.cfg.n_slots) if self._occupied[s]])
        if self._mixed_phase_pending():
            self._mixed_fn = self._mixed_fns.get(mode)
            if self._mixed_fn is None:
                self._mixed_fn = self._build_mixed_segment(mode)
                self._mixed_fns[mode] = self._mixed_fn
            (self.cache, self.slots, self.mon_state, stats, swrites,
             emits, acts) = (
                self._mixed_fn(self.params, self.cache, self.slots,
                               self.mon_state, self.prompts, enabled_j))
            return "mixed", (stats, None, swrites, emits, acts)
        if self._spec_enabled:
            # the third path: draft-then-verify rounds need every live
            # slot in DECODE phase and the draft lanes in sync
            self._sync_draft()
            self._spec_fn = self._spec_fns.get(mode)
            if self._spec_fn is None:
                self._spec_fn = self._build_spec_segment(mode)
                self._spec_fns[mode] = self._spec_fn
            (self.cache, self.draft_cache, self.slots, self.mon_state,
             stats, sstats, swrites, emits, acts) = (
                self._spec_fn(self.params, self.draft_params, self.cache,
                              self.draft_cache, self.slots,
                              self.mon_state, enabled_j))
            return "spec", (stats, sstats, swrites, emits, acts)
        self._segment_fn = self._segment_fns.get(mode)
        if self._segment_fn is None:
            self._segment_fn = self._build_segment(mode)
            self._segment_fns[mode] = self._segment_fn
        (self.cache, self.slots, self.mon_state, stats, swrites,
         emits, acts) = (
            self._segment_fn(self.params, self.cache, self.slots,
                             self.mon_state, enabled_j))
        return "decode", (stats, None, swrites, emits, acts)

    def _readback(self, kind: str, stats, sstats, swrites, emits,
                  acts) -> np.ndarray:
        """Bring one segment's outputs to the host (the host waits on the
        device here) and fold them into the counters and the outputs."""
        spec_ran = kind == "spec"
        emits, acts = np.asarray(emits), np.asarray(acts)
        swrites = np.asarray(swrites)
        d, s, dr, pf, walked, live = (int(x) for x in stats)
        self.stats["direct_writes"] += d
        self.stats["staged_writes"] += s
        self.stats["drains"] += dr
        self.stats["prefill_writes"] += pf
        self.stats["read_pages_walked"] += walked
        self.stats["read_pages_live"] += live
        self.stats["segments"] += 1
        if spec_ran:
            sp, sa, sc, sr = (int(x) for x in sstats)
            self.stats["spec_proposed"] += sp
            self.stats["spec_accepted"] += sa
            self.stats["spec_committed"] += sc
            self.stats["spec_rounds"] += sr
            committed = sc
        else:
            # non-spec segments commit exactly one token per emission
            committed = int(acts.sum())
        if self.time_model is not None:
            # virtual-time mode: the segment "took" what the cost model
            # says its write mix costs — advance BEFORE stamping first
            # tokens, so TTFT includes the segment that produced them
            self.clock.advance(self.time_model(
                {"direct": d, "staged": s, "drains": dr, "prefill": pf,
                 "committed": committed}))
        first = []
        for slot in range(self.cfg.n_slots):
            if self._occupied[slot]:
                rid = self._slot_req[slot]
                self.req_writes[rid] += swrites[slot]
                if spec_ran:
                    # [T, S, K+1] multi-token emission: step-major, then
                    # commit order within the round
                    toks = emits[:, slot][acts[:, slot]]
                else:
                    toks = emits[acts[:, slot], slot]
                if len(toks):
                    if not self.outputs[rid]:
                        first.append(rid)
                    self.outputs[rid].extend(int(t) for t in toks)
        if first:
            self._record_first_tokens(first)
        if spec_ran:
            # the in-scan draft stayed in lock-step with pos; record it
            pos = np.asarray(self.slots.pos)
            for slot in range(self.cfg.n_slots):
                if self._occupied[slot] and not self._parked[slot]:
                    self._draft_pos[slot] = int(pos[slot])
            return acts.any(axis=2)
        return acts

    @phase("engine.retire")
    def retire_done(self) -> int:
        """Free every occupied-but-done slot (host, between segments)."""
        done = np.asarray(self.slots.done)
        retiring = [s for s in range(self.cfg.n_slots)
                    if self._occupied[s] and bool(done[s])]
        self._retire(retiring)
        return len(retiring)

    def serve(self, queue: RequestQueue,
              max_segments: int = 100_000) -> Dict[int, np.ndarray]:
        """Drain the queue to completion: admit / scan a segment / collect /
        retire, until no request is live. Returns {req_id: tokens}."""
        if self._t_serve0 is None:
            self._t_serve0 = self._now()
        for _ in range(max_segments):
            self.retire_done()
            self.admit(queue)
            if not any(self._occupied):
                # admit() marks every admitted slot occupied, so an empty
                # engine here means nothing was admittable
                if len(queue) == 0:
                    break
                raise RuntimeError(
                    "queue head unadmittable with an empty engine "
                    "(request larger than pool capacity?)")
            # all-done slot arrays would make the segment a no-op: only
            # scan when at least one slot is live
            live = ~np.asarray(self.slots.done) & np.asarray(self._occupied)
            if not live.any():
                continue
            enabled = self._topup_blocks()
            if not (live & enabled).any():
                raise RuntimeError(
                    "every live slot stalled on block top-up: the pool is "
                    "too small for the admitted working set")
            self.run_segment(enabled)
        else:
            raise RuntimeError(f"serve() exceeded {max_segments} segments")
        return {rid: np.asarray(t, np.int32) for rid, t in self.outputs.items()}
