"""Write-path registry: the paper's two-path contract as a pluggable API.

The paper's core requirement is that the offload (direct RDMA scatter)
and unload (staging ring + local copy) paths stay *interchangeable and
compatible* behind one decision plane. This module formalizes that
contract: a :class:`WritePath` declares, by name, HOW writes reach memory
(``uses_ring``: straight scatter vs staging-ring overlay with bulk
drains) and WHICH routing decisions it can absorb (``capabilities``), and
engines are configured from ``(path="adaptive", policy="hysteresis")``
strings resolved through the registry — so a new backend is a
registration, not an engine fork.

Capabilities
------------
``direct``    the path can land a scattered write straight at its final
              destination (the offload/RNIC path).
``staged``    the path can absorb a write into the staging ring and drain
              it later (the unload path; implies drain machinery).
``bulk-pin``  bulk/contiguous (prefill-phase) writes can be pinned to the
              direct path even while scattered traffic stages — required
              for chunked prefill, where the decision plane tags
              PHASE_BULK writes.

Negotiation (:func:`negotiate`) errors loudly on incompatible combos:
a policy that may emit "unload" needs a ``staged``-capable path, a policy
that may emit "offload" needs ``direct`` support (``bulk-pin`` covers
only phase-tagged bulk writes), the dense-lane KV layout only takes
pure-direct paths, and chunked prefill needs ``bulk-pin``.

Built-ins mirror the legacy ``write_mode`` strings: ``direct`` /
``staged`` / ``adaptive`` — old configs keep meaning the same thing.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple, Union

from ..distributed.sharding import ParallelConfig
from .decision import DecisionModule
from .monitor import ExactMonitor
from .policy import get_policy_factory

CAP_DIRECT = "direct"
CAP_STAGED = "staged"
CAP_BULK_PIN = "bulk-pin"
_KNOWN_CAPS = frozenset({CAP_DIRECT, CAP_STAGED, CAP_BULK_PIN})


@dataclasses.dataclass(frozen=True)
class WritePath:
    """A named KV/memory write mechanism and its negotiation surface.

    name            registry key (and the engine config string).
    capabilities    subset of {direct, staged, bulk-pin} — the decisions
                    this path can absorb.
    uses_ring       True = writes may ride the staging-ring overlay and
                    the engine must run drain machinery (full-ring,
                    conflict-forced, and segment-boundary drains).
    default_policy  RoutingPolicy name paired with this path when the
                    caller names no policy.
    description     one-liner for error messages / docs.
    """

    name: str
    capabilities: frozenset
    uses_ring: bool
    default_policy: str
    description: str = ""

    def __post_init__(self):
        unknown = set(self.capabilities) - _KNOWN_CAPS
        if unknown:
            raise ValueError(
                f"write path {self.name!r}: unknown capabilities "
                f"{sorted(unknown)} (known: {sorted(_KNOWN_CAPS)})")
        if CAP_STAGED in self.capabilities and not self.uses_ring:
            raise ValueError(
                f"write path {self.name!r}: the 'staged' capability "
                f"requires uses_ring=True (staged writes need the ring "
                f"overlay + drain machinery)")

    def supports(self, cap: str) -> bool:
        return cap in self.capabilities

    def __repr__(self) -> str:
        # deterministic (sorted) capability order: this repr lands in
        # error messages and the committed public-API snapshot
        caps = ", ".join(sorted(self.capabilities))
        return (f"WritePath(name={self.name!r}, capabilities={{{caps}}}, "
                f"uses_ring={self.uses_ring}, "
                f"default_policy={self.default_policy!r})")


_PATHS: Dict[str, WritePath] = {}


def register_path(path: WritePath, *, overwrite: bool = False) -> WritePath:
    """Register a write path by its name. Third-party paths registered
    here are constructible from ``path="..."`` strings in every engine
    config (the registry IS the extension point)."""
    if path.name in _PATHS and not overwrite:
        raise ValueError(
            f"write path {path.name!r} already registered "
            f"(pass overwrite=True to replace it)")
    _PATHS[path.name] = path
    return path


def get_path(name: Union[str, WritePath]) -> WritePath:
    if isinstance(name, WritePath):
        return name
    try:
        return _PATHS[name]
    except KeyError:
        raise ValueError(
            f"unknown write path {name!r}; registered paths: "
            f"{sorted(_PATHS)}") from None


def available_paths() -> Tuple[str, ...]:
    return tuple(sorted(_PATHS))


DIRECT = register_path(WritePath(
    name="direct",
    capabilities=frozenset({CAP_DIRECT, CAP_BULK_PIN}),
    uses_ring=False,
    default_policy="always-offload",
    description="per-write scatter straight to the destination "
                "(the offload/RNIC path)",
))

STAGED = register_path(WritePath(
    name="staged",
    capabilities=frozenset({CAP_STAGED, CAP_BULK_PIN}),
    uses_ring=True,
    default_policy="always-unload",
    description="staging-ring append + bulk drain for every scattered "
                "write (the unload path)",
))

ADAPTIVE = register_path(WritePath(
    name="adaptive",
    capabilities=frozenset({CAP_DIRECT, CAP_STAGED, CAP_BULK_PIN}),
    uses_ring=True,
    default_policy="frequency",
    description="per-write routing between direct scatter and the "
                "staging ring (the paper's composite)",
))


def negotiate(path: WritePath, policy, *, layout: Optional[str] = None,
              chunked: bool = False) -> None:
    """Validate a (path, policy, layout, scheduling) combination.

    Raises ``ValueError`` with the full incompatibility story — which
    capability is missing and what would need to change — instead of
    letting an unsupported combination mis-route writes at runtime.
    """
    emits = getattr(policy, "emits", frozenset({"offload", "unload"}))
    pname = getattr(policy, "name", type(policy).__name__)
    if "unload" in emits and not path.supports(CAP_STAGED):
        raise ValueError(
            f"policy {pname} can route writes to the unload path, but "
            f"write path {path.name!r} lacks the 'staged' capability "
            f"(capabilities: {sorted(path.capabilities)}); pick a "
            f"staged-capable path or an offload-only policy")
    if "offload" in emits and not path.supports(CAP_DIRECT):
        raise ValueError(
            f"policy {pname} can keep scattered writes on the offload "
            f"path, but write path {path.name!r} lacks the 'direct' "
            f"capability (capabilities: {sorted(path.capabilities)}; "
            f"'bulk-pin' covers only phase-tagged bulk writes); pick a "
            f"direct-capable path or an unload-only policy")
    if layout == "lanes" and path.supports(CAP_STAGED):
        raise ValueError(
            f"kv_layout='lanes' is direct-only (per-slot cache lanes "
            f"have no ring overlay), but write path {path.name!r} "
            f"carries the 'staged' capability; use path='direct' or the "
            f"paged layout")
    if chunked and not path.supports(CAP_BULK_PIN):
        raise ValueError(
            f"chunked prefill tags bulk writes for offload-path pinning, "
            f"but write path {path.name!r} lacks the 'bulk-pin' "
            f"capability (capabilities: {sorted(path.capabilities)})")


def negotiate_memory(*, layout: Optional[str] = None, chunked: bool = False,
                     prefix_cache: bool = False,
                     host_tier: bool = False) -> None:
    """Validate the memory-capacity feature set against the KV layout,
    mirroring :func:`negotiate`'s loud-error contract.

    ``prefix_cache`` (refcounted shared-prefix block reuse) needs the
    paged layout — sharing is a page-table indirection, dense lanes have
    none — AND chunked prefill, because a cache hit is consumed by
    starting the in-scan chunk cursor past the cached rows (the blocking
    engine's admission-time prefill has no partial-prompt entry point).
    ``host_tier`` (unloading cold blocks device->host and paging them
    back on demand) likewise needs the paged layout: parking a slot swaps
    page-table entries, not cache lanes.
    """
    if prefix_cache and layout != "paged":
        raise ValueError(
            f"prefix_cache shares physical blocks through the page table, "
            f"but kv_layout={layout!r} has none; use the paged layout "
            f"(dense non-SWA DecoderLM family)")
    if prefix_cache and not chunked:
        raise ValueError(
            "prefix_cache needs chunked=True: a cache hit starts the "
            "in-scan prefill cursor past the cached rows, and only the "
            "mixed-phase scheduler can enter a prompt mid-way")
    if host_tier and layout != "paged":
        raise ValueError(
            f"host_tier parks and restores physical blocks through the "
            f"page table, but kv_layout={layout!r} has none; use the "
            f"paged layout")


ATTN_FUSED = "fused"
ATTN_REFERENCE = "reference"
_KNOWN_ATTN = ("auto", ATTN_FUSED, ATTN_REFERENCE)


def resolve_attention(attention: str = "auto", *,
                      layout: Optional[str] = None,
                      arch_paged_capable: bool = True,
                      backend: Optional[str] = None) -> str:
    """Negotiate the decode-attention implementation, mirroring
    :func:`negotiate`'s loud-error contract.

    ``fused`` is the ``flash_decode_paged`` read kernel: a scalar-prefetch
    page-table walk over the physical pool with the staging ring as a
    second softmax source. It REQUIRES the paged layout (the dense-lane
    layout has no page table to walk) — requesting it elsewhere is a
    config error, not a silent fallback. ``auto`` picks fused wherever the
    kernel compiles natively (any non-CPU backend serving a paged cache)
    and the reference jnp path on CPU, where interpret mode is the
    validation lane, not a serving path. CI sets ``REPRO_ATTENTION=fused``
    to force the kernel (interpret mode) through ``auto`` configs so CPU
    jobs exercise the fused read path end to end.
    """
    if attention not in _KNOWN_ATTN:
        raise ValueError(
            f"unknown attention implementation {attention!r} "
            f"(known: {list(_KNOWN_ATTN)})")
    paged = layout == "paged" and arch_paged_capable
    if attention == ATTN_FUSED and not paged:
        raise ValueError(
            f"attention='fused' needs the paged KV layout to walk "
            f"(layout={layout!r}, paged-capable={arch_paged_capable}); "
            f"use kv_layout='paged' on a dense decoder arch, or "
            f"attention='reference'")
    if attention != "auto":
        return attention
    if not paged:
        return ATTN_REFERENCE
    env = os.environ.get("REPRO_ATTENTION")
    if env is not None:
        return resolve_attention(env, layout=layout,
                                 arch_paged_capable=arch_paged_capable,
                                 backend=backend)
    if backend is None:
        import jax

        backend = jax.default_backend()
    return ATTN_FUSED if backend != "cpu" else ATTN_REFERENCE


def resolve_drain_kernel(drain_kernel: Optional[bool] = None, *,
                         backend: Optional[str] = None) -> bool:
    """Whether ``kvcache.paged.drain_ring`` runs the ``staged_scatter``
    kernel. The paged pool always meets the kernel's preconditions
    (full-row entries, drain-unique destinations), so ``None`` selects it
    wherever it is the fast path: any backend but the CPU. There the jnp
    scatter is faster; ``REPRO_DRAIN_KERNEL=1`` forces the kernel anyway
    (interpret mode) so CPU CI serves through the real drain kernel, and
    ``REPRO_DRAIN_KERNEL=0`` forces the jnp scatter everywhere."""
    if drain_kernel is not None:
        return drain_kernel
    env = os.environ.get("REPRO_DRAIN_KERNEL")
    if env is not None:
        return env not in ("", "0")
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend != "cpu"


# ---------------------------------------------------------------------------
# grouped sub-configs + the single validate() entry point (DESIGN.md §9)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MemoryConfig:
    """Memory-capacity feature group (the old flat ``prefix_cache`` /
    ``host_tier`` / ``skip_ahead_limit`` engine kwargs, which remain as
    one-release deprecation shims). Semantics are unchanged — see
    :func:`negotiate_memory` and DESIGN.md §8."""

    prefix_cache: bool = False
    host_tier: bool = False
    skip_ahead_limit: int = 4

    def __post_init__(self):
        if self.skip_ahead_limit < 0:
            raise ValueError(
                f"MemoryConfig.skip_ahead_limit must be >= 0, got "
                f"{self.skip_ahead_limit}")


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    """Read-side implementation group: ``impl`` is the old flat
    ``attention`` string (``auto`` | ``fused`` | ``reference``, resolved
    through :func:`resolve_attention`), ``drain_kernel`` the old flat
    drain-kernel tri-state (``None`` = auto-select, see
    :func:`resolve_drain_kernel`)."""

    impl: str = "auto"
    drain_kernel: Optional[bool] = None

    def __post_init__(self):
        if self.impl not in _KNOWN_ATTN:
            raise ValueError(
                f"unknown attention implementation {self.impl!r} "
                f"(known: {list(_KNOWN_ATTN)})")


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding group (the third execution path, DESIGN.md
    §11): a small draft model proposes ``k`` tokens per live slot inside
    the scan and the target verifies all of them in ONE
    ``decode_chunk_paged`` call, committing the accepted prefix plus one
    target-sampled token.

    ``draft_arch`` names the proposer from the config registry
    (``repro.configs.ARCHS``); ``None`` means self-draft — the target
    model drafts for itself (acceptance ≈ 1 by construction; the
    mechanics/upper-bound arm the benchmarks pin). The draft must share
    the target's vocabulary — :func:`resolve_draft` matches the target's
    ACTUAL vocab against the draft's full and reduced configs and
    :func:`validate` rejects when neither fits."""

    draft_arch: Optional[str] = None
    k: int = 4
    enabled: bool = False


# draft cache families the engine can roll back on rejection: dense
# (positional KV rows — rewinding the write cursor IS the rollback) and
# pure-SSM (no positions; tiny recurrent state snapshotted per draft
# step). Mixed/positional-plus-recurrent families have neither property.
_DRAFT_POSITIONAL = ("dense", "moe")
_DRAFT_SNAPSHOT = ("ssm",)


def resolve_draft(spec: SpecConfig, *, vocab: Optional[int] = None,
                  arch_name: Optional[str] = None):
    """Resolve ``spec.draft_arch`` to the concrete draft ``ModelConfig``
    whose vocab matches the target's actual ``vocab`` (full config first,
    then its reduced smoke variant — a reduced target pairs with a
    reduced draft). Raises ``ValueError`` on a vocab mismatch or an
    unsupported (non-rollbackable) draft family."""
    from ..configs import get_config

    name = spec.draft_arch or arch_name
    if name is None:
        raise ValueError(
            "SpecConfig.draft_arch is unset and the target arch is "
            "unknown; name a draft model from repro.configs.ARCHS")
    # self-draft against a reduced() target: the smoke variant is not a
    # registry entry of its own, so resolve through its base arch (the
    # vocab match below then picks the reduced candidate back out)
    if name.endswith("-smoke"):
        name = name[: -len("-smoke")]
    full = get_config(name)
    cands = (full, full.reduced())
    dcfg = next((c for c in cands if vocab is None or c.vocab == vocab),
                None)
    if dcfg is None:
        raise ValueError(
            f"speculative draft {name!r} shares no vocabulary with the "
            f"target (target vocab {vocab}; draft full={full.vocab}, "
            f"reduced={full.reduced().vocab}) — verify compares token ids "
            f"directly, so draft and target need one tokenizer; pick a "
            f"same-vocab draft_arch or SpecConfig(draft_arch=None) for "
            f"self-draft")
    if dcfg.family not in _DRAFT_POSITIONAL + _DRAFT_SNAPSHOT:
        raise ValueError(
            f"speculative draft {name!r} (family {dcfg.family!r}) mixes "
            f"positional and recurrent cache state, which the engine "
            f"cannot roll back on rejection; supported draft families: "
            f"dense/moe (positional rewind) and ssm (state snapshot)")
    # served like the target: parameters stored in the compute dtype
    # (``serve.api.build_model_and_params``)
    return dataclasses.replace(dcfg, param_dtype=dcfg.dtype)


@dataclasses.dataclass(frozen=True, eq=False)
class ResolvedPlan:
    """Everything an engine needs, resolved and negotiated ONCE by
    :func:`validate` — engines consume only this, never the raw config.

    layout        "paged" | "lanes" (the ``auto`` resolution applied)
    attention     "fused" | "reference" (the ``auto`` resolution applied)
    drain_kernel  whether ``kvcache.paged.drain_ring`` runs the
                  ``staged_scatter`` kernel (``None`` in the config is
                  resolved here by :func:`resolve_drain_kernel`)
    path/decision the negotiated WritePath + assembled DecisionModule
    sched         the resolved AdmissionPolicy instance (admission-order
                  plane; ``serve.traffic.sched`` registry)
    memory        the (normalized) MemoryConfig group
    parallel/mesh the (normalized) ParallelConfig and its concrete Mesh
                  (``None`` = single-device; see DESIGN.md §9)
    n_blocks / max_pages / n_regions
                  the derived pool geometry (region universe = physical
                  blocks for paged, per-slot pages for lanes)
    spec / draft_cfg
                  the (normalized) SpecConfig group and, when enabled,
                  the vocab-matched draft ``ModelConfig`` the scheduler
                  builds its proposer from (``None`` when disabled)
    """

    layout: str
    attention: str
    drain_kernel: bool
    path: WritePath
    decision: DecisionModule
    sched: Any
    memory: MemoryConfig
    parallel: ParallelConfig
    mesh: Optional[Any]
    n_blocks: int
    max_pages: int
    n_regions: int
    spec: SpecConfig = dataclasses.field(default_factory=SpecConfig)
    draft_cfg: Optional[Any] = None


def normalize_groups(cfg) -> None:
    """Shared ``EngineConfig``/``BatchConfig`` ``__post_init__``
    normalizer: default the grouped sub-configs (``memory`` /
    ``attention`` / ``parallel``) and coerce an ``attention`` string to
    ``AttentionConfig(impl=...)``. The PR-7 flat-kwarg deprecation shims
    (``fold_flat_kwargs`` + the ``<unset>`` sentinel) lived here for
    their one-release window and are gone — flat spellings now fail
    like any unknown kwarg."""
    cfg.memory = getattr(cfg, "memory", None) or MemoryConfig()
    a = getattr(cfg, "attention", None)
    cfg.attention = a if isinstance(a, AttentionConfig) else AttentionConfig(
        impl=a or "auto")
    cfg.parallel = getattr(cfg, "parallel", None) or ParallelConfig()
    cfg.spec = getattr(cfg, "spec", None) or SpecConfig()


def _memory_of(cfg) -> MemoryConfig:
    m = getattr(cfg, "memory", None)
    if isinstance(m, MemoryConfig):
        return m
    sal = getattr(cfg, "skip_ahead_limit", None)
    return MemoryConfig(
        prefix_cache=bool(getattr(cfg, "prefix_cache", False) or False),
        host_tier=bool(getattr(cfg, "host_tier", False) or False),
        skip_ahead_limit=4 if sal is None else int(sal),
    )


def _attention_of(cfg) -> AttentionConfig:
    a = getattr(cfg, "attention", "auto")
    if isinstance(a, AttentionConfig):
        return a
    return AttentionConfig(impl=a or "auto",
                           drain_kernel=getattr(cfg, "drain_kernel", None))


def validate(cfg, *, paged_capable: bool = True,
             n_heads: Optional[int] = None,
             n_kv_heads: Optional[int] = None,
             backend: Optional[str] = None,
             arch_name: Optional[str] = None,
             vocab: Optional[int] = None) -> ResolvedPlan:
    """THE config -> plan entry point: one call, one loud error surface.

    Subsumes what engines used to stitch together from :func:`negotiate`
    (via :func:`build_decision`), :func:`negotiate_memory`, and
    :func:`resolve_attention`, and adds the mesh interactions those
    pre-sharding helpers never saw:

    * **mesh x lanes layout** — rejected: TP shards the PAGED pool's head
      axis; the lanes layout serves the model's own cache pytree, whose
      sharding belongs to ``distributed.sharding.cache_pspec``, not the
      serve tier.
    * **fused attention x head divisibility** — rejected when the model
      axis does not divide both Hq and Hkv: the fused kernel's grid walks
      (slot, q-head, page) per shard, so a head shard must hold whole
      q-head GROUPS (the "heads" scheme in ``attention_scheme``).
    * **mesh x host tier** — the host store is single-process; parking
      gathers per-shard slabs with ``np.asarray`` and that requires every
      mesh device to be process-addressable. Rejected on multi-process
      meshes with the fix spelled out.

    ``cfg`` is duck-typed over ``EngineConfig`` / ``BatchConfig`` (both
    normalize their grouped sub-configs in ``__post_init__``); model-side
    facts (``paged_capable``, head counts, ``arch_name``) are passed by
    the engine that owns the model. Raises ``ValueError`` with an
    actionable message on every incompatible combination; returns the
    :class:`ResolvedPlan` the engine serves from.
    """
    layout = getattr(cfg, "kv_layout", "auto") or "auto"
    if layout not in ("auto", "paged", "lanes"):
        raise ValueError(
            f"unknown kv_layout {layout!r} (known: auto | paged | lanes)")
    if layout == "auto":
        layout = "paged" if paged_capable else "lanes"
    if layout == "paged" and not paged_capable:
        raise ValueError(
            f"paged KV serves the linear-addressed dense family; "
            f"{arch_name or 'this model'} needs kv_layout='lanes'")
    chunked = bool(getattr(cfg, "chunked", False))
    if chunked and getattr(cfg, "chunk_size", 1) < 1:
        raise ValueError("chunk_size must be >= 1")

    memory = _memory_of(cfg)
    attn_cfg = _attention_of(cfg)
    parallel = getattr(cfg, "parallel", None) or ParallelConfig()

    if parallel.enabled and layout != "paged":
        raise ValueError(
            f"mesh-sharded serving (ParallelConfig mesh "
            f"{parallel.mesh_shape}x{parallel.axis_names}) shards the "
            f"paged pool's head axis, but kv_layout={layout!r} serves the "
            f"model's own cache lanes; use kv_layout='paged' (dense "
            f"non-SWA DecoderLM family) or the default single-device "
            f"ParallelConfig()")
    negotiate_memory(layout=layout, chunked=chunked,
                     prefix_cache=memory.prefix_cache,
                     host_tier=memory.host_tier)
    attention = resolve_attention(attn_cfg.impl, layout=layout,
                                  arch_paged_capable=paged_capable,
                                  backend=backend)
    tp = parallel.tp
    if attention == ATTN_FUSED and tp > 1 and (
            n_heads is not None and n_kv_heads is not None) and (
            n_heads % tp or n_kv_heads % tp):
        raise ValueError(
            f"attention='fused' under a model-axis of {tp} needs whole "
            f"head groups per shard, but {arch_name or 'this model'} has "
            f"Hq={n_heads}, Hkv={n_kv_heads} (both must divide by {tp}); "
            f"use attention='reference', a divisible mesh, or a "
            f"divisible-head arch")
    mesh = parallel.build_mesh()  # raises with the XLA_FLAGS hint when
    if memory.host_tier and mesh is not None:    # devices are missing
        import jax

        if any(d.process_index != jax.process_index()
               for d in mesh.devices.flat):
            raise ValueError(
                "host_tier gathers per-shard block slabs into a "
                "single-process host store, but the mesh spans devices "
                "of other processes; park/unpark needs a fully "
                "process-addressable mesh (single-host TP) or "
                "host_tier=False")

    # admission-order plane: resolve the sched name through the traffic
    # registry (function-level import — core must not import serve at
    # module scope) and negotiate its capability needs. Preemption parks
    # victim KV through the host tier, so sched/preempt without
    # memory.host_tier (which itself implies the paged layout) is an
    # illegal combo and fails here, loudly, at config time.
    from ..serve.traffic.sched import build_sched

    sched = build_sched(getattr(cfg, "sched", None) or "fifo")
    if sched.preempts and not memory.host_tier:
        raise ValueError(
            f"sched={sched.name!r} preempts running slots by parking "
            f"their KV in the host tier, but memory.host_tier is off; "
            f"use memory=MemoryConfig(host_tier=True) (paged layout) or "
            f"a non-preempting sched")

    # speculative decoding (the third execution path): the k-token verify
    # IS a chunked step through decode_chunk_paged, so spec needs the
    # paged layout (lanes has no chunk verifier), a proposal depth of at
    # least one, and a draft whose vocabulary matches the target's
    spec = getattr(cfg, "spec", None) or SpecConfig()
    draft_cfg = None
    if spec.enabled:
        if layout != "paged":
            raise ValueError(
                f"speculative decoding verifies k tokens through "
                f"decode_chunk_paged over the paged pool, but "
                f"kv_layout={layout!r} has no chunk verifier; use the "
                f"paged layout (dense non-SWA DecoderLM family) or "
                f"SpecConfig(enabled=False)")
        if spec.k < 1:
            raise ValueError(
                f"SpecConfig.k must be >= 1 (k tokens proposed per "
                f"verify round), got {spec.k}")
        draft_cfg = resolve_draft(spec, vocab=vocab, arch_name=arch_name)

    ps = getattr(cfg, "page_size", 8)
    n_slots = getattr(cfg, "n_slots", 8)
    max_pages = -(-int(getattr(cfg, "max_seq")) // int(ps))
    n_blocks = int(getattr(cfg, "n_blocks", 0) or n_slots * max_pages)
    n_regions = n_blocks if layout == "paged" else n_slots * max_pages
    path_name = (getattr(cfg, "path", None)
                 or getattr(cfg, "write_mode", None) or "direct")
    path, decision = build_decision(
        path_name, getattr(cfg, "policy", None), n_regions=n_regions,
        hot_threshold=getattr(cfg, "hot_threshold", 4), layout=layout,
        chunked=chunked)
    return ResolvedPlan(
        layout=layout, attention=attention,
        drain_kernel=resolve_drain_kernel(attn_cfg.drain_kernel,
                                          backend=backend), path=path, decision=decision,
        sched=sched, memory=memory, parallel=parallel, mesh=mesh,
        n_blocks=n_blocks, max_pages=max_pages, n_regions=n_regions,
        spec=spec, draft_cfg=draft_cfg)


def build_decision(path: Union[str, WritePath] = "direct",
                   policy: Optional[str] = None, *,
                   n_regions: int,
                   hot_threshold: int = 4,
                   layout: Optional[str] = None,
                   chunked: bool = False,
                   **policy_kw) -> Tuple[WritePath, DecisionModule]:
    """The one (path, policy) -> decision-plane factory.

    Resolves both names through their registries, negotiates capabilities
    (loud errors on incompatible combos), and assembles the
    :class:`DecisionModule`: policies that own their routing state
    (``owns_state``) keep their monitor to themselves; decide-style
    policies share the module-level monitor so every write heats the
    same counters the engine reads for telemetry.
    """
    wp = get_path(path)
    pol_name = policy or wp.default_policy
    factory = get_policy_factory(pol_name)
    monitor = ExactMonitor(n_regions=n_regions)
    pol = factory(monitor=monitor, n_regions=n_regions,
                  hot_threshold=hot_threshold, **policy_kw)
    negotiate(wp, pol, layout=layout, chunked=chunked)
    if getattr(pol, "owns_state", not hasattr(pol, "decide")):
        module = DecisionModule(policy=pol)
    else:
        module = DecisionModule(policy=pol, monitor=monitor)
    return wp, module


__all__ = [
    "CAP_DIRECT", "CAP_STAGED", "CAP_BULK_PIN",
    "ATTN_FUSED", "ATTN_REFERENCE",
    "WritePath", "register_path", "get_path", "available_paths",
    "DIRECT", "STAGED", "ADAPTIVE",
    "negotiate", "negotiate_memory", "resolve_attention",
    "resolve_drain_kernel", "build_decision",
    "ParallelConfig", "MemoryConfig", "AttentionConfig", "SpecConfig",
    "resolve_draft", "ResolvedPlan", "validate", "normalize_groups",
]
