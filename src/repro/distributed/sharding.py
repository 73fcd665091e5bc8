"""Logical sharding rules: parameter/optimizer/batch/cache PartitionSpecs.

Axis roles
----------
  "model"          tensor parallelism (TP): attention heads or head_dim,
                   MLP hidden, experts (EP), vocab — per divisibility.
  "data"           FSDP for parameters + optimizer state, batch data
                   parallelism for activations.
  "pod"            (multi-pod mesh only) pure DP across pods: parameters
                   replicated across pods, gradients all-reduced over
                   ("pod",) in addition to FSDP's reduce-scatter over data.

Divisibility-driven schemes (recorded per arch in DESIGN.md):
* attention: shard heads when Hq%TP==0 and Hkv%TP==0; else shard q-heads and
  REPLICATE kv projections (Megatron GQA style) when Hq%TP==0; else shard
  head_dim (contraction-sharded attention) when Dh%TP==0; else replicate.
* vocab: shard V over model when divisible (TP vocab parallelism: logits +
  loss reductions partition over V), else shard D.
* experts: EP over model when E%TP==0 (qwen3: 128/16), else TP inside the
  expert FFN (granite: 40 experts, d_ff 512 -> shard d_ff... only when
  divisible, else data).
* KV caches at decode: heads over model when Hkv%TP==0, else SEQUENCE over
  model (flash-decode partial-softmax combine, GSPMD-lowered); batch over
  ("pod","data") when divisible; batch==1 (long_500k) shards sequence over
  every available axis.
"""
from __future__ import annotations

import dataclasses
from math import prod
from typing import Any, Optional, Tuple

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig

_KNOWN_AXES = ("pod", "data", "model")


def make_abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """``AbstractMesh((16, 16), ("data", "model"))`` from any sequences."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """ONE mesh spec driving both stacks (DESIGN.md §9).

    The train side consumes it through :meth:`param_shardings` /
    :meth:`state_shardings` (the pjit'd train step's sharding rules); the
    serve side through ``core.paths.validate`` -> ``ResolvedPlan.mesh`` +
    :meth:`cache_shardings` (the paged pool / staging-ring placement).
    Axis names carry the repo-wide roles pinned in this module's
    docstring: ``model`` = tensor parallelism, ``data`` = FSDP/DP,
    ``pod`` = cross-pod DP.

    The default ``(1,)`` mesh means "single device": ``build_mesh``
    returns ``None`` and every consumer takes its unsharded path — a
    ``ParallelConfig`` is always safe to construct and pass around.
    """

    mesh_shape: Tuple[int, ...] = (1,)
    axis_names: Tuple[str, ...] = ("model",)

    def __post_init__(self):
        shape = tuple(int(s) for s in self.mesh_shape)
        names = tuple(str(a) for a in self.axis_names)
        object.__setattr__(self, "mesh_shape", shape)
        object.__setattr__(self, "axis_names", names)
        if len(shape) != len(names):
            raise ValueError(
                f"ParallelConfig: mesh_shape {shape} and axis_names "
                f"{names} disagree on rank")
        if len(set(names)) != len(names):
            raise ValueError(f"ParallelConfig: duplicate axis in {names}")
        unknown = set(names) - set(_KNOWN_AXES)
        if unknown:
            raise ValueError(
                f"ParallelConfig: unknown axis names {sorted(unknown)} "
                f"(known roles: {list(_KNOWN_AXES)})")
        if any(s < 1 for s in shape):
            raise ValueError(
                f"ParallelConfig: axis sizes must be >= 1, got {shape}")

    # ------------------------------------------------------------------
    @classmethod
    def tensor(cls, tp: int) -> "ParallelConfig":
        """Pure tensor parallelism: a 1-D ``("model",)`` mesh of ``tp``."""
        return cls(mesh_shape=(tp,), axis_names=("model",))

    @property
    def n_devices(self) -> int:
        return prod(self.mesh_shape)

    @property
    def tp(self) -> int:
        """Size of the ``model`` axis (1 when the mesh has none)."""
        if "model" not in self.axis_names:
            return 1
        return self.mesh_shape[self.axis_names.index("model")]

    @property
    def enabled(self) -> bool:
        return self.n_devices > 1

    # ------------------------------------------------------------------
    def build_mesh(self, devices=None) -> Optional[Mesh]:
        """Concrete ``jax.sharding.Mesh`` over real devices, or ``None``
        for the trivial (single-device) spec. Loud, actionable error when
        the platform exposes fewer devices than the spec needs — on CPU
        hosts that means launching with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax
        is imported (the sharded CI lane's setup)."""
        if not self.enabled:
            return None
        if devices is None:
            devices = jax.devices()
        if len(devices) < self.n_devices:
            raise ValueError(
                f"ParallelConfig{self.mesh_shape}x{self.axis_names} needs "
                f"{self.n_devices} devices but the platform exposes "
                f"{len(devices)}; on a CPU host set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.n_devices} "
                f"before importing jax (tests/benchmarks do this in a "
                f"subprocess), or shrink the mesh")
        import numpy as np

        arr = np.asarray(devices[: self.n_devices]).reshape(self.mesh_shape)
        return Mesh(arr, self.axis_names)

    # ------------------------------------------------------------------
    # the one-spec-drives-both-stacks surface
    # ------------------------------------------------------------------
    def param_shardings(self, cfg: ModelConfig, abstract_params: Any,
                        devices=None) -> Optional[Any]:
        """Train/serve parameter placement from this spec (None = trivial)."""
        mesh = self.build_mesh(devices)
        if mesh is None:
            return None
        return param_shardings(cfg, mesh, abstract_params)

    def state_shardings(self, cfg: ModelConfig, abstract_state: Any,
                        devices=None) -> Optional[Any]:
        """TrainState placement (params + opt moments) from this spec."""
        mesh = self.build_mesh(devices)
        if mesh is None:
            return None
        return state_shardings(cfg, mesh, abstract_state)

    def cache_shardings(self, cfg: ModelConfig, cache: Any,
                        devices=None) -> Optional[Any]:
        """Serve-side paged-cache placement from this spec."""
        mesh = self.build_mesh(devices)
        if mesh is None:
            return None
        return serve_cache_shardings(cfg, mesh, cache)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def axis_size(mesh: Mesh, *names: str) -> int:
    return prod(mesh.shape[n] for n in names if n in mesh.axis_names)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _maybe(axes, size, mesh) -> Optional[Any]:
    """axes (str or tuple) if their product divides size, else None."""
    t = (axes,) if isinstance(axes, str) else tuple(axes)
    if all(a in mesh.axis_names for a in t) and size % axis_size(mesh, *t) == 0:
        return axes
    return None


def attention_scheme(cfg: ModelConfig, mesh: Mesh) -> str:
    m = axis_size(mesh, "model")
    if cfg.n_heads == 0:
        return "none"
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if hq % m == 0 and hkv % m == 0:
        return "heads"
    if hq % m == 0:
        return "qheads_kvrepl"
    if dh % m == 0:
        return "headdim"
    return "replicate"


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------


def param_pspec(cfg: ModelConfig, mesh: Mesh, path: str, shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one parameter leaf, keyed on its tree path."""
    name = path.split("/")[-1]
    parent = path.split("/")[-2] if "/" in path else ""
    scheme = attention_scheme(cfg, mesh)

    # ---- embeddings / head ----
    if name == "tok":
        # LOOKUP table: never shard the vocab dim — a gather over a
        # vocab-sharded table triggers SPMD "involuntary full
        # rematerialization" (replicates the gather operand). D over model
        # keeps the lookup local; the residual stream re-gathers D cheaply.
        v, d = shape
        return P(None, _maybe("model", d, mesh) or _maybe("data", d, mesh))
    if name == "head":
        # OUTPUT projection: TP vocab parallelism (logits + loss reductions
        # partition over V).
        v, d = shape
        vs = _maybe("model", v, mesh)
        if vs:
            return P(vs, _maybe("data", d, mesh))
        return P(None, _maybe("model", d, mesh) or _maybe("data", d, mesh))

    # ---- attention projections ----
    if name in ("wq", "wk", "wv"):
        d, h, k = shape[-3:]
        lead = (None,) * (len(shape) - 3)  # stacked layer dims
        fs = _maybe("data", d, mesh)
        if scheme == "heads" or (scheme == "qheads_kvrepl" and name == "wq"):
            return P(*lead, fs, _maybe("model", h, mesh), None)
        if scheme == "headdim":
            return P(*lead, fs, None, _maybe("model", k, mesh))
        return P(*lead, fs, None, None)
    if name in ("bq", "bk", "bv"):
        h, k = shape[-2:]
        lead = (None,) * (len(shape) - 2)
        if scheme == "heads" or (scheme == "qheads_kvrepl" and name == "bq"):
            return P(*lead, _maybe("model", h, mesh), None)
        if scheme == "headdim":
            return P(*lead, None, _maybe("model", k, mesh))
        return P(*lead, None, None)
    if name == "wo" and parent in ("attn", "self_attn", "cross_attn"):
        h, k, d = shape[-3:]
        lead = (None,) * (len(shape) - 3)
        fs = _maybe("data", d, mesh)
        if scheme in ("heads", "qheads_kvrepl"):
            return P(*lead, _maybe("model", h, mesh), None, fs)
        if scheme == "headdim":
            return P(*lead, None, _maybe("model", k, mesh), fs)
        return P(*lead, None, None, fs)

    # ---- MoE ----
    if name == "router":
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _maybe("data", shape[-2], mesh), None)
    if parent == "moe" and name in ("wi", "wg"):
        e, d, ff = shape[-3:]
        lead = (None,) * (len(shape) - 3)
        ep = _maybe("model", e, mesh)
        if ep:
            return P(*lead, ep, _maybe("data", d, mesh), None)
        return P(*lead, None, _maybe("data", d, mesh), _maybe("model", ff, mesh))
    if parent == "moe" and name == "wo":
        e, ff, d = shape[-3:]
        lead = (None,) * (len(shape) - 3)
        ep = _maybe("model", e, mesh)
        if ep:
            return P(*lead, ep, None, _maybe("data", d, mesh))
        return P(*lead, None, _maybe("model", ff, mesh), _maybe("data", d, mesh))

    # ---- dense MLP ----
    if name in ("wi", "wg"):
        d, ff = shape[-2:]
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _maybe("data", d, mesh), _maybe("model", ff, mesh))
    if name == "wo":
        ff, d = shape[-2:]
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _maybe("model", ff, mesh), _maybe("data", d, mesh))

    # ---- mamba ----
    if name == "in_proj":
        d, k = shape[-2:]
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _maybe("data", d, mesh), None)
    if name == "out_proj":
        k, d = shape[-2:]
        lead = (None,) * (len(shape) - 2)
        return P(*lead, None, _maybe("data", d, mesh))

    # ---- positions (replicated: small or latency-critical) / norms / rest ----
    return P(*((None,) * len(shape)))


def _key_str(p) -> str:
    if hasattr(p, "key"):      # DictKey
        return str(p.key)
    if hasattr(p, "name"):     # GetAttrKey (NamedTuple fields)
        return str(p.name)
    if hasattr(p, "idx"):      # SequenceKey
        return str(p.idx)
    return str(p)


def tree_paths_and_leaves(tree: Any):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield "/".join(_key_str(p) for p in path), leaf


def param_shardings(cfg: ModelConfig, mesh: Mesh, abstract_params: Any) -> Any:
    """NamedSharding pytree matching the (abstract) params."""
    flat = {
        k: NamedSharding(mesh, param_pspec(cfg, mesh, k, v.shape))
        for k, v in tree_paths_and_leaves(abstract_params)
    }
    leaves = [flat[k] for k, _ in tree_paths_and_leaves(abstract_params)]
    treedef = jax.tree_util.tree_structure(abstract_params)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# batch / cache rules
# ---------------------------------------------------------------------------


def _dp_for_batch(mesh: Mesh, b: int):
    axes = dp_axes(mesh)
    if axes and b % axis_size(mesh, *axes) == 0:
        return axes if len(axes) > 1 else axes[0]
    if "data" in mesh.axis_names and b % axis_size(mesh, "data") == 0:
        return "data"
    return None


def batch_pspec(cfg: ModelConfig, mesh: Mesh, path: str, shape) -> P:
    """Inputs: tokens/labels/media/pos (batch-leading)."""
    dp = _dp_for_batch(mesh, shape[0]) if len(shape) else None
    return P(dp, *((None,) * (len(shape) - 1)))


def cache_pspec(cfg: ModelConfig, mesh: Mesh, path: str, shape) -> P:
    """Decode caches: [L, B, S, H, K] kv, [L, B, H, P, N] ssm, etc."""
    name = path.split("/")[-1]
    m = axis_size(mesh, "model")
    if name in ("k", "v", "cross_k", "cross_v", "ring_k", "ring_v"):
        l, b, s, h, k = shape
        dp = _dp_for_batch(mesh, b)
        if name in ("ring_k", "ring_v"):
            return P(None, dp, None, _maybe("model", h, mesh), None)
        if dp is None:
            # long_500k (B=1): shard the sequence over every available axis
            all_ax = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
            return P(None, None,
                     _maybe(all_ax, s, mesh) or _maybe("model", s, mesh),
                     _maybe("model", h, mesh) if not _maybe(all_ax, s, mesh) else None,
                     None)
        if h % m == 0:
            return P(None, dp, None, "model", None)
        return P(None, dp, _maybe("model", s, mesh), None, None)
    if name == "ssm":
        l, b, h, p_, n = shape
        dp = _dp_for_batch(mesh, b)
        return P(None, dp, _maybe("model", h, mesh), None, None)
    if name == "conv":
        dp = _dp_for_batch(mesh, shape[1])
        return P(None, dp, *((None,) * (len(shape) - 2)))
    if name in ("ring_slot",):
        dp = _dp_for_batch(mesh, shape[0])
        return P(dp, None)
    if name == "ring_fill":
        return P()
    # fallback: batch-leading
    return batch_pspec(cfg, mesh, path, shape)


def input_shardings(cfg: ModelConfig, mesh: Mesh, specs: Any, step: str) -> Any:
    """Attach NamedShardings to the input_specs pytree of a dry-run cell."""

    def one(key, leaf):
        if key.startswith("cache"):
            ps = cache_pspec(cfg, mesh, key, leaf.shape)
        else:
            ps = batch_pspec(cfg, mesh, key, leaf.shape)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, ps))

    flat = [(k, v) for k, v in tree_paths_and_leaves(specs)]
    leaves = [one(k, v) for k, v in flat]
    treedef = jax.tree_util.tree_structure(specs)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# train-state rules
# ---------------------------------------------------------------------------


def state_shardings(cfg: ModelConfig, mesh: Mesh, abstract_state: Any) -> Any:
    """TrainState: params + opt moments follow param rules; scalars and
    monitor arrays replicate."""

    def one(key, leaf):
        if key.startswith(("params", "opt/mu", "opt/nu")):
            pkey = key.split("/", 1)[1]
            if pkey.startswith(("mu/", "nu/")):
                pkey = pkey.split("/", 1)[1]
            return NamedSharding(mesh, param_pspec(cfg, mesh, pkey, leaf.shape))
        return NamedSharding(mesh, P(*((None,) * len(leaf.shape))))

    flat = [(k, v) for k, v in tree_paths_and_leaves(abstract_state)]
    leaves = [one(k, v) for k, v in flat]
    treedef = jax.tree_util.tree_structure(abstract_state)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def with_shardings(abstract: Any, shardings: Any) -> Any:
    """ShapeDtypeStruct pytree with shardings attached (for .lower)."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings,
    )


# ---------------------------------------------------------------------------
# serve-side paged-cache rules (DESIGN.md §9)
# ---------------------------------------------------------------------------


def serve_cache_pspec(cfg: ModelConfig, mesh: Mesh, name: str, shape) -> P:
    """PartitionSpec for one SERVE-layer paged-cache leaf.

    Data planes shard on the HEAD axis (axis 3 of both the pool's
    [L, n_blocks, ps, H, Dh] and the ring's [L, n_slots, R, H, Dh]) when
    Hkv divides the model-axis size — the same divisibility rule as
    :func:`cache_pspec`'s decode branch. Everything the host mutates
    between segments (page table, ring metadata/cursor) REPLICATES: the
    decision plane — routing, drains, allocation — stays identical on
    every shard, so drains never move data across shard boundaries (each
    shard scatters only its own head slice; the paper's
    keep-the-unload-local contract under TP).
    """
    if name in ("pages_k", "pages_v", "ring_k", "ring_v"):
        h = shape[3]
        return P(None, None, None, _maybe("model", h, mesh), None)
    return P(*((None,) * len(shape)))


def serve_cache_shardings(cfg: ModelConfig, mesh: Mesh, cache: Any) -> Any:
    """NamedSharding dict for a ``kvcache.paged`` cache (serve layer)."""
    return {
        k: NamedSharding(mesh, serve_cache_pspec(cfg, mesh, k, v.shape))
        for k, v in cache.items()
    }


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated NamedSharding — the telemetry-readback pin (see
    ``core.decision.replicate_for_readback``)."""
    return NamedSharding(mesh, P())
