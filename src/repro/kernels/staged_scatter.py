"""staged_scatter — Pallas TPU kernel for the unload-path drain.

The drain moves staged payload rows (appended sequentially into the staging
ring by the unload module) to their final destination rows (KV-cache pages /
expert buffers). This is the TPU-native analogue of the paper's target-CPU
memcpy: the staging buffer is read CONTIGUOUSLY (perfect HBM streaming) and
each row lands in its destination page via a scalar-prefetched index map —
no gather/scatter HLO, no worst-case dense lowering.

TPU adaptation notes (DESIGN.md §2):
* destination row indices arrive via ``PrefetchScalarGridSpec`` so the DMA
  engine knows the target block BEFORE the grid step runs (the RNIC "knows
  the translation" — by construction, not by cache luck);
* payload rows are tiled to (1, 1, BW) VMEM blocks over a free [rows, 1, W]
  view, BW a multiple of 128 lanes: a block's last two dims are then
  (whole unit axis, lane-aligned width), which the TPU tiling accepts for
  any row count;
* ``input_output_aliases`` updates the destination in place (the drain is
  an update, not a copy of the whole memory);
* the kernel body is an UNCONDITIONAL copy: invalid entries are handled in
  the (jnp) wrapper by redirecting them to duplicate the last valid write —
  identical data to an identical row is deterministic under any grid order,
  so the kernel needs no predication at all.

PRECONDITION (guaranteed by the unload module's conflict-triggered drains):
valid destination rows are unique within one drain batch.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU lane width: last-dim blocks should be multiples of 128.
_LANE = 128


def _drain_kernel(dst_row_ref, staging_ref, dest_in_ref, dest_ref):
    """One grid step: copy staging row ``i`` block ``j`` -> dest row
    dst_row[i] block ``j`` (row selection happens in the index maps)."""
    dest_ref[...] = staging_ref[...].astype(dest_ref.dtype)


def staged_scatter(
    dest: jnp.ndarray,     # [R, W]
    staging: jnp.ndarray,  # [N, W]
    dst_row: jnp.ndarray,  # int32[N]
    valid: jnp.ndarray,    # bool[N]
    *,
    block_w: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drain staged rows into destination rows. See module docstring."""
    r, w = dest.shape
    n = staging.shape[0]
    bw = min(block_w, w)
    if w % bw:
        raise ValueError(f"W={w} must be divisible by block_w={bw}")

    # ---- sanitize: valid entries first; tail duplicates the last valid ----
    order = jnp.argsort(~valid, stable=True)
    rows_s = dst_row[order]
    stage_s = staging[order]
    valid_s = valid[order]
    nv = jnp.sum(valid.astype(jnp.int32))
    last = jnp.maximum(nv - 1, 0)
    fill_row = jnp.where(nv > 0, rows_s[last], 0)
    fill_data = jnp.where(nv > 0, stage_s[last], dest[0])
    rows_eff = jnp.where(valid_s, rows_s, fill_row).astype(jnp.int32)
    stage_eff = jnp.where(valid_s[:, None], stage_s, fill_data[None, :])

    row_block = pl.BlockSpec((1, 1, bw), lambda i, j, rows: (rows[i], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # rows_eff
        grid=(n, w // bw),
        in_specs=[
            pl.BlockSpec((1, 1, bw), lambda i, j, rows: (i, 0, j)),  # staging
            row_block,                                              # dest (aliased)
        ],
        out_specs=row_block,
    )
    fn = pl.pallas_call(
        _drain_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1, w), dest.dtype),
        input_output_aliases={2: 0},  # dest (operand 2, counting prefetch) -> out
        interpret=interpret,
        name="staged_scatter",
    )
    out = fn(rows_eff, stage_eff.reshape(n, 1, w), dest.reshape(r, 1, w))
    return out.reshape(r, w)
