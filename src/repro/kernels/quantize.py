"""Pallas TPU kernel: conservative uint16 quantization of MBR tile grids.

GP-Tree-style grid discretization (PAPERS.md) for the fused level sweep:
the schedule's float32 node MBRs are snapped to a ``CELLS``-cell uint16
grid with OUTWARD rounding — lo coordinates floor, hi coordinates ceil —
so every quantized box contains its exact box.  Queries are quantized
outward the same way at scan time, which makes the quantized overlap test
a conservative superset of the exact one: true hits are never dropped,
and the (rare, one-grid-cell-wide) false positives are removed by the
exact float32 confirming pass of
:func:`repro.kernels.pyramid_scan.pyramid_scan_compact` (DESIGN.md §7).

The grid derives from the root bounding box (the union of the object
MBRs), per axis: ``cell = clip(round((v - origin) * cells / extent))``.
Padded slots (lo=+inf / hi=-inf sentinels) map to the integer
never-overlap sentinel ``Q_NEVER_MBR`` (lo = cells+1 > any query hi).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flat import (
    CELLS,
    CELLS8,
    Q_NEVER_MBR,
    LevelSchedule,
    QuantizedSchedule,
)


def grid_params(schedule: LevelSchedule, cells: int = CELLS):
    """Derive the per-axis grid from the object-MBR union (== root box).

    Returns ``(origin (4,) f32, inv_cell (4,) f32)`` laid out
    coordinate-major (x, y, x, y) so they broadcast against the
    ``(lx, ly, hx, hy)`` coordinate rows directly.  ``cells`` picks the
    grid resolution — ``CELLS`` for the uint16 form, ``CELLS8`` for the
    coarse uint8 upper-level form (same origin either way).
    """
    obj = np.asarray(schedule.obj_mbr, np.float64)
    lo = obj[:, :2].min(axis=0)
    hi = obj[:, 2:].max(axis=0)
    # Cap the scale well inside float32: a degenerate (zero-extent) axis
    # must not produce an inf scale, or quantizing a query AT the origin
    # hits 0*inf=NaN.  With a capped scale the axis still quantizes
    # conservatively (everything lands in cells [0, 1]).
    with np.errstate(divide="ignore"):
        inv = np.minimum(cells / np.maximum(hi - lo, 0.0), 1e30)
    origin = np.concatenate([lo, lo]).astype(np.float32)
    inv_cell = np.concatenate([inv, inv]).astype(np.float32)
    return origin, inv_cell


def quantize_cm_jnp(mbr_cm, origin, inv_cell, *, cells: int = CELLS,
                    dtype=jnp.uint16):
    """Reference (and large-array) quantizer: (L, 4, W) f32 -> ``dtype``
    grid cells on a ``cells``-cell outward-rounded grid."""
    mbr_cm = jnp.asarray(mbr_cm, jnp.float32)
    t = (mbr_cm - origin[None, :, None]) * inv_cell[None, :, None]
    is_lo = (jnp.arange(4) < 2)[None, :, None]
    cell = jnp.where(is_lo, jnp.floor(t), jnp.ceil(t))
    cell = jnp.clip(cell, 0.0, float(cells))
    # lo=+inf sentinel (padded slot) -> integer never-overlap sentinel
    cell = jnp.where(is_lo & (mbr_cm == jnp.inf), float(cells + 1), cell)
    return cell.astype(dtype)


def quantize_rows(mbrs: np.ndarray, origin: np.ndarray,
                  inv_cell: np.ndarray) -> np.ndarray:
    """Conservative uint16 quantization of row-major (N, 4) MBRs onto an
    EXISTING schedule grid — the delta-buffer lowering (DESIGN.md §8).

    Unlike node boxes, delta rows may extend past the grid domain (inserts
    land anywhere).  Clipping lo-after-floor and hi-after-ceil into
    ``[0, CELLS]`` preserves the conservative-superset property because
    scan-time queries are clipped into the same range and clip is
    monotone: real-interval intersection still implies clipped-integer
    intersection on every axis; the exact confirming pass removes the
    extra boundary candidates.  Same float32 arithmetic as
    :func:`quantize_cm_jnp`, so delta tiles behave exactly like base
    tiles.  Rows with ``lo == +inf`` (empty slots) map to ``Q_NEVER_MBR``.
    """
    m = np.asarray(mbrs, np.float32)
    origin = np.asarray(origin, np.float32)
    inv_cell = np.asarray(inv_cell, np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        t = (m - origin[None, :]) * inv_cell[None, :]
        cell = np.concatenate(
            [np.floor(t[:, :2]), np.ceil(t[:, 2:])], axis=1
        )
    cell = np.clip(cell, 0.0, float(CELLS))
    out = cell.astype(np.uint16)
    out[np.isposinf(m[:, 0])] = Q_NEVER_MBR
    return out


def _quantize_kernel(mbr_ref, org_ref, inv_ref, out_ref, *, block_w: int):
    v = mbr_ref[0]                       # (4, BW) f32
    org = org_ref[0][:, None]            # (4, 1)
    inv = inv_ref[0][:, None]
    t = (v - org) * inv
    is_lo = jax.lax.broadcasted_iota(jnp.int32, (4, block_w), 0) < 2
    cell = jnp.where(is_lo, jnp.floor(t), jnp.ceil(t))
    cell = jnp.clip(cell, 0.0, float(CELLS))
    cell = jnp.where(is_lo & (v == jnp.inf), float(CELLS + 1), cell)
    # Mosaic has no float -> uint16 cast; the cells fit int32 exactly.
    out_ref[0] = cell.astype(jnp.int32).astype(jnp.uint16)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def quantize_cm_pallas(mbr_cm, origin, inv_cell, *, block_w: int = 128,
                       interpret: bool = False):
    """Device quantizer: one elementwise Pallas pass over the level grid."""
    mbr_cm = jnp.asarray(mbr_cm, jnp.float32)
    levels, _, w = mbr_cm.shape
    pad = (-w) % block_w
    if pad:
        # pad with the float never-sentinel; quantizes to Q_NEVER_MBR
        sent = jnp.asarray(
            [jnp.inf, jnp.inf, -jnp.inf, -jnp.inf], jnp.float32
        )
        mbr_cm = jnp.concatenate(
            [mbr_cm, jnp.broadcast_to(sent[None, :, None], (levels, 4, pad))],
            axis=2,
        )
    wp = w + pad
    out = pl.pallas_call(
        functools.partial(_quantize_kernel, block_w=block_w),
        grid=(levels, wp // block_w),
        in_specs=[
            pl.BlockSpec((1, 4, block_w), lambda l, t: (l, 0, t)),
            pl.BlockSpec((1, 4), lambda l, t: (0, 0)),
            pl.BlockSpec((1, 4), lambda l, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 4, block_w), lambda l, t: (l, 0, t)),
        out_shape=jax.ShapeDtypeStruct((levels, 4, wp), jnp.uint16),
        interpret=interpret,
    )(mbr_cm, origin[None, :], inv_cell[None, :])
    return out[:, :, :w]


def quantize_schedule(
    schedule: LevelSchedule,
    *,
    engine: str = "auto",
    block_w: int = 128,
    interpret: bool | None = None,
    upper8: bool = False,
    split: int | None = None,
) -> QuantizedSchedule:
    """Lower a :class:`LevelSchedule` to its compact uint16 tile form.

    ``upper8=True`` additionally materializes coarse uint8 tiles for the
    upper levels (``[0, split)``, default all but the deepest level) on a
    254-cell grid sharing the same origin — the hierarchical form
    :func:`repro.kernels.ops.pyramid_scan_compact8` sweeps (DESIGN.md
    §12).  Outward rounding is resolution-independent, so the confirming
    pass keeps hit sets bit-identical at any split.
    """
    from . import ops  # runtime import: ops imports this module at load

    if interpret is None:
        interpret = ops.interpret_default()
    if engine == "auto":
        engine = "jnp" if interpret else "pallas"
    origin, inv_cell = grid_params(schedule)
    if engine == "pallas":
        mbr_q = quantize_cm_pallas(
            schedule.mbr_cm, jnp.asarray(origin), jnp.asarray(inv_cell),
            block_w=block_w, interpret=interpret,
        )
    elif engine == "jnp":
        mbr_q = quantize_cm_jnp(
            schedule.mbr_cm, jnp.asarray(origin), jnp.asarray(inv_cell)
        )
    else:
        raise ValueError(f"unknown quantize engine {engine!r}")
    # Parent slots stream as uint16 while the level width fits; wider
    # schedules (pyramid width == n > 65535) fall back to int32 parents,
    # keeping the MBR tiles uint16 (bytes ratio 0.6 instead of 0.5).
    pdtype = (
        np.uint16 if schedule.width <= np.iinfo(np.uint16).max else np.int32
    )
    # Every entry is confirmed against its own MBR, which every ancestor
    # box holds (DESIGN.md §7): exact on trees and on pyramids whose
    # deepest groups hold several objects alike.
    confirm = np.asarray(schedule.obj_mbr, np.float32)
    mbr_q8 = None
    inv_cell8 = None
    if split is None:
        split = max(schedule.levels - 1, 0) if upper8 else 0
    if upper8 and split > 0:
        _, inv_cell8 = grid_params(schedule, cells=CELLS8)
        mbr_q8 = np.asarray(
            quantize_cm_jnp(
                schedule.mbr_cm[:split], jnp.asarray(origin),
                jnp.asarray(inv_cell8), cells=CELLS8, dtype=jnp.uint8,
            )
        )
    return QuantizedSchedule(
        base=schedule,
        mbr_q=np.asarray(mbr_q),
        parent_q=schedule.parent.astype(pdtype),
        origin=origin,
        inv_cell=inv_cell,
        confirm_mbr=confirm,
        cells=CELLS,
        mbr_q8=mbr_q8,
        split=split if upper8 else 0,
        cells8=CELLS8,
        inv_cell8=inv_cell8,
    )
