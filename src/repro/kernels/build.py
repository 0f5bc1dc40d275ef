"""Pallas TPU kernel: device-resident bulk build of the mqr group pyramid.

The host build path (``core/mqrtree.py`` insertion, then ``flatten`` +
``level_schedule``) is per-object Python and dominates end-to-end time for
large n; ``core/bulk.py`` already phrases the canonical mqr tree as a
level-by-level centroid-quadrant fixed point in pure jnp.  This module
computes that same fixed point ON DEVICE and emits the
:class:`repro.core.flat.LevelSchedule` arrays the fused region-search
kernel consumes directly — no host pointer tree, no ``flatten()`` on the
hot build path (DESIGN.md §7).

Two engines, bit-identical outputs:

* ``engine="pallas"`` — ONE ``pallas_call`` with ``grid=(levels,)``.  The
  object MBRs stay VMEM-resident coordinate-major for the whole build; per
  level the kernel (a) subdivides each multi-member group by the
  branch-free Fig. 2 quadrant select of ``bulk.quad_code``, (b) densifies
  the new ``parent*5+quad`` keys into ascending-key ranks (identical
  numbering to ``bulk._densify``'s sort-based ranks), and (c) computes
  each group's enclosing MBR as a segment min/max over ``block_n``-object
  tiles (one-hot select + tile reduce).  On the TPU (a) and (b) are
  gather-free compare-select-reduce passes over (128, 128) tiles — Mosaic
  lowers neither lane gathers nor ``cumsum``; the interpreter keeps the
  gather + prefix-sum form.  Group-of / slot-MBR / parent rows are
  emitted level by level straight into the schedule layout.
* ``engine="jnp"`` — ``bulk.build_pyramid`` (the parity oracle) plus a
  vectorized scatter for the parent map, all jit'd; this is also the
  large-n path, since the kernel holds the whole object set in VMEM and is
  therefore sized for VMEM-scale n (DESIGN.md §7).

Both produce a schedule bit-identical to the host
``flat.pyramid_schedule(bulk.build_pyramid(...))`` lowering
(tests/test_device_build.py), so the fused scan's hit sets and per-level
access counts are unchanged — only where the build runs moves.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bulk
from repro.core.flat import LevelSchedule, pyramid_entries

from .pyramid_scan import COMPILER_PARAMS

# Above this the whole-set VMEM residency of the build kernel stops making
# sense (objects, bounds, and the 5x key space all live on chip); the
# ``auto`` engine falls back to the jit'd jnp fixed point.
PALLAS_BUILD_MAX_N = 4096


def _build_kernel(
    mbr_ref,      # (4, W) f32 — object MBRs coordinate-major, resident
    gof_ref,      # out (1, 1, W) i32 — group id per object at this level
    mbr_out_ref,  # out (1, 4, W) f32 — slot MBRs of this level
    par_out_ref,  # out (1, 1, W) i32 — parent slot of each slot
    gid_ref,      # scratch (1, W) i32 — current-level group ids
    prev_ref,     # scratch (1, W) i32 — previous-level group ids
    key_ref,      # scratch (1, W) i32 — subdivision keys of this level
    rank_ref,     # scratch (1, W) f32 — dense ranks of those keys
    bounds_ref,   # scratch (4, W) f32 — per-slot MBRs (segment min/max)
    counts_ref,   # scratch (1, W) f32 — per-slot member counts
    bcol_ref,     # scratch (5, W, B) f32 — bounds + counts, slot-major
    *,
    n: int,
    width: int,
    block_n: int,
    onehot_gather: bool,
):
    l = pl.program_id(0)

    @pl.when(l == 0)
    def _root():
        gid_ref[...] = jnp.zeros((1, width), jnp.int32)
        prev_ref[...] = jnp.zeros((1, width), jnp.int32)

    @pl.when(l > 0)
    def _subdivide():
        # Level l-1 state is still in scratch: derive level-l group ids.
        if onehot_gather:
            _subdivide_tiled(mbr_ref, gid_ref, prev_ref, key_ref, rank_ref,
                             bcol_ref, n=n, width=width, block_n=block_n)
        else:
            _subdivide_gather(mbr_ref, gid_ref, prev_ref, bounds_ref,
                              counts_ref, n=n, width=width, block_n=block_n)

    _segment_bounds(mbr_ref, gid_ref, prev_ref, bounds_ref, counts_ref,
                    bcol_ref, par_out_ref, l=l, n=n, width=width,
                    block_n=block_n)
    gof_ref[0] = gid_ref[...]
    mbr_out_ref[0] = bounds_ref[...]


def _group_key(mbr, gid, gb, cnt):
    """Fig. 2 subdivision key of each object: its quadrant about its
    group's MBR centroid; singletons keep their slot ("quad 0" of their
    own group), so keys stay unique per group (bulk.build_pyramid)."""
    cx = (mbr[0] + mbr[2]) * 0.5
    cy = (mbr[1] + mbr[3]) * 0.5
    gcx = (gb[0] + gb[2]) * 0.5
    gcy = (gb[1] + gb[3]) * 0.5
    quad = bulk.quad_code(cx, cy, gcx, gcy)
    return jnp.where(cnt > 1.5, gid * 5 + quad, gid * 5)


def _subdivide_gather(mbr_ref, gid_ref, prev_ref, bounds_ref, counts_ref, *,
                      n, width, block_n):
    """Interpreter path: lane gathers and a cumsum over the 5W key space."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)[0]  # (W,)
    valid = lane < n
    n_tiles = width // block_n
    gid = gid_ref[0, :]
    # Empty slots carry +/-inf sentinels; members only ever gather their
    # own (non-empty, finite) group.
    safe = jnp.where(counts_ref[...] > 0.0, bounds_ref[...], 0.0)
    gb = jnp.take(safe, gid, axis=1)                # (4, W)
    cnt = jnp.take(counts_ref[0, :], gid)           # (W,)
    key = _group_key(mbr_ref[...], gid, gb, cnt)
    key = jnp.where(valid, key, 0)
    # Densify: presence mask over the 5W key space, then prefix-sum
    # ranks — ascending-key numbering, exactly bulk._densify's.
    kspace = 5 * width
    pres = jnp.zeros((kspace,), jnp.float32)
    for t in range(n_tiles):
        sl = slice(t * block_n, (t + 1) * block_n)
        oh5 = (
            jax.lax.broadcasted_iota(jnp.int32, (block_n, kspace), 1)
            == key[sl][:, None]
        ) & valid[sl][:, None]
        pres = jnp.maximum(pres, oh5.astype(jnp.float32).max(axis=0))
    rank = jnp.cumsum(pres) - 1.0  # (5W,) f32; exact for n < 2**24
    new_gid = jnp.take(rank, key).astype(jnp.int32)
    prev_ref[...] = gid_ref[...]
    gid_ref[0, :] = jnp.where(valid, new_gid, 0)


def _subdivide_tiled(mbr_ref, gid_ref, prev_ref, key_ref, rank_ref, bcol_ref,
                     *, n, width, block_n):
    """TPU path: the same subdivision with no gather and no scan.

    Mosaic lowers neither lane gathers nor cumsum, so every lookup is a
    (B, B) compare-select-reduce over one slot tile × one object tile:
    each object's group box and count are selected out of the slot-major
    ``bcol`` copy, and the dense rank of a key is the number of DISTINCT
    valid keys below it (``bulk._densify``'s ascending-key numbering),
    counted pairwise.  Keys and ids stay below 2**24, so the float32
    compares and counts are exact."""
    b = block_n
    n_tiles = width // b
    sub = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    eye = sub == lane

    def tile(t):
        return pl.ds(pl.multiple_of(t * b, b), b)

    def key_tile(ot, carry):
        gid_t = gid_ref[:, tile(ot)]                          # (1, B)

        def gather(gt, acc):
            own = sub + gt * b == gid_t                       # (slots, objs)
            return tuple(
                jnp.maximum(a, jnp.max(
                    jnp.where(own, bcol_ref[c, tile(gt), :], -jnp.inf),
                    axis=0, keepdims=True))
                for c, a in enumerate(acc)
            )

        init = (jnp.full((1, b), -jnp.inf, jnp.float32),) * 5
        lox, loy, hix, hiy, cnt = jax.lax.fori_loop(0, n_tiles, gather, init)
        m = mbr_ref[:, tile(ot)]                              # (4, B)
        key = _group_key([m[c:c + 1] for c in range(4)], gid_t,
                         (lox, loy, hix, hiy), cnt)
        valid = lane[:1] + ot * b < n
        key_ref[:, tile(ot)] = jnp.where(valid, key, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, key_tile, 0)

    rank_ref[...] = jnp.zeros((1, width), jnp.float32)

    def rank_tile(jt, carry):
        kj = key_ref[:, tile(jt)].astype(jnp.float32)
        kj = jnp.max(jnp.where(eye, kj, -jnp.inf), axis=1, keepdims=True)
        j = sub + jt * b                                      # j on sublanes

        def dup_tile(it, dup):
            i = lane + it * b
            same = (kj == key_ref[:, tile(it)].astype(jnp.float32)) & (i < j)
            return jnp.maximum(dup, jnp.max(
                jnp.where(same & (i < n), 1.0, 0.0), axis=1, keepdims=True))

        dup = jax.lax.fori_loop(0, n_tiles, dup_tile,
                                jnp.zeros((b, 1), jnp.float32))
        first = (dup < 0.5) & (j < n)   # j is the first object with its key

        def count_tile(it, c):
            below = first & (kj < key_ref[:, tile(it)].astype(jnp.float32))
            rank_ref[:, tile(it)] += jnp.sum(
                jnp.where(below, 1.0, 0.0), axis=0, keepdims=True)
            return c

        jax.lax.fori_loop(0, n_tiles, count_tile, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, rank_tile, 0)
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) < n
    prev_ref[...] = gid_ref[...]
    gid_ref[...] = jnp.where(valid, rank_ref[...].astype(jnp.int32), 0)


def _segment_bounds(mbr_ref, gid_ref, prev_ref, bounds_ref, counts_ref,
                    bcol_ref, par_out_ref, *, l, n, width, block_n):
    """Segment min/max, member count and parent of every slot of the
    CURRENT level, one (B, B) slot-tile × object-tile block at a time
    (slots on sublanes, objects on lanes; lane reductions only)."""
    b = block_n
    n_tiles = width // b
    sub = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    eye = sub == lane

    def tile(t):
        return pl.ds(pl.multiple_of(t * b, b), b)

    def slot_tile(gt, carry):
        slot = sub + gt * b

        def obj_tile(ot, acc):
            member = (slot == gid_ref[:, tile(ot)]) & (lane + ot * b < n)
            m = mbr_ref[:, tile(ot)]
            lox, loy, hix, hiy, cnt, par = acc

            def red(acc, op, x, fill):  # min/max over the member lanes
                part = jnp.where(member, x, fill)
                lanes = jnp.min if op is jnp.minimum else jnp.max
                return op(acc, lanes(part, axis=1, keepdims=True))

            # parent[slot] = a member's previous-level gid (groups nest,
            # so every member agrees); max-reduce the (prev + 1) tags.
            tag = prev_ref[:, tile(ot)].astype(jnp.float32) + 1.0
            return (
                red(lox, jnp.minimum, m[0:1], jnp.inf),
                red(loy, jnp.minimum, m[1:2], jnp.inf),
                red(hix, jnp.maximum, m[2:3], -jnp.inf),
                red(hiy, jnp.maximum, m[3:4], -jnp.inf),
                cnt + jnp.sum(jnp.where(member, 1.0, 0.0), axis=1,
                              keepdims=True),
                red(par, jnp.maximum, tag, 0.0),
            )

        def col(v):
            return jnp.full((b, 1), v, jnp.float32)

        init = (col(jnp.inf), col(jnp.inf), col(-jnp.inf), col(-jnp.inf),
                col(0.0), col(0.0))
        *box, cnt, par = jax.lax.fori_loop(0, n_tiles, obj_tile, init)

        def row(v):  # (B, 1) slot column -> (1, B) lane row
            return jnp.max(jnp.where(eye, v, -jnp.inf), axis=0, keepdims=True)

        for c, v in enumerate(box + [cnt]):
            bcol_ref[c, tile(gt), :] = jnp.broadcast_to(v, (b, b))
        for c, v in enumerate(box):
            bounds_ref[c:c + 1, tile(gt)] = row(v)
        counts_ref[:, tile(gt)] = row(cnt)
        parent = jnp.maximum(row(par), 1.0).astype(jnp.int32) - 1
        par_out_ref[0, :, tile(gt)] = jnp.where(l > 0, parent, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, slot_tile, 0)


@functools.partial(
    jax.jit, static_argnames=("levels", "block_n", "interpret", "onehot_gather")
)
def build_levels_pallas(
    mbrs: jnp.ndarray,  # (n, 4) f32
    *,
    levels: int,
    block_n: int = 128,
    interpret: bool = False,
    onehot_gather: bool | None = None,
):
    """One-launch device build.  Returns ``(group_of (L, n) i32,
    mbr_cm (L, 4, n) f32, parent (L, n) i32, n_real (L,) i32)`` — exactly
    the level arrays of ``flat.pyramid_schedule``."""
    mbrs = jnp.asarray(mbrs, jnp.float32)
    n = mbrs.shape[0]
    width = max(((n + block_n - 1) // block_n) * block_n, block_n)
    if onehot_gather is None:
        onehot_gather = not interpret  # same policy as pyramid_scan
    mbr_cm_in = jnp.concatenate(
        [mbrs.T, jnp.zeros((4, width - n), jnp.float32)], axis=1
    )  # (4, W); padding is masked out of every reduction by `valid`
    kernel = functools.partial(
        _build_kernel,
        n=n,
        width=width,
        block_n=block_n,
        onehot_gather=onehot_gather,
    )
    group_of, mbr_cm, parent = pl.pallas_call(
        kernel,
        grid=(levels,),
        in_specs=[pl.BlockSpec((4, width), lambda l: (0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, width), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 4, width), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 1, width), lambda l: (l, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((levels, 1, width), jnp.int32),
            jax.ShapeDtypeStruct((levels, 4, width), jnp.float32),
            jax.ShapeDtypeStruct((levels, 1, width), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, width), jnp.int32),
            pltpu.VMEM((1, width), jnp.int32),
            pltpu.VMEM((1, width), jnp.int32),
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.VMEM((4, width), jnp.float32),
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.VMEM((5, width, block_n), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(mbr_cm_in)
    group_of = group_of[:, 0, :n]
    parent = parent[:, 0]
    n_real = group_of.max(axis=1) + 1
    return group_of, mbr_cm[:, :, :n], parent[:, :n], n_real


@functools.partial(jax.jit, static_argnames=("levels",))
def build_levels_jnp(mbrs: jnp.ndarray, *, levels: int):
    """Pure-jnp device build (large-n engine; parity oracle wiring): the
    ``bulk.build_pyramid`` fixed point plus a vectorized parent scatter.
    Same return contract as :func:`build_levels_pallas`."""
    mbrs = jnp.asarray(mbrs, jnp.float32)
    pyr = bulk.build_pyramid(mbrs, levels)
    group_of = pyr.group_of                          # (L, n)
    n = group_of.shape[1]
    mbr_cm = jnp.transpose(pyr.group_mbr, (0, 2, 1))  # (L, 4, n)
    parent = jnp.zeros((levels, n), jnp.int32)
    if levels > 1:
        rows = jnp.broadcast_to(
            jnp.arange(1, levels)[:, None], (levels - 1, n)
        )
        parent = parent.at[rows, group_of[1:]].set(group_of[:-1])
    n_real = group_of.max(axis=1) + 1
    return group_of, mbr_cm, parent, n_real


def device_schedule(
    mbrs,
    *,
    levels: int | None = None,
    engine: str = "auto",
    block_n: int = 128,
    interpret: bool | None = None,
    order: str | None = None,
) -> LevelSchedule:
    """Device-resident bulk build straight to a :class:`LevelSchedule`.

    ``engine="auto"`` uses the Pallas kernel when it would compile natively
    (on-TPU) and the object set fits its VMEM residency
    (:data:`PALLAS_BUILD_MAX_N`), the jit'd jnp fixed point otherwise —
    both emit bit-identical schedules.  The returned schedule is the same
    object the host ``flat.pyramid_schedule`` path produces, so every
    backend (host/lax/pallas/serve) serves it unchanged.

    Objects the last level leaves sharing a group come first among the
    entries and are confirmed against their own MBR at search time
    (``LevelSchedule.n_shared``), so answers are exact on any data.

    ``order="hilbert"`` additionally renumbers every level's slots along
    the Hilbert curve of the slot-MBR centers (:func:`hilbert_permute`) —
    hit sets, ids, and per-level access counts are invariant under the
    within-level bijection; only which *tiles* the visited slots cluster
    into changes (DESIGN.md §12).
    """
    from . import ops  # runtime import: ops imports this module at load

    mbrs_f32 = np.asarray(mbrs, np.float32).reshape(-1, 4)
    n = mbrs_f32.shape[0]
    if n == 0:
        raise ValueError("device_schedule needs at least one MBR")
    if levels is None:
        levels = bulk.default_levels(n)
    if interpret is None:
        interpret = ops.interpret_default()
    if engine == "auto":
        engine = "pallas" if (not interpret and n <= PALLAS_BUILD_MAX_N) else "jnp"
    if engine == "pallas":
        group_of, mbr_cm, parent, n_real = build_levels_pallas(
            jnp.asarray(mbrs_f32), levels=levels, block_n=block_n,
            interpret=interpret,
        )
    elif engine == "jnp":
        group_of, mbr_cm, parent, n_real = build_levels_jnp(
            jnp.asarray(mbrs_f32), levels=levels
        )
    else:
        raise ValueError(f"unknown build engine {engine!r}")
    schedule = LevelSchedule(
        mbr_cm=np.ascontiguousarray(np.asarray(mbr_cm)),
        parent=np.asarray(parent),
        n_real=np.asarray(n_real, np.int32),
        n_objects=n,
        root_unconditional=False,
        test_object_mbr=False,
        **pyramid_entries(mbrs_f32, np.asarray(group_of[levels - 1]), levels),
    )
    if order not in (None, "none", "hilbert"):
        raise ValueError(f"unknown slot order {order!r}")
    if order == "hilbert":
        schedule = hilbert_permute(schedule)
    return schedule


# ---------------------------------------------------------------------------
# Build-time Hilbert slot ordering (DESIGN.md §12)
# ---------------------------------------------------------------------------


def hilbert_keys(x, y, order: int = 16) -> np.ndarray:
    """Vectorized Hilbert-curve index of points normalized to [0, 1].

    Standard bitwise xy→d walk over ``order`` bits (rotate/reflect per
    quadrant), evaluated with numpy array ops so a whole level keys in one
    pass.  Ties (identical centers) are broken by the stable argsort of
    the caller, keeping the permutation deterministic."""
    n = 1 << order
    x = np.clip((np.asarray(x, np.float64) * n).astype(np.int64), 0, n - 1)
    y = np.clip((np.asarray(y, np.float64) * n).astype(np.int64), 0, n - 1)
    d = np.zeros_like(x)
    s = n >> 1
    while s > 0:
        rx = ((x & s) > 0).astype(np.int64)
        ry = ((y & s) > 0).astype(np.int64)
        d += s * s * ((3 * rx) ^ ry)
        # rotate the quadrant: reflect when rx==1, then swap axes (ry==0)
        swap = ry == 0
        refl = swap & (rx == 1)
        xr = np.where(refl, s - 1 - x, x)
        yr = np.where(refl, s - 1 - y, y)
        x = np.where(swap, yr, xr)
        y = np.where(swap, xr, yr)
        s >>= 1
    return d


def hilbert_permute(schedule: LevelSchedule, order: int = 16) -> LevelSchedule:
    """Renumber every level's real slots along the Hilbert curve of their
    MBR centers (a within-level bijection; padded slots stay in place).

    Parent references are remapped through the previous level's
    permutation and object entry slots through their own level's, so the
    sweep recurrence computes the *same* per-level active sets under new
    slot numbers: hit sets, ``AccessStats`` ids, and per-level visit
    counts are all bit-identical (tests/test_hilbert.py).  What changes
    is tile locality — a small query's survivors cluster into few
    ``block_w`` tiles instead of scattering across the level, which is
    what the visited-tile bytes/query metric of DESIGN.md §12 measures.
    """
    obj = np.asarray(schedule.obj_mbr, np.float64)
    lo = obj[:, :2].min(axis=0)
    span = np.maximum(obj[:, 2:].max(axis=0) - lo, 1e-30)
    mbr = np.array(schedule.mbr_cm, copy=True)
    parent = np.array(schedule.parent, copy=True)
    obj_slot = np.array(schedule.obj_slot, copy=True)
    obj_level = np.asarray(schedule.obj_level)
    levels = schedule.levels
    prev_perm = None  # old slot -> new slot, previous level
    for l in range(levels):
        nr = int(schedule.n_real[l])
        cx = (schedule.mbr_cm[l, 0, :nr] + schedule.mbr_cm[l, 2, :nr]) / 2.0
        cy = (schedule.mbr_cm[l, 1, :nr] + schedule.mbr_cm[l, 3, :nr]) / 2.0
        keys = hilbert_keys((cx - lo[0]) / span[0], (cy - lo[1]) / span[1],
                            order=order)
        by_key = np.argsort(keys, kind="stable")  # new slot -> old slot
        perm = np.empty(nr, np.int64)
        perm[by_key] = np.arange(nr)              # old slot -> new slot
        mbr[l, :, :nr] = schedule.mbr_cm[l][:, by_key]
        if l > 0:
            old_parent = np.asarray(schedule.parent[l, :nr], np.int64)
            parent[l, :nr] = prev_perm[old_parent[by_key]].astype(
                schedule.parent.dtype
            )
        mask = obj_level == l
        if mask.any():
            obj_slot[mask] = perm[
                np.asarray(schedule.obj_slot)[mask].astype(np.int64)
            ].astype(obj_slot.dtype)
        prev_perm = perm
    return dataclasses.replace(
        schedule, mbr_cm=mbr, parent=parent, obj_slot=obj_slot
    )
