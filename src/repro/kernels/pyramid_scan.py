"""Pallas TPU kernel: fused multi-level region search (one launch per sweep).

``mbr_scan`` scans ONE tree level per kernel call, so a height-``L`` search
pays ``L`` dispatches and the survivor frontier round-trips through host
Python between levels.  This kernel fuses the whole levelized sweep of a
:class:`repro.core.flat.LevelSchedule` into a single ``pallas_call``
(DESIGN.md §3.3):

* grid = (levels, width tiles) — levels iterate in the outer grid dimension,
  and TPU grid execution is sequential, so level ``l`` sees level ``l-1``'s
  results;
* the per-level survivor masks live in two VMEM scratch buffers
  (``prev``/``cur``, each (Q, W)) that persist across grid steps;
* the Q query rectangles stay resident in VMEM for the entire sweep;
* node-MBR tiles are streamed coordinate-major (4, block_w) — one tile fetch
  = one "disk access" of the paper (DESIGN.md §3);
* the parent gather ``prev[:, parent[j]]`` is expressed as a one-hot matmul
  (broadcasted-iota compare + ``jnp.dot``) so it runs on the MXU instead of
  a lane gather.

The VMEM-resident layout above caps single-chip width: the two survivor
masks alone cost ``2·Q·W·4`` bytes of VMEM.  ``stream=True`` switches to
the HBM-streaming variant (DESIGN.md §12): MBR/parent tiles live in HBM
(``memory_space=ANY``) and are double-buffered into VMEM with explicit
async copies (copy of tile ``t+1`` overlaps compute of tile ``t``,
``emit_pipeline``-style), and the survivor masks ping-pong through an HBM
scratch — each grid step only reads back the narrow *parent window*
actually referenced by its tile (``parent_windows``).  Per-step VMEM then
scales with ``Q·(win_w + O(block_w))`` instead of ``Q·W``, which is what
lets one chip sweep 1e7+ objects.

The kernel emits the full per-level active mask; a thin jnp epilogue (still
one kernel launch) reduces it to object hits and per-level access counts
that are bit-identical to the host pointer search / ``bulk.pyramid_search``
(tests/test_pyramid_scan.py, tests/test_stream_scan.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flat import (
    NEVER_MBR,
    Q8_NEVER_MBR,
    Q_NEVER_MBR,
    LevelSchedule,
    QuantizedSchedule,
    _overlaps,
    confirm_shared,
    confirm_width,
)
from repro.obs import counters as _obs_counters
from repro.obs import trace as _obs_trace

from .transfer import count_confirm, to_device

# Scoped-VMEM budget of the sweep, pair-sweep and build kernels.  The
# compiler's default (16 MiB on v5e) is far below the chip's 128 MiB; the
# VMEM-resident kernels keep whole (Q, W) survivor masks on chip, so their
# widest compilable schedule is set by this limit.
VMEM_LIMIT_BYTES = 100 * 2**20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)

# Tiles per SMEM block of the streamed sweep's window-offset table.
STREAM_META_BLOCK = 1024

# Ceilings of the static capacities of a pyramid launch that returns hit
# ids (DESIGN.md §12, :func:`ids_caps`).  The deepest level's active mask
# is cut into column blocks of one sweep tile (``block_w`` slots, every
# query), at most IDS_BLOCKS of them non-empty; their active slots, at
# most IDS_SLOTS, are expanded to their members in chunks of IDS_CHUNK
# lanes, at most IDS_CHUNKS chunks: IDS_CHUNK × IDS_CHUNKS id lanes.
IDS_BLOCKS = 256
IDS_SLOTS = 1 << 13
IDS_CHUNK = 16
IDS_CHUNKS = 1 << 15


# Survivor masks travel as int32 0/1 inside every sweep kernel: Mosaic
# cannot select between boolean vectors, and the level recurrence below is
# a select on the level index.  Masks leave the kernels as int8.
def _overlap_tile(q, mbr_tile):
    """(Q, 4) resident queries vs (4, BW) coordinate-major tile -> (Q, BW)
    int32 0/1.

    Works for float32 tiles and for uint16/uint8 compact tiles (tiles are
    cast to the query dtype — int32 for quantized sweeps — after the VMEM
    load, so HBM only ever streams the narrow form)."""
    if mbr_tile.dtype != q.dtype:
        mbr_tile = mbr_tile.astype(q.dtype)
    lx, ly, hx, hy = (mbr_tile[c:c + 1, :] for c in range(4))  # (1, BW)
    qlx, qly, qhx, qhy = (q[:, c:c + 1] for c in range(4))      # (Q, 1)
    ov = (lx <= qhx) & (qlx <= hx) & (ly <= qhy) & (qly <= hy)
    return ov.astype(jnp.int32)


def _act_formula(ov, parent_active, *, l, t, block_w, root_unconditional,
                 uncond_from):
    """The shared per-tile active-mask recurrence of every sweep kernel
    (int32 0/1 operands and result)."""
    if root_unconditional:
        # The pointer search always examines the root node (slot 0).
        col = jax.lax.broadcasted_iota(jnp.int32, ov.shape, 1)
        act0 = (t * block_w + col == 0).astype(jnp.int32)
    else:
        act0 = ov
    # Levels at or past ``uncond_from`` are FLAT appendices (the live-update
    # delta buffer, DESIGN.md §8): every slot is tested against the query
    # directly, with no parent gating — a linear scan fused into the same
    # launch as the hierarchical sweep.
    return jnp.where(
        l == 0, act0, jnp.where(l >= uncond_from, ov, parent_active & ov)
    )


def _gather_parents(mask, parent_row, *, onehot_gather):
    """``mask[:, parent_row]`` -> (Q, BW) int32 0/1 for an f32 0/1 mask
    (Q, M) and a (1, BW) int32 row of column indices into it."""
    if onehot_gather:
        # TPU path: the gather as a one-hot matmul on the MXU,
        # onehot[p, j] = (p == parent[j]) — no lane gather needed.
        iota = jax.lax.broadcasted_iota(
            jnp.int32, (mask.shape[1], parent_row.shape[1]), 0
        )
        onehot = (iota == parent_row).astype(jnp.float32)
        pa = jnp.dot(mask, onehot, preferred_element_type=jnp.float32)
    else:
        # Interpreter path: O(Q·BW) column gather instead of O(Q·M·BW).
        pa = jnp.take(mask, parent_row[0], axis=1)
    return (pa > 0.5).astype(jnp.int32)


def _sweep_kernel(
    q_ref,       # (Q, 4) f32, resident
    mbr_ref,     # (1, 4, BW) f32 tile of level l
    parent_ref,  # (1, 1, BW) i32 tile of level l
    act_ref,     # out (1, Q, BW) int8
    prev_ref,    # scratch (Q, W) f32 — level l-1 survivors
    cur_ref,     # scratch (Q, W) f32 — level l survivors
    *,
    block_w: int,
    root_unconditional: bool,
    onehot_gather: bool,
    uncond_from: int,
):
    l = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((t == 0) & (l > 0))
    def _roll():  # level finished: its survivors become the parent mask
        prev_ref[...] = cur_ref[...]

    ov = _overlap_tile(q_ref[...], mbr_ref[0])  # (Q, BW)
    parent_active = _gather_parents(
        prev_ref[...], parent_ref[0].astype(jnp.int32),  # uint16 if compact
        onehot_gather=onehot_gather,
    )
    act = _act_formula(
        ov, parent_active, l=l, t=t, block_w=block_w,
        root_unconditional=root_unconditional, uncond_from=uncond_from,
    )
    col = pl.multiple_of(t * block_w, block_w)
    cur_ref[:, pl.ds(col, block_w)] = act.astype(jnp.float32)
    act_ref[0] = act.astype(jnp.int8)


def _hier_sweep_kernel(
    q8_ref,      # (Q, 4) i32 — queries on the coarse uint8 grid
    q16_ref,     # (Q, 4) i32 — queries on the fine uint16 grid
    mbr8_ref,    # (1, 4, BW) u8 tile (level index clamped to < split)
    mbr16_ref,   # (1, 4, BW) u16 tile (level index clamped to >= split)
    parent_ref,  # (1, 1, BW)
    act_ref,     # out (1, Q, BW) int8
    prev_ref,    # scratch (Q, W) f32
    cur_ref,     # scratch (Q, W) f32
    *,
    block_w: int,
    split: int,
    root_unconditional: bool,
    onehot_gather: bool,
    uncond_from: int,
):
    """Two-segment sweep: coarse uint8 tiles for levels < ``split``, fine
    uint16 tiles after (DESIGN.md §12).  Both BlockSpec index maps clamp
    into their own segment, so each step fetches one narrow tile and the
    level selects which overlap result feeds the shared recurrence."""
    l = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((t == 0) & (l > 0))
    def _roll():
        prev_ref[...] = cur_ref[...]

    ov8 = _overlap_tile(q8_ref[...], mbr8_ref[0])
    ov16 = _overlap_tile(q16_ref[...], mbr16_ref[0])
    ov = jnp.where(l < split, ov8, ov16)
    parent_active = _gather_parents(
        prev_ref[...], parent_ref[0].astype(jnp.int32),
        onehot_gather=onehot_gather,
    )
    act = _act_formula(
        ov, parent_active, l=l, t=t, block_w=block_w,
        root_unconditional=root_unconditional, uncond_from=uncond_from,
    )
    col = pl.multiple_of(t * block_w, block_w)
    cur_ref[:, pl.ds(col, block_w)] = act.astype(jnp.float32)
    act_ref[0] = act.astype(jnp.int8)


def _stream_sweep_kernel(
    meta_ref,    # SMEM (1, 2, MB) i32 — window starts of this/next step
    q_ref,       # (Q, 4) VMEM, resident
    mbr_hbm,     # (L, 4, Wp) ANY (HBM) — streamed, never VMEM-resident
    par_hbm,     # (L, 1, Wp) ANY (HBM)
    act_ref,     # out (1, Q, BW) int8
    mask_hbm,    # out ANY (2, Q, Wp) f32 — ping-pong survivor masks (by
                 # level); an output only because Mosaic allocates scratch
                 # in VMEM/SMEM alone — the caller drops it
    mbr_buf,     # VMEM (2, 4, BW) — double-buffered tile landing slots
    par_buf,     # VMEM (2, 1, BW)
    win_buf,     # VMEM (2, Q, win_w) f32 — double-buffered parent windows
    cur_buf,     # VMEM (Q, BW) f32 — this tile's survivors, staged out
    sem_in,      # DMA sems (2 slots × {mbr, parent})
    sem_win,     # DMA sem — level-boundary window read
    sem_pre,     # DMA sem — next-step window prefetch
    sem_out,     # DMA sem — survivor write-back
    *,
    block_w: int,
    win_w: int,
    n_tiles: int,
    meta_block: int,
    root_unconditional: bool,
    onehot_gather: bool,
    uncond_from: int,
):
    """HBM-streaming twin of :func:`_sweep_kernel` (DESIGN.md §12).

    Copy/compute overlap: at linear step ``s = l·T + t`` the tile for step
    ``s+1`` is prefetched into VMEM slot ``(s+1) % 2`` while slot ``s % 2``
    is consumed — the double-buffer recurrence ``emit_pipeline`` would
    generate, written out so the survivor masks can ride an HBM scratch.
    Level ``l`` writes its survivors to ``mask_hbm[l % 2]`` and reads its
    parents from ``mask_hbm[(l+1) % 2]`` (= parity of ``l-1``), but only
    the ``win_w``-wide window starting at this step's ``meta`` offset that
    the tile's parent slots actually span, so VMEM never holds a
    full-width mask.  The offsets arrive in SMEM blocks of ``meta_block``
    tiles — row 0 for this step, row 1 for the next — so SMEM never holds
    the whole (levels × tiles) table.

    Dead-window skip: the window for step ``s+1`` is fetched (into the
    other ``win_buf`` slot) before step ``s+1``'s tile copies are issued.
    If no parent slot in it survived for ANY query, every activation in
    tile ``s+1`` would gather a zero — the tile is provably all-dead, so
    its MBR/parent DMA is skipped outright and only the zero write-back
    happens. Root, flat-delta, and level-boundary tiles are always
    fetched (the first tile of a level cannot read its window a step
    early: the previous level's last write-back may still be in flight)."""
    l = pl.program_id(0)
    t = pl.program_id(1)
    step = l * n_tiles + t
    slot = jax.lax.rem(step, 2)
    m = jax.lax.rem(t, meta_block)
    off = meta_ref[0, 0, m]    # < 0: statically-empty tile
    off1 = meta_ref[0, 1, m]   # the next step's; < 0 also past the end

    def tile_copies(li, ti, s):
        col = pl.multiple_of(ti * block_w, block_w)
        return (
            pltpu.make_async_copy(
                mbr_hbm.at[li, :, pl.ds(col, block_w)],
                mbr_buf.at[s],
                sem_in.at[s, 0],
            ),
            pltpu.make_async_copy(
                par_hbm.at[li, :, pl.ds(col, block_w)],
                par_buf.at[s],
                sem_in.at[s, 1],
            ),
        )

    def win_copy(li, o, s, sem):
        # Offsets are multiples of 128 lanes (``parent_windows``); a
        # negative one marks a statically-empty tile, whose copy is never
        # started — the clamp only keeps the descriptor in range.
        o = pl.multiple_of(jnp.maximum(o, 0), 128)
        return pltpu.make_async_copy(
            mask_hbm.at[jax.lax.rem(li + 1, 2), :, pl.ds(o, win_w)],
            win_buf.at[s],
            sem,
        )

    def gated_at(li):
        # Only hierarchical, non-root levels gate on the previous level's
        # survivors; flat delta levels and level 0 test unconditionally.
        return (li > 0) & (li < uncond_from)

    gated = gated_at(l)
    boundary = t == 0
    empty = off < 0

    @pl.when(step == 0)
    def _warmup():  # first tile has no previous step to prefetch it
        for c in tile_copies(l, t, slot):
            c.start()

    # Level-boundary window: read synchronously at this step (the mask of
    # level l-1 is complete once level l starts, but was not yet at the
    # previous step, when the boundary tile's copies were issued).
    bwin = win_copy(l, off, slot, sem_win)

    @pl.when(gated & boundary & ~empty)
    def _boundary_win():
        bwin.start()
        bwin.wait()

    # Prefetch for step s+1 with dead-window skip: fetch the next tile's
    # parent window first; tile copies are only issued if some parent
    # slot in it is still alive for some query (and never for
    # statically-empty tiles, at any level).
    nxt = step + 1
    l1 = jax.lax.div(nxt, n_tiles)
    t1 = jax.lax.rem(nxt, n_tiles)
    s1 = jax.lax.rem(nxt, 2)
    empty1 = off1 < 0
    skippable1 = gated_at(l1) & (t1 != 0)
    pwin = win_copy(l1, off1, s1, sem_pre)

    @pl.when(skippable1 & ~empty1)
    def _prefetch_win():
        pwin.start()
        pwin.wait()

    live1 = jnp.max(win_buf[s1]) > 0.5

    @pl.when(~empty1 & (live1 | ~skippable1))
    def _prefetch():  # overlap: next tile's copy rides this tile's compute
        for c in tile_copies(l1, t1, s1):
            c.start()

    # Wait for our own tile — unless the previous step skipped its DMA.
    # ``live`` re-reads the same window slot the skip decision used (it
    # is untouched in between), so the predicate matches exactly.
    live = jnp.max(win_buf[slot]) > 0.5
    fetched = ~empty & (live | ~gated | boundary)

    @pl.when(fetched)
    def _tile_wait():
        for c in tile_copies(l, t, slot):
            c.wait()

    ov = _overlap_tile(q_ref[...], mbr_buf[slot])  # (Q, BW)
    # Window-local parent slot.  Real slots are guaranteed in-window by
    # ``parent_windows``; padded slots may clamp to a garbage column, but
    # their sentinel MBRs make ``ov`` 0 so the AND discards it.  At
    # gated=False steps win_buf is stale/uninitialized — same argument:
    # the selected branch of ``_act_formula`` never reads parent_active.
    loc = jnp.clip(
        par_buf[slot].astype(jnp.int32) - jnp.maximum(off, 0), 0, win_w - 1
    )
    parent_active = _gather_parents(
        win_buf[slot], loc, onehot_gather=onehot_gather
    )
    act = _act_formula(
        ov, parent_active, l=l, t=t, block_w=block_w,
        root_unconditional=root_unconditional, uncond_from=uncond_from,
    )
    # A skipped statically-empty tile never DMA'd its buffers, so ``ov``
    # is stale garbage there — but its true activations are provably all
    # zero (sentinel MBRs; the root mask is slot 0 of tile 0), so force
    # exactly that.
    act = jnp.where(empty, 0, act)

    cur_buf[...] = act.astype(jnp.float32)
    out_copy = pltpu.make_async_copy(
        cur_buf,
        mask_hbm.at[jax.lax.rem(l, 2), :,
                    pl.ds(pl.multiple_of(t * block_w, block_w), block_w)],
        sem_out,
    )
    out_copy.start()
    out_copy.wait()
    act_ref[0] = act.astype(jnp.int8)


def parent_windows(
    parent,
    n_real,
    *,
    block_w: int,
    uncond_from: int | None = None,
    levels: int | None = None,
    win_unit: int = 128,
) -> Tuple[np.ndarray, int]:
    """Per-tile parent-window metadata for the streaming sweep.

    For every (level, tile) of the padded grid, the window
    ``[off, off + win_w)`` must cover the parent slots of the tile's real
    entries.  Computed on the host from the concrete schedule arrays
    (outside jit — the offsets feed the kernel through SMEM), with ONE
    static ``win_w`` (the max span over all tiles, rounded up to
    ``win_unit`` lanes and capped at the padded width, so adversarial
    orderings degrade to a full-width window rather than a wrong answer).

    Returns ``(win_off (levels, T) int32, win_w int)``.
    """
    parent = np.asarray(parent)
    n_real = np.asarray(n_real)
    n_levels, w = parent.shape
    if levels is None:
        levels = n_levels
    if uncond_from is None:
        uncond_from = n_levels
    pad = (-w) % block_w
    wp = w + pad
    n_tiles = wp // block_w
    big = np.iinfo(np.int64).max
    tmin = np.full((levels, n_tiles), big, np.int64)
    tmax = np.full((levels, n_tiles), -1, np.int64)
    gate_top = min(n_levels, uncond_from, len(n_real), levels)
    for l in range(1, gate_top):
        nr = int(n_real[l])
        p = parent[l].astype(np.int64)
        valid = np.arange(w) < nr
        lo = np.concatenate([np.where(valid, p, big), np.full(pad, big)])
        hi = np.concatenate([np.where(valid, p, -1), np.full(pad, -1)])
        tmin[l] = lo.reshape(n_tiles, block_w).min(axis=1)
        tmax[l] = hi.reshape(n_tiles, block_w).max(axis=1)
    # Window starts are aligned down to ``win_unit`` lanes: the chip's DMA
    # engine only slices the lane dimension at whole (8, 128) tiles.
    base = np.where(tmin == big, 0, tmin // win_unit * win_unit)
    spans = np.where(tmax >= tmin, tmax - base + 1, 1)
    span = max(1, int(spans.max()))
    win_w = min(wp, int(-(-span // win_unit)) * win_unit)
    win_w = max(win_w, min(wp, win_unit))
    off = np.clip(np.minimum(base, wp - win_w), 0, max(wp - win_w, 0))
    off = off.astype(np.int32)
    # Statically-empty tiles (every slot past n_real[l]) can never
    # activate — sentinel MBRs overlap nothing and the root mask is slot
    # 0 only — so mark them with off = -1: the streaming kernel skips
    # their DMA outright, at every level including root and flat ones.
    tidx = np.arange(n_tiles) * block_w
    for l in range(min(levels, n_levels, len(n_real))):
        off[l, tidx >= int(n_real[l])] = -1
    return np.ascontiguousarray(off), win_w


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_w", "root_unconditional", "interpret", "onehot_gather",
        "uncond_from", "stream", "win_w", "padded",
    ),
)
def level_sweep(
    queries: jnp.ndarray,   # (Q, 4) f32
    mbr_cm: jnp.ndarray,    # (L, 4, W) f32
    parent: jnp.ndarray,    # (L, W) i32
    *,
    block_w: int = 128,
    root_unconditional: bool = True,
    interpret: bool = False,
    onehot_gather: bool | None = None,
    uncond_from: int | None = None,
    stream: bool = False,
    win_off: jnp.ndarray | None = None,   # (L, T) i32, see parent_windows
    win_w: int | None = None,
    padded: bool = False,
) -> jnp.ndarray:
    """Run the fused sweep; returns the (L, Q, W) per-level active mask,
    or with ``padded=True`` the kernel's own int8 0/1 mask, (L, Q, W)
    padded to whole tiles with slots that are never active.

    ``uncond_from`` marks the first FLAT level: levels ``>= uncond_from``
    skip the parent gate and test every slot against the query directly —
    how the live-update delta buffer rides the same launch (DESIGN.md §8).
    ``None`` (the default) keeps the whole sweep hierarchical.

    ``stream=True`` runs the HBM-streaming kernel instead of the
    VMEM-resident one (bit-identical masks, DESIGN.md §12); it requires
    the ``(win_off, win_w)`` pair from :func:`parent_windows` computed
    with the same ``block_w`` and ``uncond_from``.
    """
    levels, _, w = mbr_cm.shape
    q = queries.shape[0]
    pad = (-w) % block_w
    if pad:
        never = (
            NEVER_MBR
            if jnp.issubdtype(mbr_cm.dtype, jnp.floating)
            else Q_NEVER_MBR.astype(mbr_cm.dtype)
        )
        mbr_cm = jnp.concatenate(
            [mbr_cm,
             jnp.broadcast_to(jnp.asarray(never)[None, :, None],
                              (levels, 4, pad))],
            axis=2,
        )
        parent = jnp.concatenate(
            [parent, jnp.zeros((levels, pad), parent.dtype)], axis=1
        )
    wp = w + pad
    n_tiles = wp // block_w
    grid = (levels, n_tiles)
    if onehot_gather is None:
        # The MXU one-hot matmul is the native TPU lowering; the column
        # gather is cheaper (O(Q·W) vs O(Q·W²/BW)) where gathers are free.
        onehot_gather = not interpret
    uncond = levels if uncond_from is None else uncond_from
    parent = parent.reshape(levels, 1, wp)  # a (1, BW) parent row per tile
    if not stream:
        kernel = functools.partial(
            _sweep_kernel,
            block_w=block_w,
            root_unconditional=root_unconditional,
            onehot_gather=onehot_gather,
            uncond_from=uncond,
        )
        act = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((q, 4), lambda l, t: (0, 0)),
                pl.BlockSpec((1, 4, block_w), lambda l, t: (l, 0, t)),
                pl.BlockSpec((1, 1, block_w), lambda l, t: (l, 0, t)),
            ],
            out_specs=pl.BlockSpec((1, q, block_w), lambda l, t: (l, 0, t)),
            out_shape=jax.ShapeDtypeStruct((levels, q, wp), jnp.int8),
            scratch_shapes=[
                pltpu.VMEM((q, wp), jnp.float32),
                pltpu.VMEM((q, wp), jnp.float32),
            ],
            compiler_params=COMPILER_PARAMS,
            interpret=interpret,
        )(queries, mbr_cm, parent)
        return act if padded else act[:, :, :w] != 0
    if win_off is None or win_w is None:
        raise ValueError(
            "stream=True needs (win_off, win_w) from parent_windows()"
        )
    win_w = min(win_w, wp)
    meta, meta_block = _stream_meta(jnp.asarray(win_off, jnp.int32))
    kernel = functools.partial(
        _stream_sweep_kernel,
        block_w=block_w,
        win_w=win_w,
        n_tiles=n_tiles,
        meta_block=meta_block,
        root_unconditional=root_unconditional,
        onehot_gather=onehot_gather,
        uncond_from=uncond,
    )
    act, _ = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2, meta_block),
                         lambda l, t: (l, 0, t // meta_block),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((q, 4), lambda l, t: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, q, block_w), lambda l, t: (l, 0, t)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((levels, q, wp), jnp.int8),
            jax.ShapeDtypeStruct((2, q, wp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 4, block_w), mbr_cm.dtype),
            pltpu.VMEM((2, 1, block_w), parent.dtype),
            pltpu.VMEM((2, q, win_w), jnp.float32),
            pltpu.VMEM((q, block_w), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(meta, queries, mbr_cm, parent)
    return act if padded else act[:, :, :w] != 0


def _stream_meta(win_off):
    """(L, T) window starts -> ((L, 2, Tp) table, block) for the streamed
    sweep's SMEM blocks: row 0 holds each step's own window start, row 1
    the next step's (-1 past the last step), padded with -1 to a whole
    number of ``block``-tile SMEM blocks."""
    levels, n_tiles = win_off.shape
    flat_off = win_off.reshape(-1)
    nxt = jnp.concatenate([flat_off[1:], jnp.full((1,), -1, jnp.int32)])
    meta = jnp.stack([win_off, nxt.reshape(levels, n_tiles)], axis=1)
    block = min(n_tiles, STREAM_META_BLOCK)
    pad = (-n_tiles) % block
    if pad:
        meta = jnp.pad(meta, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
    return meta, block


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_w", "split", "root_unconditional", "interpret",
        "onehot_gather", "uncond_from",
    ),
)
def level_sweep_hier(
    q8: jnp.ndarray,      # (Q, 4) i32 — coarse-grid queries
    q16: jnp.ndarray,     # (Q, 4) i32 — fine-grid queries
    mbr8: jnp.ndarray,    # (split, 4, W) u8
    mbr16: jnp.ndarray,   # (L - split, 4, W) u16
    parent: jnp.ndarray,  # (L, W)
    *,
    split: int,
    block_w: int = 128,
    root_unconditional: bool = True,
    interpret: bool = False,
    onehot_gather: bool | None = None,
    uncond_from: int | None = None,
) -> jnp.ndarray:
    """Hierarchical two-grid sweep: uint8 tiles for levels < ``split``,
    uint16 after; returns the (L, Q, W) active mask (DESIGN.md §12)."""
    l8 = mbr8.shape[0]
    l16 = mbr16.shape[0]
    levels = l8 + l16
    assert split == l8 and split >= 1
    w = mbr16.shape[2]
    q = q16.shape[0]
    pad = (-w) % block_w
    if pad:
        mbr8 = jnp.concatenate(
            [mbr8,
             jnp.broadcast_to(jnp.asarray(Q8_NEVER_MBR)[None, :, None],
                              (l8, 4, pad))],
            axis=2,
        )
        mbr16 = jnp.concatenate(
            [mbr16,
             jnp.broadcast_to(jnp.asarray(Q_NEVER_MBR)[None, :, None],
                              (l16, 4, pad))],
            axis=2,
        )
        parent = jnp.concatenate(
            [parent, jnp.zeros((levels, pad), parent.dtype)], axis=1
        )
    wp = w + pad
    grid = (levels, wp // block_w)
    if onehot_gather is None:
        onehot_gather = not interpret
    kernel = functools.partial(
        _hier_sweep_kernel,
        block_w=block_w,
        split=split,
        root_unconditional=root_unconditional,
        onehot_gather=onehot_gather,
        uncond_from=levels if uncond_from is None else uncond_from,
    )
    act = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q, 4), lambda l, t: (0, 0)),
            pl.BlockSpec((q, 4), lambda l, t: (0, 0)),
            # Each segment's index map clamps into its own level range, so
            # out-of-segment steps fetch a (discarded) boundary tile
            # instead of reading past the array.
            pl.BlockSpec(
                (1, 4, block_w),
                lambda l, t: (jnp.minimum(l, split - 1), 0, t),
            ),
            pl.BlockSpec(
                (1, 4, block_w),
                lambda l, t: (jnp.maximum(l - split, 0), 0, t),
            ),
            pl.BlockSpec((1, 1, block_w), lambda l, t: (l, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, q, block_w), lambda l, t: (l, 0, t)),
        out_shape=jax.ShapeDtypeStruct((levels, q, wp), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((q, wp), jnp.float32),
            pltpu.VMEM((q, wp), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(q8, q16, mbr8, mbr16, parent.reshape(levels, 1, wp))
    return act[:, :, :w] != 0


def _quantize_queries(queries, origin, inv_cell, cells: int):
    """Outward query quantization onto a schedule grid (floor lo, ceil hi,
    clip into the domain) — shared by the compact and hier sweeps."""
    t = (queries - origin[None, :]) * inv_cell[None, :]
    qq = jnp.concatenate([jnp.floor(t[:, :2]), jnp.ceil(t[:, 2:])], axis=1)
    return jnp.clip(qq, 0.0, float(cells)).astype(jnp.int32)


def _hits_epilogue(act, queries, gate_mbr, obj_level, obj_slot, obj_id,
                   n_objects: int, alive=None, shared_mbr=None,
                   n_shared=None):
    """Shared jnp epilogue: (L, Q, W) active mask -> (hits, visits,
    confirm).

    Per-level access counts: padded slots carry sentinel MBRs and are
    never active, so a plain sum counts exactly the visited real nodes.
    Entry e hits iff its holding node is active and (when ``gate_mbr`` is
    given) its exact float32 MBR overlaps the query — the confirming pass
    of the quantized paths and the object-MBR test of tree schedules are
    the same operation.  ``shared_mbr`` instead tests a pyramid's leading
    entries, the ``n_shared`` that share their deepest group up to a
    rounded width (:func:`repro.core.flat.confirm_shared`); ``confirm`` is
    then its (Q, 2) candidates/hits sums, else None."""
    visits = jnp.transpose(act.sum(axis=2, dtype=jnp.int32))  # (Q, L)
    hit = jnp.transpose(act[obj_level, :, obj_slot])           # (Q, E)
    confirm = None
    if gate_mbr is not None:
        hit = hit & _overlaps(gate_mbr[None, :, :], queries[:, None, :])
    elif shared_mbr is not None:
        hit, confirm = confirm_shared(hit, queries, shared_mbr,
                                      n_shared=n_shared)
    q = queries.shape[0]
    hits = jnp.zeros((q, max(n_objects, 1)), jnp.bool_)
    hits = hits.at[:, obj_id].max(hit)
    if alive is not None:
        # Tombstone mask: deleted ids drop out here, in the same jit program.
        hits = hits & alive[None, :]
    return hits, visits, confirm


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_objects", "block_w", "root_unconditional", "test_object_mbr",
        "confirm_w", "interpret", "stream", "win_w",
    ),
)
def _fused_search(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id,
    n_shared=None,
    *,
    n_objects: int,
    block_w: int,
    root_unconditional: bool,
    test_object_mbr: bool,
    interpret: bool,
    confirm_w: int = 0,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Sweep + epilogue of a :class:`LevelSchedule`; returns ``(hits,
    visits, confirm)``.  ``confirm_w`` is the static width of a pyramid's
    confirmed prefix (:func:`repro.core.flat.confirm_width`) and
    ``n_shared`` the scalar count of its shared entries; ``confirm`` is
    None where ``confirm_w`` is 0."""
    act = level_sweep(
        queries, mbr_cm, parent,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )  # (L, Q, W)
    return _hits_epilogue(
        act, queries, obj_mbr if test_object_mbr else None,
        obj_level, obj_slot, obj_id, n_objects,
        shared_mbr=obj_mbr[:confirm_w] if confirm_w else None,
        n_shared=n_shared,
    )


def ids_caps(staged: "StagedSchedule", n_launch: int, block_w: int
             ) -> Tuple[int, int, int, int]:
    """The static capacities ``(blocks, slots, chunk, chunks)`` of an id
    launch of ``n_launch`` queries over ``staged`` with tiles of
    ``block_w`` slots: each ceiling of the module cut to what such a
    launch can hold, and the id lanes to no more than the entries of the
    ``(n_launch, n_objects)`` mask they replace."""
    width, n = staged.source.width, staged.statics["n_objects"]
    return (min(IDS_BLOCKS, -(-width // block_w)),
            min(IDS_SLOTS, n_launch * width),
            IDS_CHUNK,
            min(IDS_CHUNKS, -(-n_launch * n // IDS_CHUNK)))


def _search(cum, target, lo, hi, steps: int):
    """Per element of ``target``: the first ``j`` in ``[lo, hi)`` with
    ``cum[j] > target``, else ``hi``, over a flat non-decreasing int32
    ``cum``; a branchless binary search of ``steps`` halvings, one
    gather each (``2**steps >= hi - lo``)."""
    last = cum.shape[0] - 1

    def halve(k, pos):
        nxt = pos + jnp.left_shift(1, steps - 1 - k)
        ok = (nxt <= hi) & (cum[jnp.minimum(nxt - 1, last)] <= target)
        return jnp.where(ok, nxt, pos)

    return jax.lax.fori_loop(0, steps, halve,
                             jnp.broadcast_to(lo, target.shape))


def _steps(n: int) -> int:
    return max(int(n).bit_length(), 1)


def _ids_fit(act, slot_start, *, block: int, caps):
    """Whether the padded (Q, Wp) deepest active mask ``act`` fits every
    capacity of :func:`_ids_epilogue`: its non-empty column blocks, its
    active slots and their member chunks, each counted by one reduction
    over ``act``."""
    n_blk, n_slot, chunk, n_chunk = caps
    q, wp = act.shape
    size = slot_start[1:] - slot_start[:-1]
    n_ch = jnp.pad((size + chunk - 1) // chunk, (0, wp - size.shape[0]))
    on = act.astype(jnp.int32)
    blocks = (on.reshape(q, wp // block, block).max(axis=(0, 2)) > 0).sum()
    return ((blocks <= n_blk) & (on.sum() <= n_slot)
            & ((on * n_ch[None, :]).sum() <= n_chunk))


def _ids_epilogue(act, queries, members, *, block: int, confirm_w: int,
                  caps):
    """The deepest level's padded (Q, Wp) int8 active mask -> hit ids
    (DESIGN.md §12), where :func:`_ids_fit` holds.

    The non-empty column blocks of ``block`` slots are compacted and
    gathered whole, then their active slots listed in query-major, slot
    order, and each slot expanded to its members (``members``, the table
    :func:`_member_table` stages: ``slot_start`` and the member-ordered
    ids and four MBR coordinates) in chunks of ``chunk`` lanes.  Where
    ``confirm_w`` says that some slot holds more than one entry, their
    members get the object test and are counted in ``confirm`` (Q, 2),
    the candidates and hits of :func:`confirm_shared`: in a pyramid the
    entries of such slots are exactly its ``n_shared`` first, and a
    slot's one entry meets a query where its slot does
    (:func:`_member_table`).  No stage scatters or sorts: each compaction
    is a prefix sum and a binary search into it.

    Returns ``(confirm, offsets (Q + 1,), ids (chunks × chunk,))``: query
    ``q``'s id lanes are ``ids[offsets[q]:offsets[q + 1]]``, -1 where a
    lane holds no hit."""
    slot_start, mem_id, *mem_mbr = members
    n_blk, n_slot, chunk, n_chunk = caps
    q, wp = act.shape
    nb = wp // block
    col = act.reshape(q, nb, block).astype(jnp.int32).sum(axis=(0, 2))
    ne_cum = jnp.cumsum(col > 0, dtype=jnp.int32)
    blk = _search(ne_cum, jnp.arange(n_blk, dtype=jnp.int32), 0, nb,
                  _steps(nb))                             # k-th non-empty
    blk_ok = blk < nb
    blk = jnp.where(blk_ok, blk, 0)
    cols = jax.vmap(lambda b: jax.lax.dynamic_slice(
        act, (0, b * block), (q, block)))(blk)           # (n_blk, Q, block)
    cols = jnp.where(blk_ok[:, None, None], cols, 0)
    row_cum = jnp.cumsum(cols, axis=2, dtype=jnp.int32).reshape(-1)
    row_cnt = jnp.transpose(cols.sum(axis=2, dtype=jnp.int32)).reshape(-1)
    row_end = jnp.cumsum(row_cnt)                         # rows (q, k)
    s = jnp.arange(n_slot, dtype=jnp.int32)
    r = _search(row_end, s, 0, q * n_blk, _steps(q * n_blk))
    slot_ok = r < q * n_blk
    r = jnp.minimum(r, q * n_blk - 1)
    slot_q, k = r // n_blk, r % n_blk
    rank = s - (row_end[r] - row_cnt[r])
    base = (k * q + slot_q) * block
    j = _search(row_cum, rank, base, base + block, _steps(block)) - base
    slot = jnp.where(slot_ok, blk[k] * block + j, 0)
    slot_q = jnp.where(slot_ok, slot_q, q)
    first = jnp.where(slot_ok, slot_start[slot], 0)
    size = jnp.where(slot_ok, slot_start[slot + 1], 0) - first

    n_ch = (size + chunk - 1) // chunk
    ch_end = jnp.cumsum(n_ch)
    c = jnp.arange(n_chunk, dtype=jnp.int32)
    i = _search(ch_end, c, 0, n_slot, _steps(n_slot))    # c-th chunk's slot
    i = jnp.minimum(i, n_slot - 1)
    off = (c - (ch_end[i] - n_ch[i])) * chunk            # in its slot
    lane = off[:, None] + jnp.arange(chunk)
    lane_ok = (lane < size[i][:, None]) & (c < ch_end[-1])[:, None]
    pos = jnp.where(lane_ok, first[i][:, None] + lane, 0).reshape(-1)

    hit = lane_ok
    confirm = None
    if confirm_w:
        box = queries[jnp.minimum(slot_q[i], q - 1)][:, None, :]
        ov = _overlaps(
            jnp.stack([x[pos].reshape(n_chunk, chunk) for x in mem_mbr], -1),
            box)
        tested = lane_ok & (size[i] > 1)[:, None]
        hit = lane_ok & ~(tested & ~ov)
        per_q = slot_q[i][:, None] == jnp.arange(q)
        confirm = jnp.stack(
            [jnp.where(per_q, x.sum(axis=1, dtype=jnp.int32)[:, None],
                       0).sum(axis=0)
             for x in (tested, tested & ov)], axis=1)
    ids = jnp.where(hit.reshape(-1), mem_id[pos], -1)
    q_chunks = jnp.where(slot_q[:, None] == jnp.arange(q), n_ch[:, None],
                         0).sum(axis=0)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(q_chunks) * chunk])
    return confirm, offsets.astype(jnp.int32), ids


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_objects", "block_w", "root_unconditional", "confirm_w",
        "interpret", "caps", "stream", "win_w",
    ),
)
def _fused_search_ids(
    queries, members, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id,
    n_shared=None,
    *,
    n_objects: int,
    block_w: int,
    root_unconditional: bool,
    confirm_w: int,
    interpret: bool,
    caps: Tuple[int, int, int, int],
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Sweep + epilogue of a pyramid schedule (every entry at the deepest
    level) that returns hit ids where they fit the capacities ``caps``
    and the dense mask where they do not, in one program: ``(visits,
    confirm, offsets, overflow, ids, hits)``.  Without ``overflow`` the
    ids (:func:`_ids_epilogue`) hold the answer and ``hits`` is all
    false; with it ``hits`` is :func:`_fused_search`'s and the ids are
    empty.  ``visits`` and ``confirm`` are the same either way."""
    act = level_sweep(
        queries, mbr_cm, parent,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
        padded=True,
    )  # (L, Q, Wp) int8
    visits = jnp.transpose(act.sum(axis=2, dtype=jnp.int32))
    q, w = queries.shape[0], mbr_cm.shape[2]
    lanes = caps[2] * caps[3]

    def from_ids(_):
        confirm, offsets, ids = _ids_epilogue(
            act[-1], queries, members, block=block_w, confirm_w=confirm_w,
            caps=caps)
        return confirm, offsets, ids, jnp.zeros((q, max(n_objects, 1)),
                                                jnp.bool_)

    def from_mask(_):
        hits, _, confirm = _hits_epilogue(
            act[:, :, :w] != 0, queries, None, obj_level, obj_slot, obj_id,
            n_objects, shared_mbr=obj_mbr[:confirm_w] if confirm_w else None,
            n_shared=n_shared)
        return (confirm, jnp.zeros((q + 1,), jnp.int32),
                jnp.full((lanes,), -1, jnp.int32), hits)

    fit = _ids_fit(act[-1], members[0], block=block_w, caps=caps)
    confirm, offsets, ids, hits = jax.lax.cond(fit, from_ids, from_mask, None)
    return visits, confirm, offsets, ~fit, ids, hits


@jax.jit
def _member_table(mbr_last, obj_mbr, obj_slot, obj_id, n_shared):
    """A pyramid's member table, built on the device (DESIGN.md §12).

    Returns ``(ok, (slot_start, ids, lx, ly, hx, hy))``: ``slot_start``
    (W + 1,) is the offset of each deepest slot's first member, then the
    ids and the four MBR coordinates of the entries stably sorted by
    slot, each (E,).  ``ok`` says whether the table answers as the dense
    epilogue does: the ``n_shared`` first entries exactly those whose
    slot holds another, and each other slot's box (``mbr_last``, (4, W))
    its one entry's box, so that its group test is the object test."""
    width = mbr_last.shape[1]
    order = jnp.argsort(obj_slot, stable=True)
    slot_start = jnp.searchsorted(
        obj_slot[order], jnp.arange(width + 1, dtype=obj_slot.dtype),
        side="left", method="sort").astype(jnp.int32)
    alone = (slot_start[1:] - slot_start[:-1])[obj_slot] == 1
    ok = jnp.all(alone == (jnp.arange(obj_slot.shape[0]) >= n_shared))
    ok &= jnp.all(~alone[:, None] | (mbr_last[:, obj_slot].T == obj_mbr))
    mbr = obj_mbr[order]
    return ok, (slot_start, obj_id[order], *(mbr[:, c] for c in range(4)))


@dataclasses.dataclass(frozen=True, eq=False)
class StagedSchedule:
    """A schedule with its arrays on the device (DESIGN.md §12).

    Built by :func:`stage_schedule`.  ``arrays`` are the schedule
    operands of the fused search of ``precision`` in its argument order,
    ``statics`` its static arguments (``n_objects``,
    ``root_unconditional``, ``test_object_mbr`` or the grid's cells).
    ``source`` is the host schedule it was staged from: the streamed
    sweep's parent windows are planned from its parents once per tile
    width (:meth:`windows`), a pyramid's member table once
    (:meth:`members`), and the eager launch report reads it.  A holder
    that keeps this form across launches copies only its queries to the
    device per launch.
    """

    source: LevelSchedule | QuantizedSchedule
    precision: str
    arrays: Tuple[jax.Array, ...]
    statics: dict
    _windows: dict = dataclasses.field(default_factory=dict, repr=False)
    _members: Tuple[jax.Array, ...] | bool | None = dataclasses.field(
        default=None, repr=False)

    def windows(self, block_w: int) -> Tuple[jax.Array, int]:
        """``(win_off on the device, win_w)`` of :func:`parent_windows`
        at ``block_w``, planned on the first call for that width."""
        plan = self._windows.get(block_w)
        if plan is None:
            src = self.source
            if self.precision == "float32":
                parent, n_real = src.parent, src.n_real
            else:
                parent, n_real = src.parent_q, src.base.n_real
            win_off, win_w = parent_windows(parent, n_real, block_w=block_w)
            plan = self._windows[block_w] = (to_device(win_off), win_w)
        return plan

    def members(self) -> Tuple[jax.Array, ...] | None:
        """The member table of :func:`_member_table`, built on the device
        on the first call, for a pyramid whose table answers as its dense
        epilogue does, and which can therefore return hit ids
        (:func:`scan_staged_ids`, DESIGN.md §12): float32, every entry at
        the deepest level, no object test of its own, the shared entries
        first.  None for any other schedule."""
        if self._members is None:
            src, table = self.source, False
            if (self.precision == "float32" and not src.test_object_mbr
                    and np.all(np.asarray(src.obj_level) == src.levels - 1)):
                mbr_cm, _, obj_mbr, _, obj_slot, obj_id = self.arrays[:6]
                with _obs_trace.stage("engine.prepare", "prepare_s"):
                    ok, cols = _member_table(mbr_cm[-1], obj_mbr, obj_slot,
                                             obj_id, np.int32(src.n_shared))
                    if jax.device_get(ok):
                        table = cols
            object.__setattr__(self, "_members", table)
        return self._members or None


def stage_schedule(schedule, precision: str = "float32") -> StagedSchedule:
    """Copy a host schedule's arrays to the device once.

    ``schedule`` is a :class:`LevelSchedule` for ``precision="float32"``
    and a :class:`QuantizedSchedule` for ``"compact"`` and ``"compact8"``
    (the latter from ``quantize_schedule(..., upper8=True)``).  The copy
    is an ``engine.prepare`` stage; its bytes go to ``h2d_bytes`` and
    each call adds 1 to ``schedule_stagings`` (DESIGN.md §13).
    """
    if precision == "float32":
        confirm_w = confirm_width(schedule.n_shared,
                                  schedule.obj_id.shape[0])
        arrays = (schedule.mbr_cm, schedule.parent, schedule.obj_mbr,
                  schedule.obj_level, schedule.obj_slot, schedule.obj_id)
        if confirm_w:  # the count is an operand: one program per width
            arrays += (np.int32(schedule.n_shared),)
        statics = dict(n_objects=schedule.n_objects,
                       root_unconditional=schedule.root_unconditional,
                       test_object_mbr=schedule.test_object_mbr,
                       confirm_w=confirm_w)
    elif precision in ("compact", "compact8"):
        base = schedule.base
        objs = (schedule.confirm_mbr, base.obj_level, base.obj_slot,
                base.obj_id, schedule.origin, schedule.inv_cell)
        statics = dict(n_objects=schedule.n_objects, cells=schedule.cells,
                       root_unconditional=base.root_unconditional)
        if precision == "compact":
            arrays = (schedule.mbr_q, schedule.parent_q) + objs
        else:
            if not schedule.hierarchical and schedule.levels > 1:
                raise ValueError(
                    "pyramid_scan_compact8 needs quantize_schedule(..., "
                    "upper8=True)"
                )
            split = schedule.split
            mbr_q8 = schedule.mbr_q8
            if mbr_q8 is None:
                mbr_q8 = np.zeros((0, 4, schedule.width), np.uint8)
            inv_cell8 = schedule.inv_cell8
            if inv_cell8 is None:
                inv_cell8 = schedule.inv_cell
            arrays = ((mbr_q8, schedule.mbr_q[split:], schedule.parent_q)
                      + objs + (inv_cell8,))
            statics.update(cells8=schedule.cells8, split=split)
    else:
        raise ValueError(
            f"unknown precision {precision!r}; expected 'float32', "
            f"'compact' or 'compact8'"
        )
    with _obs_trace.stage("engine.prepare", "prepare_s"):
        staged = StagedSchedule(
            schedule, precision, tuple(to_device(a) for a in arrays), statics
        )
    _obs_trace.add("schedule_stagings", 1)
    return staged


def _as_staged(schedule, precision: str) -> StagedSchedule:
    """``schedule`` itself if already staged for ``precision``, else a
    staging of it for this one call."""
    if not isinstance(schedule, StagedSchedule):
        return stage_schedule(schedule, precision)
    if schedule.precision != precision:
        raise ValueError(
            f"schedule staged for precision {schedule.precision!r}, "
            f"not {precision!r}"
        )
    return schedule


def _launch_report(staged, queries, *, block_w, stream, win_off, win_w):
    if staged.precision == "compact8":
        return _obs_counters.scan_report_compact8(
            staged.source, queries, block_w=block_w)
    report = (_obs_counters.scan_report_float32
              if staged.precision == "float32"
              else _obs_counters.scan_report_compact)
    return report(staged.source, queries, block_w=block_w, stream=stream,
                  win_off=win_off, win_w=win_w)


def scan_staged(
    staged: StagedSchedule,
    queries,
    *,
    block_w: int = 128,
    interpret: bool = False,
    stream: bool = False,
    pad_to: int | None = None,
):
    """One fused launch over a :class:`StagedSchedule` of any precision.

    Returns device ``(hits, visits, confirm)``; ``confirm`` is the (Q, 2)
    object-test sums of a pyramid's shared entries (float32 schedules
    with ``n_shared``), else None.  ``pad_to`` pads a batch of fewer
    queries up to that many with :data:`NEVER_MBR` rows, which meet no
    object: inverted and infinitely far out at float32, and still
    inverted once quantized outward (lo = the grid's last cell, hi = 0),
    so at compact precision they meet only a node spanning the whole
    grid.  Every batch size thus launches one program (the streamed
    sweep compiles for whole query blocks only); the outputs keep the
    padded rows, and the launch report sees the real queries alone.
    """
    precision = staged.precision
    with _obs_trace.stage("engine.prepare", "prepare_s"):
        queries, kwargs = _launch_inputs(staged, queries, block_w=block_w,
                                         stream=stream, pad_to=pad_to)
        run = {"float32": _fused_search, "compact": _fused_search_compact,
               "compact8": _fused_search_compact8}[precision]
        out = run(
            queries,
            *staged.arrays,
            **staged.statics,
            block_w=block_w,
            interpret=interpret,
            **kwargs,
        )
    return out if precision == "float32" else (*out, None)


def _launch_inputs(staged, queries, *, block_w, stream, pad_to):
    """The ``engine.prepare`` work shared by every launch over a staged
    schedule: the parent windows, the eager launch report, the queries
    padded to ``pad_to`` and staged.  Returns ``(queries on the device,
    the sweep's keyword arguments)``."""
    win_off, win_w = staged.windows(block_w) if stream else (None, None)
    if _obs_counters.collecting():  # side channel: eager wrappers only
        _obs_counters.emit(_launch_report(
            staged, queries, block_w=block_w, stream=stream,
            win_off=win_off, win_w=win_w))
    pad = 0 if pad_to is None else max(pad_to - queries.shape[0], 0)
    if pad:
        queries = np.concatenate([np.asarray(queries, np.float32),
                                  np.broadcast_to(NEVER_MBR, (pad, 4))])
    kwargs = {} if staged.precision == "compact8" else dict(
        stream=stream, win_off=win_off, win_w=win_w)
    return to_device(queries, jnp.float32), kwargs


def scan_staged_ids(
    staged: StagedSchedule,
    queries,
    *,
    block_w: int = 128,
    interpret: bool = False,
    stream: bool = False,
    pad_to: int | None = None,
    caps: Tuple[int, int, int, int] | None = None,
):
    """One launch over a pyramid's :class:`StagedSchedule` (one whose
    :meth:`~StagedSchedule.members` is not None) that returns hit ids in
    place of the dense mask where they fit (DESIGN.md §12): device
    ``(visits, confirm, offsets, overflow, ids, hits)``.  Query ``q``'s
    id lanes are ``ids[offsets[q]:offsets[q + 1]]``, -1 where a lane
    holds no hit.  Where the launch needs more than a capacity of
    ``caps`` (default :func:`ids_caps`) ``overflow`` is set, the ids are
    empty and ``hits`` holds :func:`scan_staged`'s dense mask, else it is
    all false.  ``visits`` and ``confirm`` are :func:`scan_staged`'s.
    Padding as in :func:`scan_staged`."""
    with _obs_trace.stage("engine.prepare", "prepare_s"):
        queries, kwargs = _launch_inputs(staged, queries, block_w=block_w,
                                         stream=stream, pad_to=pad_to)
        statics = dict(staged.statics)
        del statics["test_object_mbr"]
        return _fused_search_ids(
            queries,
            staged.members(),
            *staged.arrays,
            **statics,
            block_w=block_w,
            interpret=interpret,
            caps=caps or ids_caps(staged, queries.shape[0], block_w),
            **kwargs,
        )


def pyramid_scan(
    schedule: LevelSchedule | StagedSchedule,
    queries,
    *,
    block_w: int = 128,
    interpret: bool = False,
    stream: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused region search over a :class:`LevelSchedule`.

    Returns ``(hits, visits)``: hits (Q, n_objects) bool object mask and
    visits (Q, L) int32 per-level access counts — both identical to the
    host pointer search (tree schedules) / the pyramid's numpy sweep
    (``index.backends.schedule_region_numpy``).  ONE kernel launch
    regardless of tree height.  ``stream=True`` uses the HBM-streaming
    kernel (DESIGN.md §12) — bit-identical results, VMEM bounded by the
    tile/window working set.

    ``schedule`` is a host :class:`LevelSchedule`, staged to the device
    for this call alone, or its :func:`stage_schedule` form, which a
    caller keeps across launches: then the ``engine.prepare`` stage
    stages only the queries, and plans the parent windows only on the
    first streamed launch at each ``block_w``.
    """
    return scan_staged(
        _as_staged(schedule, "float32"), queries, block_w=block_w,
        interpret=interpret, stream=stream,
    )[:2]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_objects", "cells", "block_w", "root_unconditional", "interpret",
        "stream", "win_w",
    ),
)
def _fused_search_compact(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell,
    *,
    n_objects: int,
    cells: int,
    block_w: int,
    root_unconditional: bool,
    interpret: bool,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Fused sweep over uint16 tiles + exact float32 confirming pass.

    Queries are quantized OUTWARD onto the schedule's grid (lo floor, hi
    ceil, clipped to the domain — node boxes never extend past it), so
    the integer sweep's survivors are a superset of the exact sweep's.
    The confirming pass intersects them with the exact ``confirm_mbr``
    overlap, which by MBR nesting implies the full exact ancestor chain:
    hit sets come out bit-identical to :func:`_fused_search`
    (tests/test_quantized.py).  ``visits`` counts the accesses this path
    actually performed — the conservative sweep may touch slightly more
    nodes per level than the exact one (DESIGN.md §7).
    """
    qq = _quantize_queries(queries, origin, inv_cell, cells)
    act = level_sweep(
        qq, mbr_q, parent_q,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )  # (L, Q, W) candidate mask, superset of the exact active mask
    return _hits_epilogue(
        act, queries, confirm_mbr, obj_level, obj_slot, obj_id, n_objects
    )[:2]


def pyramid_scan_compact(
    qsched: QuantizedSchedule | StagedSchedule,
    queries,
    *,
    block_w: int = 128,
    interpret: bool = False,
    stream: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused region search over a :class:`QuantizedSchedule`: half the
    streamed bytes per tile, hit sets bit-identical to the float32 path;
    ``visits`` reports the compact sweep's own (conservative) accesses.
    ``qsched`` may be its ``stage_schedule(qsched, "compact")`` form, as
    in :func:`pyramid_scan`."""
    return scan_staged(
        _as_staged(qsched, "compact"), queries, block_w=block_w,
        interpret=interpret, stream=stream,
    )[:2]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_objects", "cells", "cells8", "split", "block_w",
        "root_unconditional", "interpret",
    ),
)
def _fused_search_compact8(
    queries, mbr_q8, mbr_q16, parent_q, confirm_mbr, obj_level, obj_slot,
    obj_id, origin, inv_cell, inv_cell8,
    *,
    n_objects: int,
    cells: int,
    cells8: int,
    split: int,
    block_w: int,
    root_unconditional: bool,
    interpret: bool,
):
    """Hierarchically quantized sweep: uint8 coarse tiles for the upper
    ``split`` levels, uint16 fine tiles below, one launch (DESIGN.md §12).

    Conservativity is per-level and grid-independent: both grids round
    node boxes AND queries outward, so each level's candidate mask is a
    superset of the exact sweep's regardless of cell size, and the exact
    confirming pass keeps hit sets bit-identical.  Only ``visits`` may
    inflate on the coarse levels (those are exactly the levels whose fat
    MBRs make extra candidates cheap — the skip-quadtree intuition)."""
    qq16 = _quantize_queries(queries, origin, inv_cell, cells)
    if split == 0:  # degenerate (single-level) schedule: plain compact
        act = level_sweep(
            qq16, mbr_q16, parent_q,
            block_w=block_w,
            root_unconditional=root_unconditional,
            interpret=interpret,
        )
    else:
        qq8 = _quantize_queries(queries, origin, inv_cell8, cells8)
        act = level_sweep_hier(
            qq8, qq16, mbr_q8, mbr_q16, parent_q,
            split=split,
            block_w=block_w,
            root_unconditional=root_unconditional,
            interpret=interpret,
        )
    return _hits_epilogue(
        act, queries, confirm_mbr, obj_level, obj_slot, obj_id, n_objects
    )[:2]


def pyramid_scan_compact8(
    qsched: QuantizedSchedule | StagedSchedule,
    queries,
    *,
    block_w: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused region search over the hierarchical (uint8 upper-level) form
    of a :class:`QuantizedSchedule` — ``quantize_schedule(..., upper8=
    True)``, or its ``stage_schedule(qsched, "compact8")`` form.  Hit
    sets bit-identical to every other precision; upper-level tiles
    stream at 1 byte per coordinate (DESIGN.md §12)."""
    return scan_staged(
        _as_staged(qsched, "compact8"), queries, block_w=block_w,
        interpret=interpret,
    )[:2]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_objects", "base_levels", "block_w", "root_unconditional",
        "test_object_mbr", "interpret", "stream", "win_w",
    ),
)
def _fused_search_live(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id, alive,
    *,
    n_objects: int,
    base_levels: int,
    block_w: int,
    root_unconditional: bool,
    test_object_mbr: bool,
    interpret: bool,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Fused sweep over base levels + appended flat delta levels.

    The live-update subsystem (DESIGN.md §8) appends the delta buffer as
    ``uncond_from = base_levels`` flat levels: one launch still sweeps
    everything, and the epilogue scatters base entries and delta slots
    into the same global-id hit mask, then masks tombstoned ids with
    ``alive``.  ``visits`` keeps the per-level layout — columns past
    ``base_levels`` are delta-side accesses.
    """
    act = level_sweep(
        queries, mbr_cm, parent,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        uncond_from=base_levels,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )  # (L_base + D, Q, W)
    return _hits_epilogue(
        act, queries, obj_mbr if test_object_mbr else None,
        obj_level, obj_slot, obj_id, n_objects, alive=alive,
    )[:2]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_objects", "cells", "base_levels", "block_w",
        "root_unconditional", "interpret", "stream", "win_w",
    ),
)
def _fused_search_compact_live(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell, alive,
    *,
    n_objects: int,
    cells: int,
    base_levels: int,
    block_w: int,
    root_unconditional: bool,
    interpret: bool,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Compact (uint16-tile) twin of :func:`_fused_search_live`.

    Delta rows are quantized outward onto the base grid (clipped — see
    ``kernels.quantize.quantize_rows``), swept as flat levels in the same
    integer launch, and confirmed exactly against their float32 MBRs, so
    the tombstone-masked hit sets stay bit-identical to the float32 live
    path (DESIGN.md §8).
    """
    qq = _quantize_queries(queries, origin, inv_cell, cells)
    act = level_sweep(
        qq, mbr_q, parent_q,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        uncond_from=base_levels,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )
    return _hits_epilogue(
        act, queries, confirm_mbr, obj_level, obj_slot, obj_id, n_objects,
        alive=alive,
    )[:2]


def per_level_region_search(
    schedule: LevelSchedule,
    queries,
    *,
    block_w: int = 128,
    interpret: bool = False,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Status-quo baseline: ONE ``mbr_scan`` launch per level, survivor
    frontier combined in host Python between launches.  Returns
    ``(hits, visits, n_launches)`` with hits/visits matching
    :func:`pyramid_scan`; exists so the benchmark can measure what fusing
    the sweep saves (DESIGN.md §3.3).
    """
    from .mbr_scan import mbr_scan

    q = np.asarray(queries, np.float32)
    nq = q.shape[0]
    levels, _, w = schedule.mbr_cm.shape
    launches = 0
    active = None
    acts = []
    for l in range(levels):
        mbrs = np.ascontiguousarray(schedule.mbr_cm[l].T)  # (W, 4) row-major
        # Sentinel-padded rows contain inf; mbr_scan pads with inf itself,
        # so the scan is well defined and padded slots never overlap.
        ov = np.asarray(
            mbr_scan(jnp.asarray(mbrs), jnp.asarray(q),
                     block_n=block_w, interpret=interpret)
        )
        launches += 1
        if l == 0:
            if schedule.root_unconditional:
                act = np.zeros((nq, w), bool)
                act[:, 0] = True
            else:
                act = ov
        else:
            act = ov & active[:, schedule.parent[l]]
        active = act
        acts.append(act)
    act = np.stack(acts)  # (L, Q, W)
    visits = act.sum(axis=2).T.astype(np.int32)
    entry_act = act[schedule.obj_level, :, schedule.obj_slot].T  # (Q, E)
    if schedule.test_object_mbr:
        entry_act = entry_act & _overlaps(
            schedule.obj_mbr[None, :, :], q[:, None, :]
        )
    elif schedule.n_shared:
        entry_act, confirm = confirm_shared(
            entry_act, q, schedule.obj_mbr[:schedule.n_shared], xp=np)
        count_confirm(confirm)
    hits = np.zeros((nq, max(schedule.n_objects, 1)), bool)
    np.maximum.at(hits, (slice(None), schedule.obj_id), entry_act)
    return hits, visits, launches
