"""Public jit'd wrappers for the Pallas kernels.

Off the TPU the kernels execute with ``interpret=True`` (the Pallas
interpreter runs the kernel body for correctness); on a TPU they lower
natively through Mosaic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.flat import confirm_width

from . import ref
from .build import build_levels_jnp as build_levels_jnp  # noqa: F401
from .build import build_levels_pallas as build_levels_pallas  # noqa: F401
from .build import device_schedule as _device_schedule
from .build import hilbert_keys as hilbert_keys  # noqa: F401 (re-export)
from .build import hilbert_permute as hilbert_permute  # noqa: F401
from .join_scan import _fused_join
from .join_scan import pair_sweep as _pair_sweep
from .mbr_scan import mbr_scan as _mbr_scan
from .pyramid_scan import (
    _fused_search,
    _fused_search_compact,
    _fused_search_compact8,
    _fused_search_compact_live,
    _fused_search_live,
)
from .pyramid_scan import level_sweep as level_sweep  # noqa: F401
from .pyramid_scan import level_sweep_hier as level_sweep_hier  # noqa: F401
from .pyramid_scan import parent_windows as parent_windows  # noqa: F401
from .pyramid_scan import per_level_region_search as _per_level
from .pyramid_scan import pyramid_scan as _pyramid_scan
from .pyramid_scan import pyramid_scan_compact as _pyramid_scan_compact
from .pyramid_scan import pyramid_scan_compact8 as _pyramid_scan_compact8
from .pyramid_scan import _fused_search_ids as fused_search_ids  # noqa: F401
from .pyramid_scan import ids_caps as ids_caps  # noqa: F401
from .pyramid_scan import scan_staged as _scan_staged
from .pyramid_scan import scan_staged_ids as _scan_staged_ids
from .pyramid_scan import stage_schedule as stage_schedule  # noqa: F401
from .quantize import grid_params as grid_params  # noqa: F401 (re-export)
from .quantize import quantize_cm_pallas as quantize_cm_pallas  # noqa: F401
from .quantize import quantize_rows as quantize_rows  # noqa: F401 (re-export)
from .quantize import quantize_schedule as _quantize_schedule
from .transfer import count_confirm as count_confirm  # noqa: F401
from .transfer import fetch as fetch  # noqa: F401 (re-export)
from .transfer import to_device as to_device  # noqa: F401 (re-export)


def interpret_default() -> bool:
    """Default Pallas execution policy: interpret off TPU, compile on TPU.
    This is the ONE public source of that policy — callers outside
    ``kernels/`` must not reach for private module state."""
    return jax.default_backend() != "tpu"


# Internal alias kept for the kernel wrappers below.
_interpret = interpret_default


def fused_search(
    queries,
    mbr_cm,
    parent,
    obj_mbr,
    obj_level,
    obj_slot,
    obj_id,
    *,
    n_objects: int,
    block_w: int = 128,
    root_unconditional: bool = True,
    test_object_mbr: bool = True,
    n_shared: int = 0,
    interpret: bool | None = None,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Array-level public entry of the fused sweep (DESIGN.md §3.3).

    Same computation as :func:`pyramid_scan` but over the unpacked
    ``LevelSchedule`` arrays, so callers (e.g. the spatial server) can
    ``vmap``/``pmap`` it over query blocks with the schedule arrays held
    constant.  Returns ``(hits (Q, n_objects), visits (Q, L), confirm)``;
    ``confirm`` is the (Q, 2) object-test sums of a pyramid's
    ``n_shared`` leading entries (``LevelSchedule.n_shared``), else None.

    ``stream=True`` runs the HBM-streaming double-buffered sweep
    (DESIGN.md §12); pass the ``(win_off, win_w)`` parent windows from
    :func:`parent_windows` alongside.
    """
    if interpret is None:
        interpret = interpret_default()
    confirm_w = confirm_width(n_shared, obj_id.shape[0])
    return _fused_search(
        queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id,
        jnp.int32(n_shared) if confirm_w else None,
        n_objects=n_objects,
        block_w=block_w,
        root_unconditional=root_unconditional,
        test_object_mbr=test_object_mbr,
        confirm_w=confirm_w,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )


def fused_search_live(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id, alive,
    *,
    n_objects: int,
    base_levels: int,
    block_w: int = 128,
    root_unconditional: bool = True,
    test_object_mbr: bool = True,
    interpret: bool | None = None,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Live-update variant of :func:`fused_search` (DESIGN.md §8): the
    level grid carries ``base_levels`` hierarchical levels plus appended
    FLAT delta-buffer levels (swept unconditionally in the same launch),
    object ids are global, and ``alive`` masks tombstoned ids out of the
    hit set.  Returns ``(hits (Q, n_objects), visits (Q, L+D))``."""
    if interpret is None:
        interpret = interpret_default()
    return _fused_search_live(
        queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id, alive,
        n_objects=n_objects,
        base_levels=base_levels,
        block_w=block_w,
        root_unconditional=root_unconditional,
        test_object_mbr=test_object_mbr,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )


def fused_search_compact_live(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell, alive,
    *,
    n_objects: int,
    cells: int,
    base_levels: int,
    block_w: int = 128,
    root_unconditional: bool = True,
    interpret: bool | None = None,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Live-update variant of :func:`fused_search_compact`: uint16 base
    tiles + quantized flat delta levels in one integer sweep, exact
    confirming pass, tombstones masked via ``alive`` (DESIGN.md §8)."""
    if interpret is None:
        interpret = interpret_default()
    return _fused_search_compact_live(
        queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
        origin, inv_cell, alive,
        n_objects=n_objects,
        cells=cells,
        base_levels=base_levels,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )


def fused_join(
    a_cm, a_parent, a_anc, a_level, a_gid,
    b_cm, b_parent, b_anc, b_level, b_gid,
    table_a, table_b, alive_a, alive_b, delta_a, delta_b,
    *,
    block_a: int = 128,
    block_b: int = 128,
    interpret: bool | None = None,
    symmetric: bool = False,
):
    """Tree-vs-tree spatial join: one fused pair-sweep launch + exact
    confirming epilogue (DESIGN.md §10).

    Both sides arrive as their first ``K = min(levels_a, levels_b)``
    schedule levels (float32 tiles, or uint16 tiles quantized onto one
    JOINT grid for ``precision="compact"``), per-entry ancestor chains
    from :func:`repro.core.flat.ancestor_chains`, global-id float32 MBR
    tables, tombstone ``alive`` masks, and delta-buffer candidate row
    masks.  Returns ``(pairs (Na, Nb) bool, visits (K + 2,) int32)`` —
    the pair set is bit-identical to the brute-force nested-loop oracle
    on every precision; only ``visits`` (tile-pair tests per level, plus
    one delta cross-scan column per side) depends on tile precision.

    ``symmetric=True`` (self-join: both sides the same schedule + live
    state) sweeps only the upper pair triangle — half the tile-pair
    work — and mirrors in the epilogue; the pair set is unchanged.
    """
    if interpret is None:
        interpret = interpret_default()
    return _fused_join(
        a_cm, a_parent, a_anc, a_level, a_gid,
        b_cm, b_parent, b_anc, b_level, b_gid,
        table_a, table_b, alive_a, alive_b, delta_a, delta_b,
        block_a=block_a,
        block_b=block_b,
        interpret=interpret,
        symmetric=symmetric,
    )


def pair_sweep(a_cm, a_parent, b_cm, b_parent, *, block_a: int = 128,
               block_b: int = 128, interpret: bool | None = None,
               symmetric: bool = False, onehot_gather: bool | None = None):
    """Raw (K, Wa, Wb) pair-active mask of the synchronized level sweep —
    the join kernel without its epilogue, for tests and benches."""
    if interpret is None:
        interpret = interpret_default()
    return _pair_sweep(
        a_cm, a_parent, b_cm, b_parent,
        block_a=block_a, block_b=block_b, interpret=interpret,
        symmetric=symmetric, onehot_gather=onehot_gather,
    )


def device_schedule(mbrs, *, levels=None, engine: str = "auto",
                    block_n: int = 128, interpret: bool | None = None,
                    order: str | None = None):
    """Device-resident bulk build straight to a ``LevelSchedule`` — no
    host pointer tree, no ``flatten()`` (DESIGN.md §7).  ``engine="auto"``
    picks the one-launch Pallas build kernel when compiling natively and
    the object set fits its VMEM residency, the jit'd jnp fixed point
    otherwise; both are bit-identical to the host
    ``flat.pyramid_schedule`` lowering.  ``order="hilbert"`` permutes the
    real slots of every level into Hilbert-curve order of their MBR
    centers after the build (DESIGN.md §12) — hit sets, visit counts and
    reported ids are unchanged; only tile locality improves."""
    if interpret is None:
        interpret = interpret_default()
    return _device_schedule(
        mbrs, levels=levels, engine=engine, block_n=block_n,
        interpret=interpret, order=order,
    )


def quantize_schedule(schedule, *, engine: str = "auto", block_w: int = 128,
                      interpret: bool | None = None, upper8: bool = False,
                      split: int | None = None):
    """Lower a ``LevelSchedule`` to its conservative uint16 tile form
    (``QuantizedSchedule``, DESIGN.md §7) for the compact fused scan.
    ``upper8=True`` adds coarse uint8 tiles for levels ``[0, split)`` on
    a 254-cell grid — the hierarchical form :func:`pyramid_scan_compact8`
    sweeps (DESIGN.md §12)."""
    if interpret is None:
        interpret = interpret_default()
    return _quantize_schedule(
        schedule, engine=engine, block_w=block_w, interpret=interpret,
        upper8=upper8, split=split,
    )


def pyramid_scan_compact(qsched, queries, *, block_w: int = 128,
                         interpret: bool | None = None,
                         stream: bool = False):
    """Fused region search over uint16 tiles + exact float32 confirming
    pass: hit sets bit-identical to :func:`pyramid_scan` at ~half the
    streamed bytes per query; ``visits`` reports the compact sweep's own
    conservative access counts (DESIGN.md §7).  ``stream=True`` runs the
    HBM-streaming sweep (DESIGN.md §12).  ``qsched`` may be staged once
    with ``stage_schedule(qsched, "compact")``."""
    if interpret is None:
        interpret = interpret_default()
    return _pyramid_scan_compact(
        qsched, queries, block_w=block_w, interpret=interpret, stream=stream
    )


def pyramid_scan_compact8(qsched, queries, *, block_w: int = 128,
                          interpret: bool | None = None):
    """Hierarchical compact region search (DESIGN.md §12): coarse uint8
    tiles gate the upper levels, uint16 tiles the lower, and the exact
    float32 confirming pass keeps hit sets bit-identical to
    :func:`pyramid_scan`.  Needs ``quantize_schedule(..., upper8=True)``
    (or its ``stage_schedule(qsched, "compact8")`` form); upper-level
    streamed bytes drop ~2x vs the uint16 form."""
    if interpret is None:
        interpret = interpret_default()
    return _pyramid_scan_compact8(
        qsched, queries, block_w=block_w, interpret=interpret
    )


def fused_search_compact(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell,
    *,
    n_objects: int,
    cells: int,
    block_w: int = 128,
    root_unconditional: bool = True,
    interpret: bool | None = None,
    stream: bool = False,
    win_off=None,
    win_w: int | None = None,
):
    """Array-level public entry of the compact sweep (the ``precision=
    "compact"`` analogue of :func:`fused_search`), ``vmap``/``pmap``-able
    over query blocks with the quantized schedule arrays held constant."""
    if interpret is None:
        interpret = interpret_default()
    return _fused_search_compact(
        queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
        origin, inv_cell,
        n_objects=n_objects,
        cells=cells,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
        stream=stream,
        win_off=win_off,
        win_w=win_w,
    )


def fused_search_compact8(
    queries, mbr_q8, mbr_q16, parent_q, confirm_mbr, obj_level, obj_slot,
    obj_id, origin, inv_cell, inv_cell8,
    *,
    n_objects: int,
    cells: int,
    cells8: int,
    split: int,
    block_w: int = 128,
    root_unconditional: bool = True,
    interpret: bool | None = None,
):
    """Array-level public entry of the hierarchical uint8/uint16 sweep
    (the ``precision="compact8"`` analogue of :func:`fused_search_compact`,
    DESIGN.md §12): ``mbr_q8`` carries the coarse tiles of levels
    ``[0, split)``, ``mbr_q16`` the fine tiles of levels ``[split, L)``."""
    if interpret is None:
        interpret = interpret_default()
    return _fused_search_compact8(
        queries, mbr_q8, mbr_q16, parent_q, confirm_mbr, obj_level, obj_slot,
        obj_id, origin, inv_cell, inv_cell8,
        n_objects=n_objects,
        cells=cells,
        cells8=cells8,
        split=split,
        block_w=block_w,
        root_unconditional=root_unconditional,
        interpret=interpret,
    )


def mbr_scan(mbrs, queries, *, block_n: int = 512):
    """(N,4) x (Q,4) -> (Q,N) overlap mask via the Pallas level-scan."""
    return _mbr_scan(
        jnp.asarray(mbrs, jnp.float32),
        jnp.asarray(queries, jnp.float32),
        block_n=block_n,
        interpret=_interpret(),
    )


def pyramid_scan(schedule, queries, *, block_w: int = 128,
                 interpret: bool | None = None, stream: bool = False):
    """Fused multi-level region search: one launch for the whole levelized
    sweep (DESIGN.md §3.3).  Returns (hits (Q, n_obj), visits (Q, L)).
    ``interpret=None`` follows :func:`interpret_default`.  ``stream=True``
    runs the HBM-streaming double-buffered sweep (DESIGN.md §12): MBR
    tiles stay in HBM and are DMA'd through a two-slot VMEM buffer, so
    VMEM residency no longer bounds the schedule width.  ``schedule`` is
    a host ``LevelSchedule`` (staged for this call) or its
    :func:`stage_schedule` form, which stays on the device across calls
    and plans each ``block_w``'s parent windows once."""
    if interpret is None:
        interpret = interpret_default()
    return _pyramid_scan(
        schedule, queries, block_w=block_w, interpret=interpret, stream=stream
    )


def scan_staged(staged, queries, *, block_w: int = 128,
                interpret: bool | None = None, stream: bool = False,
                pad_to: int | None = None):
    """One fused launch over a :func:`stage_schedule` form of any
    precision; returns device ``(hits, visits, confirm)``, ``confirm``
    the (Q, 2) object-test sums of a pyramid's shared entries or None.
    ``pad_to`` pads a shorter batch with :data:`repro.core.flat.NEVER_MBR` rows, kept in
    the outputs.  ``interpret=None`` follows :func:`interpret_default`."""
    if interpret is None:
        interpret = interpret_default()
    return _scan_staged(staged, queries, block_w=block_w,
                        interpret=interpret, stream=stream, pad_to=pad_to)


def scan_staged_ids(staged, queries, *, block_w: int = 128,
                    interpret: bool | None = None, stream: bool = False,
                    pad_to: int | None = None, caps=None):
    """One launch over a pyramid's :func:`stage_schedule` form that
    returns its hits as ids where they fit ``caps`` (DESIGN.md §12):
    device ``(visits, confirm, offsets, overflow, ids, hits)``.
    ``interpret=None`` follows :func:`interpret_default`."""
    if interpret is None:
        interpret = interpret_default()
    return _scan_staged_ids(staged, queries, block_w=block_w,
                            interpret=interpret, stream=stream,
                            pad_to=pad_to, caps=caps)


def per_level_region_search(schedule, queries, *, block_w: int = 128):
    """Baseline: one mbr_scan launch per level, host-combined frontier.
    Returns (hits, visits, n_launches)."""
    return _per_level(
        schedule, queries, block_w=block_w, interpret=_interpret()
    )


# re-export the oracle for tests/benches
mbr_scan_ref = ref.mbr_scan_ref
