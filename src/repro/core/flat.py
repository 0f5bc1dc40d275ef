"""Levelized struct-of-arrays view of a spatial tree + batched JAX search.

TPU adaptation layer (DESIGN.md §3.1): pointer-chasing trees do not
vectorize, so a built tree (mqr or R) is flattened into dense arrays and
region search becomes a masked breadth-first frontier sweep expressed with
``jax.lax`` control flow.  One "disk access" of the paper = one live row of
the frontier (a node whose entries are examined), so the JAX search reports
the *same* disk-access count as the host pointer implementation — this
equivalence is tested in tests/test_flat_search.py.

This module also exports the :class:`LevelSchedule` — the dense per-level
form of a tree that the fused region-search kernel
(:mod:`repro.kernels.pyramid_scan`, DESIGN.md §3.3) consumes in a single
launch.  Both pointer trees (via :func:`level_schedule`) and the bulk group
pyramid (via :func:`pyramid_schedule`) lower to the same schedule, so the
kernel serves either build path.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .mqrtree import MQRTree
from .rtree import RTree

EMPTY = -1  # children_idx sentinel: no entry
# children_idx >= 0   -> index of a child node
# children_idx <= -2  -> object id encoded as -(obj + 2)


@dataclasses.dataclass(frozen=True)
class FlatTree:
    """Dense array form of a spatial tree.

    node_mbr:      (N, 4)   float32
    children_mbr:  (N, F, 4) float32 (F = max fan-out)
    children_idx:  (N, F)   int32 (see sentinels above)
    n_objects:     int
    root:          int (node index of the root, always 0)
    """

    node_mbr: np.ndarray
    children_mbr: np.ndarray
    children_idx: np.ndarray
    n_objects: int
    root: int = 0

    @property
    def n_nodes(self) -> int:
        return self.node_mbr.shape[0]


def flatten(tree) -> FlatTree:
    """Flatten an ``MQRTree`` or ``RTree`` into a :class:`FlatTree`."""
    if isinstance(tree, MQRTree):
        fan = 5

        def node_entries(node):
            for _, e in node.entries():
                yield e.mbr, (e.node if e.is_node else None), e.obj

        root = tree.root
    elif isinstance(tree, RTree):
        fan = tree.M

        def node_entries(node):
            for e in node.entries:
                yield e.mbr, e.child, e.obj

        root = tree.root
    else:  # pragma: no cover
        raise TypeError(type(tree))

    nodes = []
    index = {}

    # Explicit-stack preorder walk: tree depth is unbounded (CENTER chains
    # grow one node per ~4 co-centred objects, Section 3.4), so recursion
    # would trip Python's recursion limit on degenerate datasets.
    stack = [root]
    while stack:
        node = stack.pop()
        index[id(node)] = len(nodes)
        nodes.append(node)
        children = [c for _, c, _ in node_entries(node) if c is not None]
        stack.extend(reversed(children))

    n = len(nodes)
    node_mbr = np.zeros((n, 4), np.float32)
    children_mbr = np.zeros((n, fan, 4), np.float32)
    children_idx = np.full((n, fan), EMPTY, np.int32)
    n_objects = 0
    for ni, node in enumerate(nodes):
        mbr = node.mbr if isinstance(tree, MQRTree) else node.mbr()
        node_mbr[ni] = np.asarray(mbr, np.float32)
        for fi, (embr, child, obj) in enumerate(node_entries(node)):
            children_mbr[ni, fi] = np.asarray(embr, np.float32)
            if child is not None:
                children_idx[ni, fi] = index[id(child)]
            else:
                children_idx[ni, fi] = -(obj + 2)
                n_objects = max(n_objects, obj + 1)
    return FlatTree(node_mbr, children_mbr, children_idx, n_objects)


def _overlaps(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Closed-boundary rectangle intersection, broadcasting."""
    return (
        (a[..., 0] <= b[..., 2])
        & (b[..., 0] <= a[..., 2])
        & (a[..., 1] <= b[..., 3])
        & (b[..., 1] <= a[..., 3])
    )


def region_search_batch(
    flat: FlatTree, queries: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched region search.

    Args:
      flat: flattened tree.
      queries: (Q, 4) query rectangles.

    Returns:
      hits:   (Q, n_objects) bool — object overlap mask.
      visits: (Q,) int32 — node visits (disk accesses), identical to the
              pointer implementation's count.
    """
    children_mbr = jnp.asarray(flat.children_mbr)
    children_idx = jnp.asarray(flat.children_idx)
    queries = jnp.asarray(queries, jnp.float32)
    n, fan = children_idx.shape
    q = queries.shape[0]
    n_obj = flat.n_objects

    is_node = children_idx >= 0
    is_obj = children_idx <= -2
    obj_ids = jnp.where(is_obj, -(children_idx + 2), 0)
    child_node = jnp.where(is_node, children_idx, 0)

    def step(state):
        frontier, visits, hits, _ = state
        visits = visits + frontier.sum(axis=1, dtype=jnp.int32)
        # (Q, N, F): does entry f of node n overlap query q?
        ov = _overlaps(children_mbr[None, :, :, :], queries[:, None, None, :])
        act = frontier[:, :, None] & ov
        # record object hits
        def per_query(hits_q, act_q):
            vals = (act_q & is_obj).reshape(-1)
            ids = obj_ids.reshape(-1)
            return hits_q.at[ids].max(vals)

        hits = jax.vmap(per_query)(hits, act)
        # propagate frontier to child nodes
        def frontier_query(act_q):
            vals = (act_q & is_node).reshape(-1)
            ids = child_node.reshape(-1)
            return jnp.zeros((n,), bool).at[ids].max(vals)

        nxt = jax.vmap(frontier_query)(act)
        return nxt, visits, hits, nxt.any()

    def cond(state):
        return state[3]

    frontier0 = jnp.zeros((q, n), bool).at[:, flat.root].set(True)
    visits0 = jnp.zeros((q,), jnp.int32)
    hits0 = jnp.zeros((q, max(n_obj, 1)), bool)
    frontier, visits, hits, _ = jax.lax.while_loop(
        cond, step, (frontier0, visits0, hits0, jnp.array(True))
    )
    return np.asarray(hits), np.asarray(visits)


# ---------------------------------------------------------------------------
# Level schedule: the input of the fused pyramid_scan kernel (DESIGN.md §3.3)
# ---------------------------------------------------------------------------

# MBR sentinel for padded slots: lo=+inf, hi=-inf never overlaps anything.
# Shared by the kernel (tile padding) and the server (null query padding).
NEVER_MBR = np.array([np.inf, np.inf, -np.inf, -np.inf], np.float32)

# Quantized-tile grid: real coordinates land in cells [0, CELLS]; lo=CELLS+1
# is the integer never-overlap sentinel (queries are clipped to <= CELLS, so
# a padded slot's lo exceeds every query hi).  DESIGN.md §7.
CELLS = 65534
Q_NEVER_MBR = np.array([CELLS + 1, CELLS + 1, 0, 0], np.uint16)

# Coarse uint8 grid for the UPPER levels of a hierarchical quantization
# (DESIGN.md §12): same outward rounding on a 255-cell grid, same sentinel
# scheme (lo=CELLS8+1=255 never overlaps a clipped query).  Conservativity
# holds at any resolution, so upper levels can afford 1-byte coordinates —
# the exact confirming pass still makes hit sets bit-identical.
CELLS8 = 254
Q8_NEVER_MBR = np.array([CELLS8 + 1, CELLS8 + 1, 0, 0], np.uint8)


@dataclasses.dataclass(frozen=True)
class LevelSchedule:
    """Dense per-level form of a spatial tree for the fused level sweep.

    A node at level ``l`` (depth ``l`` from the root) occupies a *slot*
    ``j`` in that level's row; padded slots carry never-overlapping
    sentinel MBRs.  The fused kernel computes, level by level,

        active[l, q, j] = active[l-1, q, parent[l, j]] & overlaps(mbr[l, j], q)

    which is exactly the breadth-first frontier of the pointer search, so
    ``active[l].sum()`` reproduces the paper's per-level disk-access counts
    (DESIGN.md §3: one MBR tile fetch = one disk access).

    mbr_cm:   (L, 4, W) float32 — node MBRs coordinate-major (lx, ly, hx, hy
              as contiguous lane vectors; W = padded max level width).
    parent:   (L, W) int32 — slot of the parent in level l-1 (0 at level 0
              and for padding; harmless, padding never overlaps).
    n_real:   (L,) int32 — real (non-padding) slots per level.
    obj_mbr:  (E, 4) float32 — MBR of each object entry.
    obj_level/obj_slot: (E,) int32 — the node holding the entry.
    obj_id:   (E,) int32 — object id the entry resolves to.
    n_objects: dense object-id space size.
    root_unconditional: the pointer search visits the root without testing
              its MBR — True for tree schedules; the group pyramid instead
              requires overlap at every level (False).
    test_object_mbr: whether an object hit additionally requires the entry
              MBR to overlap the query (True for trees; False for the
              pyramid, whose deepest group is the membership test of
              every object alone in it).
    n_shared: pyramid schedules only: entries ``[0, n_shared)`` share
              their deepest group with another entry (equal centroids
              land in the EQ quadrant at every level, so no depth parts
              them).  Those entries alone are confirmed against their own
              MBR (:func:`confirm_shared`); 0 where the build isolates
              every object, and the search then has no object test.
    """

    mbr_cm: np.ndarray
    parent: np.ndarray
    n_real: np.ndarray
    obj_mbr: np.ndarray
    obj_level: np.ndarray
    obj_slot: np.ndarray
    obj_id: np.ndarray
    n_objects: int
    root_unconditional: bool = True
    test_object_mbr: bool = True
    n_shared: int = 0

    @property
    def levels(self) -> int:
        return self.mbr_cm.shape[0]

    @property
    def width(self) -> int:
        return self.mbr_cm.shape[2]


@dataclasses.dataclass(frozen=True)
class QuantizedSchedule:
    """Conservatively quantized tile form of a :class:`LevelSchedule`.

    Node MBRs are snapped to a uint16 grid with OUTWARD rounding (lo
    coordinates floor, hi coordinates ceil), so a quantized box always
    contains its exact box and the quantized level sweep prunes a
    *superset* of the exact survivors — it can never drop a true hit.
    Survivors get one exact float32 confirming pass against
    ``confirm_mbr``, each entry's own MBR: an exact overlap there implies
    every enclosing ancestor overlaps, so confirmed hit sets are
    bit-identical to the float32 path.  Streaming uint16
    node tiles + uint16 parent slots moves half the bytes per query of
    the float32 schedule (DESIGN.md §7).

    base:        the exact schedule (float32 oracle; also carries the
                 object table the confirming pass scatters through).
    mbr_q:       (L, 4, W) uint16 outward-rounded node MBR grid cells.
    parent_q:    (L, W) uint16 parent slots while the level width fits
                 (W <= 65535); wider schedules (pyramid width == n) keep
                 int32 parents and uint16 tiles — bytes ratio 0.6.
    origin:      (4,) float32 grid origin, coordinate-major (ox, oy, ox, oy).
    inv_cell:    (4,) float32 cells-per-unit, coordinate-major.
    confirm_mbr: (E, 4) float32 exact MBR the confirming pass tests.
    cells:       highest real grid cell index (sentinel is cells+1).

    Hierarchical (uint8 upper-level) extension — DESIGN.md §12.  When
    ``mbr_q8`` is present, levels ``[0, split)`` additionally carry a
    coarse uint8 form on a 254-cell grid sharing ``origin``; the hier
    sweep tests those levels on the coarse grid (1 byte/coordinate) and
    the remaining ``[split, L)`` levels on the fine uint16 grid.  Both
    grids round outward, so every level's candidate mask stays a superset
    of the exact sweep's and the confirming pass keeps hit sets
    bit-identical; only the access counts (``visits``) may inflate.

    mbr_q8:    (split, 4, W) uint8 coarse tiles of the upper levels, or
               ``None`` for a flat (uint16-only) quantization.
    split:     first level swept on the fine grid (0 = no coarse levels).
    cells8:    highest real coarse cell index (sentinel is cells8+1).
    inv_cell8: (4,) float32 coarse cells-per-unit (shares ``origin``).
    """

    base: LevelSchedule
    mbr_q: np.ndarray
    parent_q: np.ndarray
    origin: np.ndarray
    inv_cell: np.ndarray
    confirm_mbr: np.ndarray
    cells: int = CELLS
    mbr_q8: np.ndarray | None = None
    split: int = 0
    cells8: int = CELLS8
    inv_cell8: np.ndarray | None = None

    @property
    def levels(self) -> int:
        return self.base.levels

    @property
    def width(self) -> int:
        return self.base.width

    @property
    def n_objects(self) -> int:
        return self.base.n_objects

    @property
    def hierarchical(self) -> bool:
        """Whether the uint8 upper-level tiles are materialized."""
        return self.mbr_q8 is not None and self.split > 0

    @property
    def streamed_bytes(self) -> int:
        """HBM bytes the fused sweep streams per launch (node tiles +
        parent rows); the float32 path streams ``base`` at 2x.  The
        hierarchical form streams uint8 tiles for the upper levels."""
        if self.hierarchical:
            return (
                self.mbr_q8.nbytes
                + self.mbr_q[self.split:].nbytes
                + self.parent_q.nbytes
            )
        return self.mbr_q.nbytes + self.parent_q.nbytes


def level_schedule(flat: FlatTree) -> LevelSchedule:
    """Lower a :class:`FlatTree` (mqr or R) to the kernel's level schedule."""
    n, fan = flat.children_idx.shape
    depth = np.full((n,), -1, np.int64)
    depth[flat.root] = 0
    order = [flat.root]
    head = 0
    parent_of = np.full((n,), -1, np.int64)
    while head < len(order):
        ni = order[head]
        head += 1
        for ci in flat.children_idx[ni]:
            if ci >= 0:
                depth[int(ci)] = depth[ni] + 1
                parent_of[int(ci)] = ni
                order.append(int(ci))
    levels = int(depth.max()) + 1
    width = int(np.bincount(depth, minlength=levels).max())

    slot_of = np.zeros((n,), np.int64)
    fill = np.zeros((levels,), np.int64)
    mbr = np.broadcast_to(NEVER_MBR, (levels, width, 4)).copy()
    parent = np.zeros((levels, width), np.int32)
    for ni in order:  # BFS order => parents are slotted before children
        l = int(depth[ni])
        j = int(fill[l])
        fill[l] += 1
        slot_of[ni] = j
        mbr[l, j] = flat.node_mbr[ni]
        if l > 0:
            parent[l, j] = slot_of[parent_of[ni]]

    is_obj = flat.children_idx <= -2
    node_ids, _ = np.nonzero(is_obj)
    obj_mbr = flat.children_mbr[is_obj].astype(np.float32)
    obj_level = depth[node_ids].astype(np.int32)
    obj_slot = slot_of[node_ids].astype(np.int32)
    obj_id = (-(flat.children_idx[is_obj] + 2)).astype(np.int32)

    return LevelSchedule(
        mbr_cm=np.ascontiguousarray(mbr.transpose(0, 2, 1)),
        parent=parent,
        n_real=fill.astype(np.int32),
        obj_mbr=obj_mbr,
        obj_level=obj_level,
        obj_slot=obj_slot,
        obj_id=obj_id,
        n_objects=flat.n_objects,
        root_unconditional=True,
        test_object_mbr=True,
    )


def ancestor_chains(schedule: LevelSchedule, k_levels: int) -> np.ndarray:
    """Per-entry ancestor slots: ``(E, k_levels)`` int32, column ``k`` =
    the slot of entry ``e``'s ancestor node at level ``k``.

    The tree-vs-tree join epilogue (DESIGN.md §10) looks each entry pair
    up in the synchronized pair mask at ``k = min(level_a, level_b)``;
    these chains are the row/column coordinates of that lookup.  Columns
    past an entry's own level are left 0 — the join never reads them
    (``min`` clamps to the shallower entry).  Vectorized bottom-up walk:
    O(E · max_level) numpy, no per-entry Python loop.
    """
    levels = np.asarray(schedule.obj_level, np.int64)
    e = levels.shape[0]
    max_l = int(levels.max(initial=0))
    chains = np.zeros((e, max(k_levels, max_l + 1)), np.int64)
    cur = np.asarray(schedule.obj_slot, np.int64).copy()
    chains[np.arange(e), levels] = cur
    for t in range(max_l, 0, -1):
        step = levels >= t  # entries whose chain passes through level t
        cur = np.where(step, schedule.parent[t][cur], cur)
        chains[:, t - 1] = np.where(levels >= t - 1, cur, 0)
    return chains[:, :k_levels].astype(np.int32)


def pyramid_entries(obj_mbrs, deepest_group, levels: int) -> dict:
    """The object entries of a pyramid schedule, one per object.

    Objects that share their deepest group with another object come
    first, in id order, then the objects alone in theirs; ``n_shared``
    counts the first.  Where every object is alone the entries are in id
    order and ``n_shared`` is 0.  Returns the ``obj_*`` fields and
    ``n_shared`` of :class:`LevelSchedule`.
    """
    obj_mbr = np.asarray(obj_mbrs, np.float32).reshape(-1, 4)
    slot = np.asarray(deepest_group).astype(np.int32)
    n = slot.shape[0]
    shared = np.bincount(slot)[slot] > 1
    n_shared = int(shared.sum())
    order = np.arange(n, dtype=np.int32)
    if n_shared:
        order = np.concatenate(
            [np.flatnonzero(shared), np.flatnonzero(~shared)]
        ).astype(np.int32)
        obj_mbr, slot = obj_mbr[order], slot[order]
    return dict(obj_mbr=obj_mbr, obj_level=np.full((n,), levels - 1, np.int32),
                obj_slot=slot, obj_id=order, n_shared=n_shared)


def confirm_shared(hit, queries, shared_mbr, xp=jnp, n_shared=None):
    """The object test of a pyramid's shared entries (DESIGN.md §3.1).

    ``hit`` is the (Q, E) entry-hit mask of the level sweep, whose first
    ``U = shared_mbr.shape[0]`` columns are the entries that share their
    deepest group; those alone are kept only where their own MBR meets
    the query (closed bounds).  Returns ``(hit, confirm)``: the confirmed
    mask and a (Q, 2) int32 of each query's candidates before the test
    and hits after it.  ``xp`` is ``np`` or ``jnp``.

    ``n_shared`` (a scalar, possibly traced) counts the shared entries
    where ``U`` is a rounded-up width (:func:`confirm_width`): the
    columns past it, objects alone in their group, are tested too, which
    changes no answer, and are left out of the sums.
    """
    u = shared_mbr.shape[0]
    cand = hit[:, :u]
    ok = cand & _overlaps(shared_mbr[None, :, :], queries[:, None, :])
    if n_shared is None:
        counted = cand, ok
    else:
        real = xp.arange(u)[None, :] < n_shared
        counted = cand & real, ok & real
    confirm = xp.stack([c.sum(axis=1) for c in counted], axis=1)
    return (xp.concatenate([ok, hit[:, u:]], axis=1),
            confirm.astype(xp.int32))


def confirm_width(n_shared: int, n_entries: int) -> int:
    """The static width of a pyramid's confirmed prefix: ``n_shared``
    rounded up to a 32nd to 64th of itself, in whole 128-lane tiles, at
    most ``n_entries``; 0 where nothing is shared.

    Builds of one configuration over other data draws share far more
    than they differ (4e6 bit boxes: 3,908,094 and 3,908,465 shared), so
    with the exact count passed as an operand they lower to one program
    and a compilation cache serves them all.  For an object alone in its
    deepest group the object test equals the group test, so testing the
    columns past ``n_shared`` changes no answer.
    """
    if not n_shared:
        return 0
    step = max(128, 1 << max(int(n_shared).bit_length() - 6, 0))
    return min(int(n_entries), -(-int(n_shared) // step) * step)


def pyramid_schedule(pyr, obj_mbrs: np.ndarray) -> LevelSchedule:
    """Lower a :class:`repro.core.bulk.GroupPyramid` to the level schedule.

    Dense group ids are the slots; ``bulk._group_bounds`` already pads
    unused ids with +inf/-inf sentinels.  Group nesting (a level-``l``
    group's members share one level-``l-1`` group) makes the parent map
    well defined.  An object is a candidate iff every ancestor group
    overlaps (:func:`repro.core.bulk.pyramid_search`); objects that share
    their deepest group are confirmed against their own MBR, so hits are
    the exact overlap on any data (:func:`pyramid_entries`).
    """
    group_of = np.asarray(pyr.group_of)       # (L, n)
    group_mbr = np.asarray(pyr.group_mbr, np.float32)  # (L, n, 4)
    levels, n = group_of.shape
    parent = np.zeros((levels, n), np.int32)
    for l in range(1, levels):
        parent[l, group_of[l]] = group_of[l - 1]
    n_real = (group_of.max(axis=1) + 1).astype(np.int32)
    return LevelSchedule(
        mbr_cm=np.ascontiguousarray(group_mbr.transpose(0, 2, 1)),
        parent=parent,
        n_real=n_real,
        n_objects=n,
        root_unconditional=False,
        test_object_mbr=False,
        **pyramid_entries(obj_mbrs, group_of[levels - 1], levels),
    )
