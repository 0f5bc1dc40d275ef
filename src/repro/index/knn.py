"""k-nearest-neighbour engines behind ``SpatialIndex.knn``.

The Symmetric M-tree line of related work treats k-NN as the peer of
region search; here it is first-class on every backend (DESIGN.md §6):

* :func:`knn_pointer` — exact best-first branch-and-bound over the pointer
  tree (the host oracle), MBR min-distance priority queue; generalizes
  ``mqrtree.knn_search`` to both pointer structures.
* :func:`knn_brute` — exact scan over object MBRs (host path for the
  pyramid structure, which has no pointer form).
* :func:`knn_expanding` — the device path: an expanding-radius *region
  schedule* drives the backend's fused level sweep until every point has
  ≥k survivors, one √2-margin confirming round closes the corner gap of
  the square probe, and a top-k distance epilogue in jnp ranks the
  survivors.  Exactness: survivors of an L∞ ball of radius r all lie
  within Euclidean distance r·√2, so the kth distance d_k ≤ r·√2, and the
  confirming round's L∞ ball of radius r·√2 ⊇ the Euclidean d_k-ball —
  no true neighbour can be outside the final candidate set.

All engines report distances as Euclidean point-to-MBR min-distances
(0 inside the rectangle) and the paper's access counts.
"""

from __future__ import annotations

from typing import Tuple

import heapq

import numpy as np

from repro.kernels.ops import fetch, to_device
from repro.obs import trace as _obs_trace

from .trees import node_children as _node_children
from .trees import node_mbr as _node_mbr

import jax.numpy as jnp
from jax import lax

# > sqrt(2): covers the square-vs-circle corner gap with float slack.
_CONFIRM_MARGIN = 1.5


def _mindist_np(points: np.ndarray, mbrs: np.ndarray) -> np.ndarray:
    """Euclidean min-distance point→MBR, (Q, 2) × (N, 4) -> (Q, N)."""
    px = points[:, 0][:, None]
    py = points[:, 1][:, None]
    dx = np.maximum(np.maximum(mbrs[None, :, 0] - px, px - mbrs[None, :, 2]), 0.0)
    dy = np.maximum(np.maximum(mbrs[None, :, 1] - py, py - mbrs[None, :, 3]), 0.0)
    return np.sqrt(dx * dx + dy * dy)


def _mindist_point(p: np.ndarray, mbr) -> float:
    dx = max(mbr[0] - p[0], 0.0, p[0] - mbr[2])
    dy = max(mbr[1] - p[1], 0.0, p[1] - mbr[3])
    return float(np.sqrt(dx * dx + dy * dy))


def knn_pointer(tree, points: np.ndarray, k: int):
    """Exact best-first k-NN over an ``MQRTree`` or ``RTree``.

    Returns ``(ids (Q, k) int32, dists (Q, k) float32, visits (Q,) int64)``
    — visits counts expanded nodes, the paper's disk accesses.

    Equal distances resolve by lowest object id — the same rule as the
    brute-force scan (stable argsort) and the device top-k (``lax.top_k``
    prefers the lower index): heap keys order nodes *before* objects at
    the same distance, so every object at distance ≤ d is enqueued before
    any object at distance d is emitted, and among equal-distance objects
    the id is the tiebreak.
    """
    nq = points.shape[0]
    ids = np.zeros((nq, k), np.int32)
    dists = np.zeros((nq, k), np.float32)
    visits = np.zeros((nq,), np.int64)
    for i in range(nq):
        p = points[i]
        # key: (dist, kind, id) — kind 0 = node (expand first), 1 = object.
        heap = [(0.0, 0, 0, tree.root)]
        counter = 1
        got = 0
        while heap and got < k:
            d, kind, key, item = heapq.heappop(heap)
            if kind == 0:
                node = item
                if _node_mbr(node) is None:
                    continue
                visits[i] += 1
                for embr, child, obj in _node_children(node):
                    if child is not None:
                        counter += 1
                        heapq.heappush(
                            heap, (_mindist_point(p, embr), 0, counter, child)
                        )
                    else:
                        heapq.heappush(
                            heap, (_mindist_point(p, embr), 1, obj, None)
                        )
            else:
                ids[i, got] = key
                dists[i, got] = d
                got += 1
    return ids, dists, visits


def knn_brute(obj_mbrs: np.ndarray, points: np.ndarray, k: int):
    """Exact k-NN by scanning every object MBR (pyramid host path)."""
    obj_mbrs = np.asarray(obj_mbrs)
    return knn_brute_masked(
        obj_mbrs, np.ones((obj_mbrs.shape[0],), bool), points, k
    )


def knn_brute_masked(mbr_table: np.ndarray, alive: np.ndarray,
                     points: np.ndarray, k: int):
    """Exact k-NN over the LIVE rows of an id-space MBR table — the host
    path once live updates begin (DESIGN.md §8).  Dead and unallocated
    rows are masked to +inf distance, so ids and tie-breaks (lowest
    global id first, stable argsort) resolve exactly as
    :func:`knn_brute` would on the compacted live set."""
    d = _mindist_np(
        np.asarray(points, np.float64), np.asarray(mbr_table, np.float64)
    )
    d = np.where(alive[None, :], d, np.inf)
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    dists = np.take_along_axis(d, order, axis=1).astype(np.float32)
    visits = np.full((points.shape[0],), int(alive.sum()), np.int64)
    return order.astype(np.int32), dists, visits


def knn_expanding(
    region_fn,
    obj_mbrs: np.ndarray,
    points: np.ndarray,
    k: int,
    *,
    max_rounds: int = 40,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Device k-NN: expanding-radius region schedule + jnp top-k epilogue.

    ``region_fn(queries (Q, 4)) -> (hits (Q, n_obj), visits (Q, L))`` is
    the backend's batched region search (the fused sweep for ``pallas`` /
    ``serve``).  Query shape is constant across rounds, so the device
    function compiles once.  Ties resolve by lowest object id
    (``lax.top_k`` prefers the lower index), matching :func:`knn_pointer`
    and :func:`knn_brute`.

    Returns ``(ids (Q, k), dists (Q, k), visits (Q,), rounds)``.

    Building each round's rectangles and staging the epilogue are the
    ``engine.prepare`` stage, the per-round survivor checks
    ``engine.finish``; ``region_fn`` times its own launches.
    """

    with _obs_trace.stage("engine.prepare", "prepare_s"):
        obj_mbrs = np.asarray(obj_mbrs, np.float64)
        points = np.asarray(points, np.float64)
        nq = points.shape[0]
        n = obj_mbrs.shape[0]

        # Initial radius from the density estimate: a square expected to
        # hold ~k objects under a uniform spread of n objects over the
        # data extent.
        extent = max(
            obj_mbrs[:, 2].max() - obj_mbrs[:, 0].min(),
            obj_mbrs[:, 3].max() - obj_mbrs[:, 1].min(),
            1e-6,
        )
        r = np.full((nq,), 0.5 * extent * np.sqrt(k / max(n, 1)) + 1e-6)

    def probe(radius):
        with _obs_trace.stage("engine.prepare", "prepare_s"):
            return np.stack(
                [points[:, 0] - radius, points[:, 1] - radius,
                 points[:, 0] + radius, points[:, 1] + radius],
                axis=1,
            ).astype(np.float32)

    total_visits = np.zeros((nq,), np.int64)
    rounds = 0
    satisfied = np.zeros((nq,), bool)
    for _ in range(max_rounds):
        hits, visits = region_fn(probe(r))
        rounds += 1
        with _obs_trace.stage("engine.finish", "finish_s"):
            total_visits += np.asarray(visits).sum(axis=1)
            satisfied = np.asarray(hits).sum(axis=1) >= k
            # double only the radii still short of k survivors; satisfied
            # points keep their radius (their result is already final-bound)
            r = np.where(satisfied, r, r * 2.0)
        if satisfied.all():
            break
    if not satisfied.all():
        raise RuntimeError(
            f"knn radius expansion did not reach k={k} survivors "
            f"in {max_rounds} rounds"
        )

    # Confirming round: the square of radius r·√2 covers the Euclidean
    # d_k-ball (see module docstring), making the candidate set exact.
    hits, visits = region_fn(probe(r * _CONFIRM_MARGIN))
    rounds += 1
    with _obs_trace.stage("engine.finish", "finish_s"):
        total_visits += np.asarray(visits).sum(axis=1)

    # Top-k distance epilogue in jnp over the surviving candidates.
    with _obs_trace.stage("engine.prepare", "prepare_s"):
        pts = to_device(points, jnp.float32)
        mb = to_device(obj_mbrs, jnp.float32)
        px, py = pts[:, 0][:, None], pts[:, 1][:, None]
        dx = jnp.maximum(
            jnp.maximum(mb[None, :, 0] - px, px - mb[None, :, 2]), 0.0)
        dy = jnp.maximum(
            jnp.maximum(mb[None, :, 1] - py, py - mb[None, :, 3]), 0.0)
        d = jnp.sqrt(dx * dx + dy * dy)
        d = jnp.where(to_device(hits), d, jnp.inf)
        neg_top, ids = lax.top_k(-d, k)
        dists = -neg_top
    ids, dists = fetch(ids, dists)
    return (np.asarray(ids, np.int32), np.asarray(dists, np.float32),
            total_visits, rounds)
