"""Fused pyramid_scan kernel == host pointer search, exactly.

The acceptance contract of DESIGN.md §3.3: the single-launch fused sweep
returns bit-identical object result sets AND per-level access counts to
the host pointer search (`MQRTree.region_search` / `RTree.region_search`)
and to the levelized JAX search (`flat.region_search_batch`), across
dataset shapes including the paper's zero-overlap point-data case.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import bulk, datasets, flat, mqrtree, rtree
from repro.core import mbr as M
from repro.kernels import ops
from repro.kernels.ops import level_sweep


def host_search_by_level(tree, query, levels):
    """Pointer search, recording visits per depth (root = level 0)."""
    counts = np.zeros(levels, np.int64)
    found = []
    stack = [(tree.root, 0)]
    while stack:
        node, d = stack.pop()
        node_mbr = node.mbr if not callable(node.mbr) else node.mbr()
        if node_mbr is None:
            continue
        counts[d] += 1
        entries = (
            [(e.mbr, e.node, e.obj) for _, e in node.entries()]
            if hasattr(node, "locs")
            else [(e.mbr, e.child, e.obj) for e in node.entries]
        )
        for embr, child, obj in entries:
            if not M.overlaps(embr, query):
                continue
            if child is not None:
                stack.append((child, d + 1))
            else:
                found.append(obj)
    return found, counts


DATASETS = {
    "uniform_squares": lambda: datasets.uniform_squares(300, seed=5),
    # the paper's zero-overlap case: point data never overlaps (§4)
    "uniform_points": lambda: datasets.uniform_points(256, seed=2),
    "exponential_squares": lambda: datasets.exponential_squares(250, seed=9),
}


@pytest.mark.parametrize("name", sorted(DATASETS))
@pytest.mark.parametrize("builder", [mqrtree.build, rtree.build])
def test_fused_matches_host_pointer_search(name, builder):
    data = DATASETS[name]()
    tree = builder(data)
    sched = flat.level_schedule(flat.flatten(tree))
    qs = datasets.region_queries(data, 8, seed=6)
    hits, visits = ops.pyramid_scan(sched, qs)
    hits, visits = np.asarray(hits), np.asarray(visits)
    for i, q in enumerate(qs):
        found, per_level = host_search_by_level(tree, q, sched.levels)
        assert set(np.nonzero(hits[i])[0]) == set(found)
        assert np.array_equal(per_level, visits[i]), (
            f"per-level access counts diverge: {per_level} vs {visits[i]}"
        )
        # total accesses also match the tree's own accounting
        found2, total = tree.region_search(q)
        assert set(found2) == set(found) and total == visits[i].sum()


def test_fused_matches_levelized_jax_search():
    data = datasets.uniform_squares(300, seed=5)
    tree = mqrtree.build(data)
    ft = flat.flatten(tree)
    sched = flat.level_schedule(ft)
    qs = datasets.region_queries(data, 8, seed=6)
    hits_a, visits_a = ops.pyramid_scan(sched, qs)
    hits_b, visits_b = flat.region_search_batch(ft, qs)
    assert np.array_equal(np.asarray(hits_a), hits_b)
    assert np.array_equal(np.asarray(visits_a).sum(axis=1), visits_b)


def test_per_level_baseline_parity_and_launch_count():
    data = datasets.uniform_squares(300, seed=7)
    tree = mqrtree.build(data)
    sched = flat.level_schedule(flat.flatten(tree))
    qs = datasets.region_queries(data, 8, seed=8)
    hits_f, visits_f = ops.pyramid_scan(sched, qs)
    hits_l, visits_l, launches = ops.per_level_region_search(sched, qs)
    assert np.array_equal(np.asarray(hits_f), hits_l)
    assert np.array_equal(np.asarray(visits_f), visits_l)
    # the fused kernel replaces one launch per level with a single launch
    assert launches == sched.levels >= 2


def test_pyramid_schedule_matches_bulk_search():
    pts = datasets.uniform_points(256, seed=2)
    pyr = bulk.build_pyramid(jnp.asarray(pts, jnp.float32), levels=6)
    sched = flat.pyramid_schedule(pyr, pts)
    qs = datasets.region_queries(pts, 6, seed=3)
    hits, _ = ops.pyramid_scan(sched, qs)
    hits = np.asarray(hits)
    for i, q in enumerate(qs):
        ref = np.asarray(bulk.pyramid_search(pyr, jnp.asarray(q, jnp.float32)))
        assert np.array_equal(hits[i], ref)


@pytest.mark.parametrize(
    "variant", ["float32", "compact", "stream", "stream_compact", "hier"]
)
def test_onehot_gather_matches_column_gather(variant):
    """The MXU one-hot parent gather (the TPU path) and the interpreter's
    column gather must produce the same sweep, in every sweep kernel."""
    data = datasets.uniform_squares(200, seed=11)
    sched = flat.level_schedule(flat.flatten(mqrtree.build(data)))
    qs = jnp.asarray(datasets.region_queries(data, 4, seed=12), jnp.float32)
    qsched = ops.quantize_schedule(sched, interpret=True, upper8=True)

    def grid(inv_cell, cells):  # outward query rounding onto a tile grid
        t = (qs - qsched.origin[None, :]) * inv_cell[None, :]
        q = jnp.concatenate([jnp.floor(t[:, :2]), jnp.ceil(t[:, 2:])], 1)
        return jnp.clip(q, 0.0, float(cells)).astype(jnp.int32)

    qq16 = grid(qsched.inv_cell, qsched.cells)
    if variant == "hier":
        qq8 = grid(qsched.inv_cell8, qsched.cells8)
        split = qsched.split

        def sweep(onehot):
            return ops.level_sweep_hier(
                qq8, qq16, jnp.asarray(qsched.mbr_q8),
                jnp.asarray(qsched.mbr_q[split:]),
                jnp.asarray(qsched.parent_q), split=split, interpret=True,
                onehot_gather=onehot,
            )
    else:
        compact = variant.endswith("compact")
        q = qq16 if compact else qs
        mb = qsched.mbr_q if compact else sched.mbr_cm
        pa = qsched.parent_q if compact else sched.parent
        stream = variant.startswith("stream")
        win_off, win_w = (
            ops.parent_windows(pa, sched.n_real, block_w=128)
            if stream else (None, None)
        )

        def sweep(onehot):
            return level_sweep(
                q, jnp.asarray(mb), jnp.asarray(pa), interpret=True,
                onehot_gather=onehot, stream=stream,
                win_off=None if win_off is None else jnp.asarray(win_off),
                win_w=win_w,
            )

    a = sweep(True)
    b = sweep(False)
    assert np.asarray(b).any()
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_spatial_server_transparent_and_caching():
    from repro.launch.spatial_serve import SpatialServer

    data = datasets.uniform_squares(300, seed=13)
    tree = mqrtree.build(data)
    sched = flat.level_schedule(flat.flatten(tree))
    server = SpatialServer(sched, query_block=4, cache_size=64)
    qs = datasets.region_queries(data, 6, seed=14)
    # repeated regions in the stream exercise the cache + padding paths
    stream = np.concatenate([qs, qs[:3], qs[1:2]])
    hits, visits = server.search(stream)
    ref_hits, ref_visits = ops.pyramid_scan(sched, stream)
    assert np.array_equal(hits, np.asarray(ref_hits))
    assert np.array_equal(visits, np.asarray(ref_visits))
    assert server.stats.dedup_hits == 4      # repeats within the one batch
    assert server.stats.cache_hits == 0
    assert server.stats.queries_served == 10
    # second pass: fully served from cache, no new launches
    launches = server.stats.kernel_launches
    hits2, _ = server.search(qs)
    assert np.array_equal(hits2, hits[:6])
    assert server.stats.kernel_launches == launches
    assert server.stats.cache_hits == 6


def test_spatial_server_eviction_and_disabled_cache():
    from repro.launch.spatial_serve import SpatialServer

    data = datasets.uniform_squares(200, seed=15)
    sched = flat.level_schedule(flat.flatten(mqrtree.build(data)))
    qs = datasets.region_queries(data, 16, seed=16)
    ref_hits, _ = ops.pyramid_scan(sched, qs)
    # more distinct misses than cache slots: results must not depend on
    # what the LRU evicted mid-batch
    tiny = SpatialServer(sched, query_block=4, cache_size=4)
    hits, _ = tiny.search(qs)
    assert np.array_equal(hits, np.asarray(ref_hits))
    assert len(tiny._cache) == 4
    # cache_size=0 disables caching entirely
    off = SpatialServer(sched, query_block=4, cache_size=0)
    hits0, _ = off.search(qs)
    assert np.array_equal(hits0, np.asarray(ref_hits))
    assert len(off._cache) == 0
