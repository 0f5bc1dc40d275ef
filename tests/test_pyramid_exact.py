"""A pristine pyramid answers exactly on any data (DESIGN.md §3.1).

Objects with equal centroids fall in the EQ quadrant at every level, so
no depth of the pyramid separates them.  The build orders the objects it
leaves sharing their deepest group first among the entries
(``LevelSchedule.n_shared``) and every backend confirms exactly those
against their own boxes.  Each backend and precision is compared here
with a brute-force numpy overlap written for this file alone, on
Spider-style ``bit`` boxes (many objects per lattice point), on boxes
that all share one centroid, and on uniform squares, where the build
isolates every object and the fused search keeps the program it had
without the object test.
"""
import collections
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import f32_exact
from repro.core import bulk, datasets
from repro.index import SpatialIndex
from repro.index.backends import schedule_region_numpy
from repro.kernels import ops

EXTENT = 1000.0


def bit_boxes(n, digits, seed, probability=0.2, max_side=1.0):
    """Spider's bit distribution as boxes: lattice centres crowded toward
    one corner, width and height uniform in [0, max_side]; multiples of
    2**-14 keep every coordinate and centroid float32-exact."""
    rng = np.random.default_rng(seed)
    weights = 2 ** np.arange(digits - 1, -1, -1)
    lattice = ((rng.random((n, 2, digits)) < probability) * weights).sum(-1)
    step = 2.0 ** -14
    centre = max_side / 2 + lattice / (2 ** digits - 1) * (EXTENT - max_side)
    centre = np.round(centre / step) * step
    half = np.round(rng.uniform(0, max_side, (n, 2)) / 2 / step) * step
    return np.concatenate([centre - half, centre + half], axis=1)


def one_centroid(n, seed):
    """Every box centred on one point, each of its own size."""
    rng = np.random.default_rng(seed)
    half = np.round(rng.uniform(0.01, 40.0, (n, 2)) * 2 ** 10) / 2 ** 10
    return np.concatenate([500.0 - half, 500.0 + half], axis=1)


DATA = {
    "bit": lambda: bit_boxes(3000, digits=6, seed=71),
    "one_centroid": lambda: one_centroid(400, seed=72),
    "uniform": lambda: f32_exact(datasets.uniform_squares(3000, seed=73)),
}


def _levels(name, n):
    # uniform data: enough depth to isolate every object (DESIGN.md §3.1)
    return bulk.default_levels(n) + (4 if name == "uniform" else 0)


def _queries(data, seed, count=24):
    """Small viewports near drawn objects, so that many meet a shared
    group's box but miss some of its members, plus a few wide ones."""
    rng = np.random.default_rng(seed)
    obj = data[rng.integers(0, data.shape[0], count)]
    centre = (obj[:, :2] + obj[:, 2:]) / 2 + rng.uniform(-0.6, 0.6, (count, 2))
    half = rng.uniform(0.02, 0.5, (count, 2))
    half[: count // 6] *= 120.0
    q = np.concatenate([centre - half, centre + half], axis=1)
    return f32_exact(q).astype(np.float32)


def brute(data, queries):
    """Closed-boundary overlap of every (query, box) pair, float64."""
    d = np.asarray(data, np.float64)[None, :, :]
    q = np.asarray(queries, np.float64)[:, None, :]
    return ((d[..., 0] <= q[..., 2]) & (q[..., 0] <= d[..., 2])
            & (d[..., 1] <= q[..., 3]) & (q[..., 1] <= d[..., 3]))


@functools.lru_cache(maxsize=None)
def _index(name, build):
    """``(data, host-backend index)``, built once per module."""
    data = DATA[name]()
    return data, SpatialIndex.build(
        data, structure="pyramid", backend="host", build=build,
        levels=_levels(name, data.shape[0]))


# (build, backend, backend options)
PATHS = {
    "host": ("host", "host", {}),
    "lax": ("host", "lax", {}),
    "serve": ("host", "serve", {"query_block": 8, "cache_size": 0}),
    "serve_compact": ("host", "serve", {"query_block": 8, "cache_size": 0,
                                        "precision": "compact"}),
    "pallas": ("device", "pallas", {"interpret": True}),
    "pallas_compact": ("device", "pallas", {"interpret": True,
                                            "precision": "compact"}),
    "pallas_stream": ("device", "pallas", {"interpret": True,
                                           "stream": True}),
    "pallas_stream_compact": ("device", "pallas", {
        "interpret": True, "stream": True, "precision": "compact"}),
}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("name", sorted(DATA))
def test_pyramid_answers_equal_brute_force(name, path):
    build, backend, opts = PATHS[path]
    data, base = _index(name, build)
    idx = base.with_backend(backend, **opts)
    qs = _queries(data, seed=74)
    want = brute(data, qs)
    sched = idx.artifacts.schedule
    shared_ids = sched.obj_id[:sched.n_shared]
    # the first batch runs the autotuner's probe launches, which count
    # like any launch; the counters below are read from after it
    idx.region(qs)
    before = idx.stats.to_dict()

    res = idx.region(qs)
    assert np.array_equal(res.hits, want)
    assert np.array_equal(idx.count(qs), want.sum(axis=1))
    pts = qs[:, :2]
    assert np.array_equal(idx.point(pts).hits,
                          brute(data, np.concatenate([pts, pts], axis=1)))

    # objects that share their deepest group, counted independently
    deepest = np.asarray(bulk.build_pyramid(
        jnp.asarray(data, jnp.float32), _levels(name, data.shape[0])
    ).group_of[-1])
    shared = np.bincount(deepest)[deepest] > 1
    assert idx.artifacts.unisolated_objects == shared.sum() == sched.n_shared
    assert np.array_equal(np.sort(shared_ids), np.flatnonzero(shared))
    gauge = [m for m in idx.metrics().to_json()["metrics"]
             if m["name"] == "repro_index_unisolated_objects"]
    assert [m["value"] for m in gauge] == [float(shared.sum())]

    stats = idx.stats.diff(before)
    if "compact" in path:
        # the compact sweep confirms every entry; no shared-entry test
        assert stats["confirm_candidates"] == stats["confirm_hits"] == 0
    else:
        # hits among the shared entries, over the region, count and
        # point calls
        n_hits = sum(int(w[:, shared].sum()) for w in (
            want, want, brute(data, np.concatenate([pts, pts], axis=1))))
        assert stats["confirm_hits"] == n_hits
        assert stats["confirm_candidates"] >= stats["confirm_hits"]
    if name == "uniform":
        assert sched.n_shared == 0


@pytest.mark.parametrize("name", ["bit", "one_centroid"])
def test_group_semantics_would_be_wrong_here(name):
    """The data above is one where depth alone cannot answer: the sweep
    without the object test reports objects whose own box misses."""
    data, idx = _index(name, "host")
    sched = idx.artifacts.schedule
    assert sched.n_shared > 0.5 * data.shape[0]
    qs = _queries(data, seed=74)
    groups, _ = schedule_region_numpy(
        dataclasses.replace(sched, n_shared=0), qs)
    want = brute(data, qs)
    assert not (want & ~groups).any()      # a superset ...
    assert (groups & ~want).any()          # ... and a strict one
    if name == "one_centroid":
        assert sched.n_shared == data.shape[0]


def test_host_and_device_builds_agree_on_shared_entries():
    data = DATA["bit"]()
    levels = _levels("bit", data.shape[0])
    host = _index("bit", "host")[1].artifacts.schedule
    dev = ops.device_schedule(data, levels=levels, interpret=True)
    assert dev.n_shared == host.n_shared > 0
    for f in ("obj_mbr", "obj_slot", "obj_id"):
        assert np.array_equal(getattr(dev, f), getattr(host, f))
    # shared entries first, each part in id order
    assert (np.diff(dev.obj_id[:dev.n_shared]) > 0).all()
    assert (np.diff(dev.obj_id[dev.n_shared:]) > 0).all()


def _op_histogram(lowered):
    text = lowered.as_text()
    return collections.Counter(re.findall(r"(?:stablehlo|mhlo)\.\w+", text))


def _parent_fused(queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot,
                  obj_id, *, n_objects):
    """The fused search of a pyramid as it was before the object test:
    the sweep, the entry gather and the id scatter."""
    act = ops.level_sweep(queries, mbr_cm, parent, block_w=128,
                          root_unconditional=False, interpret=True)
    visits = jnp.transpose(act.sum(axis=2, dtype=jnp.int32))
    hit = jnp.transpose(act[obj_level, :, obj_slot])
    hits = jnp.zeros((queries.shape[0], n_objects), jnp.bool_)
    return hits.at[:, obj_id].max(hit), visits


def _lower_fused(sched, qs):
    """The fused search of a pyramid schedule, lowered."""
    run = functools.partial(
        ops.fused_search, n_objects=sched.n_objects, root_unconditional=False,
        test_object_mbr=False, n_shared=sched.n_shared, block_w=128,
        interpret=True)
    return jax.jit(run).lower(qs, sched.mbr_cm, sched.parent, sched.obj_mbr,
                              sched.obj_level, sched.obj_slot, sched.obj_id)


def test_isolating_build_keeps_the_program_without_object_test():
    _, idx = _index("uniform", "device")
    sched = idx.artifacts.schedule
    assert sched.n_shared == 0
    staged = ops.stage_schedule(sched)
    assert staged.statics["confirm_w"] == 0 and len(staged.arrays) == 6
    qs = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    lowered = _lower_fused(sched, qs)
    parent = jax.jit(_parent_fused, static_argnames="n_objects").lower(
        qs, *staged.arrays, n_objects=sched.n_objects)
    assert _op_histogram(lowered) == _op_histogram(parent)
    assert len(lowered.in_avals[0]) == len(parent.in_avals[0]) == 7

    # the same schedule with shared entries adds the test; staged, it
    # also carries their count, as a scalar operand
    shared = dataclasses.replace(sched, n_shared=40)
    confirming = _lower_fused(shared, qs)
    assert len(confirming.in_avals[0]) == 7
    grown = _op_histogram(confirming) - _op_histogram(parent)
    assert grown["stablehlo.compare"] >= 4
    staged = ops.stage_schedule(shared)
    assert staged.statics["confirm_w"] == 128 and len(staged.arrays) == 7


def _clump(n, m, seed):
    """Uniform squares, the first ``m`` of them moved onto one centroid
    with sizes of their own: exactly ``m`` objects share a group."""
    data = f32_exact(datasets.uniform_squares(n, seed=seed))
    rng = np.random.default_rng(seed)
    half = np.round(rng.uniform(0.5, 30.0, (m, 2)) * 2 ** 10) / 2 ** 10
    data[:m] = np.concatenate([500.0 - half, 500.0 + half], axis=1)
    return data


def test_builds_that_share_a_confirm_width_launch_one_program():
    """Builds whose shared counts differ but round to one confirmed width
    (``flat.confirm_width``) launch the same fused program, so a
    compilation cache serves every data draw of a configuration; each
    answers exactly, with its own count in the counters."""
    n, levels = 3000, bulk.default_levels(3000) + 4
    qs = np.concatenate([_queries(_clump(n, 200, 75), seed=76),
                         np.array([[480.0, 480.0, 520.0, 520.0]])]
                        ).astype(np.float32)
    lowered = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    idx = {}
    for m in (200, 210):
        data = _clump(n, m, 75)
        idx[m] = SpatialIndex.build(
            data, structure="pyramid", build="device", backend="pallas",
            levels=levels, backend_opts={"interpret": True, "block_w": 128})
        assert idx[m].artifacts.unisolated_objects == m
        if m == 200:
            res = idx[m].region(qs)
        else:  # the second build: nothing may be lowered anew
            jax.monitoring.register_event_duration_secs_listener(on_event)
            try:
                res = idx[m].region(qs)
            finally:
                jax.monitoring.unregister_event_duration_listener(on_event)
        want = brute(data, qs)
        assert np.array_equal(res.hits, want)
        assert idx[m].stats.confirm_hits == int(want[:, :m].sum())
    assert lowered == []
