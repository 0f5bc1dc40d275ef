"""Device bulk build == host pyramid_schedule, bit for bit.

The acceptance contract of DESIGN.md §7: the one-launch Pallas build
kernel (and its jit'd jnp engine) emits a ``LevelSchedule`` identical to
the host ``flat.pyramid_schedule(bulk.build_pyramid(...))`` lowering on
every parity-matrix dataset shape — so the fused scan's hit sets AND
per-level access counts are unchanged, only where the build runs moves.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import bulk, datasets, flat
from repro.index import SpatialIndex
from repro.kernels import ops
from repro.kernels.ops import build_levels_pallas

DATASETS = {
    "uniform_squares": lambda: datasets.uniform_squares(300, seed=5),
    # the paper's zero-overlap case: degenerate point MBRs (§4)
    "uniform_points": lambda: datasets.uniform_points(256, seed=2),
    "exponential_squares": lambda: datasets.exponential_squares(250, seed=9),
}

SCHEDULE_FIELDS = (
    "mbr_cm", "parent", "n_real", "obj_mbr", "obj_level", "obj_slot", "obj_id"
)


def host_schedule(data, levels):
    pyr = bulk.build_pyramid(jnp.asarray(data, jnp.float32), levels=levels)
    return flat.pyramid_schedule(pyr, np.asarray(data, np.float32))


@pytest.mark.parametrize("name", sorted(DATASETS))
@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_device_schedule_matches_host_lowering(name, engine):
    data = DATASETS[name]()
    levels = bulk.default_levels(data.shape[0])
    host = host_schedule(data, levels)
    dev = ops.device_schedule(data, levels=levels, engine=engine,
                              interpret=True)
    for f in SCHEDULE_FIELDS:
        assert np.array_equal(getattr(host, f), getattr(dev, f)), (
            f"device build field {f} diverges from host lowering ({engine})"
        )
    assert dev.n_objects == host.n_objects
    assert dev.root_unconditional == host.root_unconditional is False
    assert dev.test_object_mbr == host.test_object_mbr is False


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_device_schedule_scan_parity(name):
    """Fused-scan hit sets and per-level access counts over the device
    schedule are bit-identical to the host pyramid path."""
    data = DATASETS[name]()
    levels = bulk.default_levels(data.shape[0])
    qs = datasets.region_queries(data, 8, seed=6)
    h_hits, h_visits = ops.pyramid_scan(host_schedule(data, levels), qs)
    d_hits, d_visits = ops.pyramid_scan(
        ops.device_schedule(data, levels=levels), qs
    )
    assert np.array_equal(np.asarray(h_hits), np.asarray(d_hits))
    assert np.array_equal(np.asarray(h_visits), np.asarray(d_visits))


@pytest.mark.parametrize("n", [1, 130, 300])
def test_build_kernel_onehot_matches_gather(n):
    """The gather-free tiled subdivision (TPU lowering) and the
    interpreter's gather path must emit the same build."""
    data = datasets.uniform_squares(n, seed=5).astype(np.float32)
    a = build_levels_pallas(jnp.asarray(data), levels=6, interpret=True,
                            onehot_gather=True)
    b = build_levels_pallas(jnp.asarray(data), levels=6, interpret=True,
                            onehot_gather=False)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n", [1, 2, 130, 257])
def test_build_kernel_edge_sizes(n):
    """Non-lane-multiple and degenerate object counts stay bit-identical
    across engines (padding lanes must never leak into the schedule)."""
    data = datasets.uniform_points(n, seed=1)
    a = ops.device_schedule(data, engine="pallas", interpret=True)
    b = ops.device_schedule(data, engine="jnp")
    for f in SCHEDULE_FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), (n, f)


def test_facade_device_build_parity_and_strictness():
    data = DATASETS["uniform_squares"]()
    qs = datasets.region_queries(data, 6, seed=6)
    ref = SpatialIndex.build(data, structure="pyramid", backend="host")
    refr = ref.region(qs)
    for backend in ("host", "lax", "pallas", "serve"):
        idx = SpatialIndex.build(
            data, structure="pyramid", backend=backend, build="device"
        )
        res = idx.region(qs)
        assert np.array_equal(res.hits, refr.hits), backend
        assert np.array_equal(res.visits_per_level, refr.visits_per_level)
    # device build is a pyramid-only option; pointer structures refuse it
    with pytest.raises(TypeError, match="does not accept"):
        SpatialIndex.build(data, structure="mqr", build="device")
    with pytest.raises(ValueError, match="unknown build"):
        SpatialIndex.build(data, structure="pyramid", build="gpu")


def test_extend_flush_always_is_the_legacy_rebuild():
    """flush="always" on a never-mutated index reproduces the old eager
    re-build bit-for-bit: fresh artifacts, no live-update state."""
    base = datasets.uniform_squares(200, seed=5)
    more = datasets.uniform_squares(80, seed=77)
    qs = datasets.region_queries(np.concatenate([base, more]), 6, seed=6)
    idx = SpatialIndex.build(
        base, structure="pyramid", backend="pallas", build="device"
    )
    ext = idx.extend(more, flush="always")
    assert ext.n_objects == 280
    assert ext.backend == "pallas" and ext.structure == "pyramid"
    assert ext._updates is None  # pristine: no update log attached
    fresh = SpatialIndex.build(
        np.concatenate([base, more]), structure="pyramid",
        backend="pallas", build="device",
    )
    a, b = ext.region(qs), fresh.region(qs)
    assert np.array_equal(a.hits, b.hits)
    assert np.array_equal(a.visits_per_level, b.visits_per_level)
    # the original index is untouched
    assert idx.n_objects == 200
    # extend works on pointer structures too (host re-build)
    mq = SpatialIndex.build(base, structure="mqr", backend="pallas")
    mq2 = mq.extend(more, flush="always")
    assert mq2.n_objects == 280
    ref = SpatialIndex.build(
        np.concatenate([base, more]), structure="mqr", backend="host"
    ).region(qs)
    assert np.array_equal(mq2.region(qs).hits, ref.hits)
    with pytest.raises(ValueError, match="unknown flush"):
        idx.extend(more, flush="eventually")


def test_extend_default_routes_through_the_delta_buffer():
    """Default extend buffers the batch (no rebuild) yet answers the same
    hit-id sets as a fresh build over the concatenated objects."""
    base = datasets.uniform_squares(200, seed=5)
    more = datasets.uniform_squares(80, seed=77)
    qs = datasets.region_queries(np.concatenate([base, more]), 6, seed=6)
    idx = SpatialIndex.build(
        base, structure="pyramid", backend="pallas", build="device"
    )
    ext = idx.extend(more)
    assert ext.n_objects == 280
    assert idx.n_objects == 200 and idx._updates is None  # source untouched
    assert ext._updates is not None and ext._updates.n_delta == 80
    assert ext._updates.flushes == 0  # buffered, not rebuilt
    fresh = SpatialIndex.build(
        np.concatenate([base, more]), structure="pyramid",
        backend="pallas", build="device",
    )
    a, b = ext.region(qs), fresh.region(qs)
    for i in range(qs.shape[0]):
        assert np.array_equal(a.ids(i), b.ids(i))
    # per-query delta-side accesses are reported separately
    assert a.base_levels == idx.schedule.levels
    assert int(a.delta_visits.sum()) == int(ext.stats.delta_accesses)
