"""The ``pallas`` adapter keeps its schedule on the device.

DESIGN.md §12: the adapter stages its schedule once, on its first
launch, and plans the streamed sweep's parent windows once per tile
width; every later launch copies only its queries.  Answers stay
bit-identical to a one-off ``ops.pyramid_scan*`` call with the host
schedule, and ``AccessStats.schedule_stagings`` counts one staging per
adapter however many launches follow.  A merge builds a new adapter over
the new base.
"""
import numpy as np
import pytest

from conftest import f32_exact
from repro.core import datasets
from repro.index import SpatialIndex
from repro.index.backends import HostBackend
from repro.kernels import ops
from repro.obs import trace as obs_trace

N = 600  # pyramid width: 2 tiles at block_w=512, 5 at 128


def _data(seed=41):
    return f32_exact(datasets.uniform_squares(N, seed=seed))


@pytest.mark.parametrize("precision", ["float32", "compact"])
@pytest.mark.parametrize("block_w", [128, 512])
@pytest.mark.parametrize("stream", [False, True])
def test_resident_schedule_answers_and_stages_once(stream, block_w,
                                                   precision):
    data = _data()
    idx = SpatialIndex.build(
        data, structure="pyramid", backend="pallas",
        backend_opts={"stream": stream, "block_w": block_w,
                      "precision": precision, "interpret": True},
    )
    batches = [datasets.region_queries(data, 8, seed=42 + i)
               .astype(np.float32) for i in range(3)]
    sched = idx.artifacts.schedule
    refs = []
    for qs in batches:
        obs_trace.drain_counters()
        hits, visits = ops.fetch(*ops.pyramid_scan(
            sched, qs, block_w=block_w, interpret=True, stream=stream))
        # a one-off call with the host schedule stages it for that call
        assert obs_trace.drain_counters()["schedule_stagings"] == 1
        if precision == "compact":
            _, visits = ops.fetch(*ops.pyramid_scan_compact(
                idx.artifacts.quantized, qs, block_w=block_w,
                interpret=True, stream=stream))
        refs.append((hits, visits))

    obs_trace.drain_counters()
    for i, (qs, (ref_hits, ref_visits)) in enumerate(zip(batches, refs)):
        before = idx.stats.to_dict()
        res = idx.region(qs)
        delta = idx.stats.diff(before)
        assert np.array_equal(res.hits, ref_hits)
        assert np.array_equal(res.visits_per_level, ref_visits)
        if i:  # past the warm launch only the queries reach the device
            assert delta["h2d_bytes"] == qs.nbytes
            assert delta["schedule_stagings"] == 0
    assert idx.stats.schedule_stagings == 1


def test_merge_builds_a_new_resident_adapter():
    data = _data(seed=43)
    idx = SpatialIndex.build(
        data, structure="pyramid", backend="pallas",
        backend_opts={"stream": True, "block_w": 128, "interpret": True},
        merge=dict(capacity=32, auto=False),
    )
    qs = datasets.region_queries(data, 6, seed=44).astype(np.float32)
    idx.region(qs)
    old = idx._current_backend()
    assert old._staged is not None

    idx.insert(f32_exact(datasets.uniform_squares(12, seed=45)))
    assert idx.flush()
    host = idx.with_backend("host")
    assert np.array_equal(idx.region(qs).hits, host.region(qs).hits)

    # the base adapter over the merged build stages the new schedule
    new = idx._current_backend()
    assert new is not old
    hits, visits, _ = new.region(qs)
    ref_hits, ref_visits, _ = HostBackend(idx.artifacts).region(qs)
    assert np.array_equal(hits, ref_hits)
    assert np.array_equal(visits, ref_visits)
    assert new._staged.source is idx.artifacts.schedule
