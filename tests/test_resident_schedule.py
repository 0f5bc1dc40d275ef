"""The ``pallas`` adapter keeps its schedule on the device.

DESIGN.md §12: the adapter stages its schedule once, on its first
launch, and plans the streamed sweep's parent windows once per tile
width; every later launch copies only its queries.  Answers stay
bit-identical to a one-off ``ops.pyramid_scan*`` call with the host
schedule, and ``AccessStats.schedule_stagings`` counts one staging per
adapter however many launches follow.  A merge builds a new adapter over
the new base.  A batch shorter than the adapter's ``query_block`` is
padded up to it with rows that meet no object, sliced off before any
count, so one program serves every batch size.
"""
import numpy as np
import pytest

from conftest import f32_exact
from repro.core import datasets
from repro.core.flat import NEVER_MBR
from repro.index import SpatialIndex
from repro.index.backends import HostBackend
from repro.kernels import ops
from repro.obs import counters as obs_counters
from repro.obs import trace as obs_trace
from repro.serve import ServingFrontEnd

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


# ---------------------------------------------------------------------------
# Short batches are padded to the query block (one program per tenant)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def padded_tenant():
    data = _data(seed=46)
    front = ServingFrontEnd.build(
        {"query_block": 16, "tenants": [{
            "name": "t", "structure": "pyramid", "build": "device",
            "backend": "pallas",
            "backend_opts": {"stream": True, "block_w": 128,
                             "interpret": True}}]},
        {"t": data})
    host = SpatialIndex.build(data, structure="pyramid", build="device",
                              backend="host")
    return front, host, data


@pytest.mark.parametrize("q", range(1, 17))
def test_pallas_tenant_pads_every_batch_size(padded_tenant, q):
    front, host, data = padded_tenant
    rt = front.tenants["t"]
    assert rt.index._backend.query_block == 16
    qs = datasets.region_queries(data, q, seed=100 + q).astype(np.float32)
    before = rt.stats.to_dict()
    tickets = [front.submit("t", "region", x) for x in qs]
    assert front.drain() == 1
    delta = rt.stats.diff(before)
    ref = host.region(qs)  # the unpadded answer
    assert np.array_equal(np.stack([t.result.hits for t in tickets]),
                          ref.hits)
    assert np.array_equal(np.stack([t.result.visits for t in tickets]),
                          ref.visits_per_level)
    assert delta["padded_queries"] == 16 - q
    assert delta["node_accesses"] == ref.visits_per_level.sum()
    assert delta["queries"] == q and delta["launches"] == 1


@pytest.mark.parametrize("precision", ["float32", "compact"])
def test_padding_rows_meet_nothing_and_are_not_reported(precision):
    data = _data(seed=47)
    idx = SpatialIndex.build(
        data, structure="pyramid", backend="pallas",
        backend_opts={"stream": True, "block_w": 128, "interpret": True,
                      "precision": precision, "query_block": 16})
    sched = idx.artifacts.schedule
    qs = datasets.region_queries(data, 3, seed=48).astype(np.float32)
    if precision == "compact":
        ref_hits, ref_visits = ops.fetch(*ops.pyramid_scan_compact(
            idx.artifacts.quantized, qs, interpret=True, stream=True))
        # outward quantization keeps the padding row inverted on both
        # axes: it can meet only a node spanning the whole grid
        q = idx.artifacts.quantized
        t = (NEVER_MBR - q.origin) * q.inv_cell
        cells = np.clip(np.concatenate([np.floor(t[:2]), np.ceil(t[2:])]),
                        0, q.cells)
        assert (cells[:2] > cells[2:]).all()
    else:
        ref_hits, ref_visits = ops.fetch(*ops.pyramid_scan(
            sched, qs, interpret=True, stream=True))
        nodes = sched.mbr_cm.transpose(0, 2, 1)  # (L, W, 4)
        pad = NEVER_MBR
        assert not ((nodes[..., 0] <= pad[2]) & (pad[0] <= nodes[..., 2])
                    & (nodes[..., 1] <= pad[3])
                    & (pad[1] <= nodes[..., 3])).any()
    obs_counters.collect_launch_reports(True)
    try:
        res = idx.region(qs)
    finally:
        obs_counters.collect_launch_reports(False)
    assert np.array_equal(res.hits, ref_hits)
    assert np.array_equal(res.visits_per_level, ref_visits)
    assert idx.stats.padded_queries == 13
    report = res.launch_report
    assert report.queries == 3 and report.query_block == 16
    assert report.survivors_per_level == tuple(
        int(x) for x in ref_visits.sum(axis=0))


@pytest.mark.parametrize("opts, tuned", [
    ({"autotune": "on"}, True),
    ({"autotune": "off"}, False),
    ({"autotune": "on", "block_w": 512}, False),
])
def test_explicit_query_block_fixes_every_launch_size(monkeypatch, opts,
                                                      tuned):
    """An explicit ``query_block`` fixes the launch size of every
    autotune candidate, so the tuner picks the tile alone;
    ``autotune="off"`` or an explicit ``block_w`` pins the whole
    configuration and times nothing.  (Streamed: every candidate is a
    fused launch, none the per-level plan, which launches unpadded.)"""
    from repro.kernels import autotune

    seen = []
    real_tune = autotune.tune

    def spy(make_run, cands, **kw):
        seen.append(list(cands))
        return real_tune(make_run, cands, **kw)

    monkeypatch.setattr(autotune, "tune", spy)
    data = _data(seed=49)
    idx = SpatialIndex.build(
        data, structure="pyramid", backend="pallas",
        backend_opts={"interpret": True, "stream": True, "query_block": 16,
                      **opts})
    qs = datasets.region_queries(data, 5, seed=50).astype(np.float32)
    ref = SpatialIndex.build(data, structure="pyramid", backend="host")
    assert np.array_equal(idx.region(qs).hits, ref.region(qs).hits)
    cfg = idx._backend._config(qs)
    assert cfg.query_block == 16
    if tuned:
        assert len(seen) == 1
        assert {c.query_block for c in seen[0]} == {16}
        assert len({c.block_w for c in seen[0]}) > 1
    else:
        assert seen == []
        assert cfg == autotune.TileConfig(opts.get("block_w", 128), 16, True)
    before = idx.stats.padded_queries
    res = idx.region(qs)  # the tuned configuration is cached: no probes
    assert np.array_equal(res.hits, ref.region(qs).hits)
    assert idx.stats.padded_queries - before == 11
