"""Serving front end: batching, admission, tenants, parity (DESIGN.md §11).

The contract under test, in order of importance:

1. every queue-served answer is BIT-IDENTICAL to calling the tenant's
   SpatialIndex directly — including while a FaultPlan forces the pallas
   rung to fail mid-run (degradation shows as slower batches, never as
   wrong or failed answers);
2. continuous batching launches on EITHER bound — a full query_block, or
   the oldest request's deadline slack running out (driven by a fake
   clock, so the tests are deterministic);
3. admission control sheds or parks per SLO class, visibly in the
   per-tenant AccessStats ledger;
4. the boundary rejects degenerate geometry with the typed
   InvalidQueryError before it can poison a batch;
5. tenants are isolated: one tenant's mutations bump only its own epoch,
   the other's cached answers stay valid and bit-identical.
"""

from __future__ import annotations

import pathlib
import re

import numpy as np
import pytest

from conftest import f32_exact, mbr_dataset

from repro.ft import FaultPlan, InjectedFailure
from repro.index import InvalidQueryError, SpatialIndex
from repro.serve import (
    OverloadShed,
    ServerConfig,
    ServingFrontEnd,
    SLOClass,
    TenantConfig,
)
from repro.serve.loadgen import data_extent, poisson_arrivals, rect_workload
from repro.serve.telemetry import LatencyHistogram

MOD = "serve_front"
N = 220


class FakeClock:
    """Deterministic front-end clock: time moves only when told to."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _data(kind: str = "exponential_squares") -> np.ndarray:
    return f32_exact(mbr_dataset(MOD, kind, N))


def _front(*, query_block=4, clock=None, classes=None, tenants=None,
           data=None, **cfg_extra) -> ServingFrontEnd:
    mbrs = _data() if data is None else data
    cfg = ServerConfig.from_dict({
        "tenants": tenants or [
            {"name": "a", "backend": "host"},
        ],
        "classes": classes or [
            {"name": "interactive", "deadline_ms": 50.0,
             "overload": "shed", "max_queue": 8},
            {"name": "batch", "deadline_ms": 2000.0, "overload": "queue",
             "max_queue": 4},
        ],
        "query_block": query_block,
        **cfg_extra,
    })
    names = [t.name for t in cfg.tenants]
    return ServingFrontEnd.build(
        cfg, {n: mbrs for n in names},
        clock=clock or FakeClock(),
    )


# ---------------------------------------------------------------------------
# declarative config boundary (the factory-config contract)
# ---------------------------------------------------------------------------


def test_config_typo_raises_with_accepted_keys():
    with pytest.raises(TypeError, match="bakend.*accepted"):
        TenantConfig.from_dict({"name": "a", "bakend": "serve"})
    with pytest.raises(TypeError, match="deadlines_ms"):
        ServerConfig.from_dict({
            "tenants": [{"name": "a"}],
            "classes": [{"name": "x", "deadlines_ms": 5}],
        })


def test_config_bad_values_fail_at_the_boundary():
    with pytest.raises(ValueError, match="structure"):
        TenantConfig(name="a", structure="kdtree")
    with pytest.raises(ValueError, match="backend"):
        TenantConfig(name="a", backend="gpu")
    with pytest.raises(ValueError, match="overload"):
        SLOClass("x", deadline_ms=10, overload="drop")
    with pytest.raises(ValueError, match="duplicate"):
        ServerConfig.from_dict(
            {"tenants": [{"name": "a"}, {"name": "a"}]}
        )
    with pytest.raises(ValueError, match="at least one tenant"):
        ServerConfig.from_dict({"tenants": []})


def test_unknown_tenant_kind_and_slo_rejected():
    front = _front()
    with pytest.raises(ValueError, match="unknown tenant"):
        front.submit("nope", "region", [0, 0, 1, 1])
    with pytest.raises(ValueError, match="unknown kind"):
        front.submit("a", "nearest", [0, 0])
    with pytest.raises(ValueError, match="unknown SLO class"):
        front.submit("a", "region", [0, 0, 1, 1], slo="platinum")


# ---------------------------------------------------------------------------
# continuous batching: size bound and deadline bound
# ---------------------------------------------------------------------------


def test_full_block_launches_without_waiting():
    clock = FakeClock()
    front = _front(query_block=4, clock=clock)
    reqs = [front.submit("a", "region", [0, 0, 9, 9]) for _ in range(4)]
    assert front.pump() == 1          # size bound tripped, clock never moved
    assert all(r.done for r in reqs)
    assert front.telemetry.deadline_launches == 0
    assert front.telemetry.avg_batch == 4.0


def test_partial_batch_waits_then_launches_on_deadline_slack():
    clock = FakeClock()
    front = _front(query_block=4, clock=clock)
    req = front.submit("a", "region", [0, 0, 9, 9])   # 50 ms deadline
    assert front.pump() == 0          # fresh: plenty of slack
    clock.advance(0.010)
    assert front.pump() == 0          # 10 ms in: still slack
    clock.advance(0.038)              # 48 ms in: inside slack margin
    assert front.pump() == 1
    assert req.done
    assert front.telemetry.deadline_launches == 1
    # the ticket records the full enqueue -> launch -> complete timeline
    tl = req.timeline()
    assert tl.queue_wait == pytest.approx(0.048)
    assert tl.latency >= tl.queue_wait


def test_coalescing_groups_by_tenant_and_k():
    front = _front(
        query_block=8,
        tenants=[{"name": "a", "backend": "host"},
                 {"name": "b", "backend": "host"}],
    )
    front.submit("a", "region", [0, 0, 1, 1])
    front.submit("a", "point", [0.5, 0.5])
    front.submit("a", "count", [0, 0, 2, 2])
    front.submit("b", "region", [0, 0, 1, 1])
    front.submit("a", "knn", [0.5, 0.5], k=3)
    front.submit("a", "knn", [0.1, 0.1], k=5)
    # rect kinds coalesce per tenant; knn splits further per k
    assert front.queue.pending() == 6
    assert len(front.queue.drain_keys()) == 4
    assert front.drain() == 4
    assert front.telemetry.completed == 6


# ---------------------------------------------------------------------------
# admission control: shed and queue per SLO class
# ---------------------------------------------------------------------------


def test_overload_shed_returns_typed_ticket_and_counts():
    front = _front(classes=[
        {"name": "interactive", "deadline_ms": 50.0, "overload": "shed",
         "max_queue": 2},
    ])
    r1 = front.submit("a", "region", [0, 0, 1, 1])
    r2 = front.submit("a", "region", [0, 0, 2, 2])
    r3 = front.submit("a", "region", [0, 0, 3, 3])   # over max_queue=2
    assert r3.status == "shed"
    with pytest.raises(OverloadShed, match="shed by admission control"):
        front.result(r3)
    assert front.telemetry.shed == 1
    assert front.stats("a").shed_queries == 1
    # the admitted requests still complete normally
    front.drain()
    assert r1.done and r2.done
    assert front.telemetry.completed == 2


def test_overload_queue_parks_but_still_serves():
    clock = FakeClock()
    front = _front(clock=clock, classes=[
        {"name": "batch", "deadline_ms": 100.0, "overload": "queue",
         "max_queue": 1},
    ])
    r1 = front.submit("a", "region", [0, 0, 1, 1])
    r2 = front.submit("a", "region", [0, 0, 2, 2])   # parked past max_queue
    assert r2.parked and r2.status == "pending"
    assert front.stats("a").queued_queries == 1
    # parked requests never drive the deadline bound...
    clock.advance(10.0)
    front.pump()
    assert r1.done          # r1's deadline launched the group
    assert r2.done          # ...but parked riders launch with it, FIFO
    assert front.telemetry.queued_overload == 1


def test_open_loop_recovers_after_a_stall():
    """An open-loop submitter that stamps each request with its scheduled
    arrival (as the benchmark's does) and pumps after every submit falls
    behind during a stall.  A launch here costs the same for one request
    as for a block, as a padded one does, so launching each late request
    on its own the moment it is submitted would keep the submitter behind
    for good; the front end instead coalesces such requests, and once the
    backlog is served launches are full blocks again and arrivals are on
    time."""
    clock = FakeClock(0.0)
    front = _front(query_block=16, clock=clock, classes=[
        {"name": "batch", "deadline_ms": 2000.0, "overload": "queue",
         "max_queue": 65536},
    ])
    index = front.tenants["a"].index
    region = index.region

    def fixed_cost_region(rects):
        out = region(rects)
        clock.advance(0.52)
        return out

    index.region = fixed_cost_region
    rate, seconds, stall_at = 24.0, 40.0, 5.0
    targets = np.arange(1, int(rate * seconds)) / rate
    tickets, lateness, stalled = [], [], False
    for target in targets:
        while clock() < target:
            if not front.pump():
                clock.advance(min(target - clock(), 1e-3))
        if not stalled and target >= stall_at:
            clock.advance(2.5)            # the host stalls once
            stalled = True
        lateness.append(clock() - target)
        tickets.append(front.submit("a", "region", [0, 0, 9, 9], slo="batch",
                                    t_arrival=target))
        front.pump()
    while any(not t.done for t in tickets):
        if not front.pump():
            clock.advance(1e-3)
    assert max(lateness) > 2.0            # the stall made arrivals late
    last = [t for t in tickets if t.t_arrival >= seconds - 10.0]
    assert max(lateness[-len(last):]) < 0.6
    sizes = {}
    for t in last:
        sizes[t.t_launch] = sizes.get(t.t_launch, 0) + 1
    assert np.mean(list(sizes.values())) >= 12


# ---------------------------------------------------------------------------
# the hardened boundary
# ---------------------------------------------------------------------------


def test_degenerate_geometry_rejected_typed_and_batch_unpoisoned():
    front = _front()
    good = front.submit("a", "region", [0, 0, 5, 5])
    for bad in ([np.nan, 0, 1, 1], [0, 0, np.inf, 1], [3, 0, 1, 1]):
        with pytest.raises(InvalidQueryError):
            front.submit("a", "region", bad)
    with pytest.raises(InvalidQueryError, match="finite"):
        front.submit("a", "point", [np.nan, 0.5])
    with pytest.raises(InvalidQueryError, match="k"):
        front.submit("a", "knn", [0.5, 0.5], k=0)
    with pytest.raises(InvalidQueryError, match="exceeds"):
        front.submit("a", "knn", [0.5, 0.5], k=N + 1)
    # InvalidQueryError is a ValueError: one except clause serves both
    assert issubclass(InvalidQueryError, ValueError)
    # the rejected requests never entered the queue
    assert front.queue.pending() == 1
    ref = SpatialIndex.build(_data(), backend="host")
    hits = front.result(good).hits
    assert (hits == ref.region(np.array([[0, 0, 5, 5]], np.float32))
            .hits[0]).all()
    assert front.telemetry.rejected == 4


def test_served_engine_boundary_is_hardened_too():
    # satellite: the low-level SpatialServer validates as well, so even
    # callers that bypass the front end can't poison a padded batch
    idx = SpatialIndex.build(_data(), backend="serve", query_block=4)
    with pytest.raises(InvalidQueryError):
        idx.region(np.array([[0, 0, np.nan, 1]], np.float32))
    with pytest.raises(InvalidQueryError):
        idx.region(np.array([[5, 0, 1, 1]], np.float32))


# ---------------------------------------------------------------------------
# bit-parity: served == direct, always — the acceptance criterion
# ---------------------------------------------------------------------------


def _drive_mixed(front, tenant, rects, *, knn_every=4, k=3):
    """Submit a mixed open-loop trace; return [(req, kind, payload)]."""
    out = []
    for i, rect in enumerate(rects):
        if knn_every and i % knn_every == knn_every - 1:
            req = front.submit(tenant, "knn", rect[:2], k=k)
            out.append((req, "knn", rect[:2]))
        else:
            kind = ("region", "count", "point")[i % 3]
            payload = rect[:2] if kind == "point" else rect
            req = front.submit(tenant, kind, payload)
            out.append((req, kind, payload))
        front.pump()
    front.drain()
    return out


def _assert_parity(front, tenant, served, ref=None):
    """Every served answer == calling the index directly, bit for bit.

    ``ref`` defaults to the tenant's OWN index (the acceptance
    criterion); pass an independent host-backend index to additionally
    assert the repo-wide cross-backend parity on region hits.
    """
    if ref is None:
        ref = front.tenants[tenant].index
    for req, kind, payload in served:
        got = front.result(req)
        if kind == "knn":
            r = ref.knn(np.asarray(payload, np.float32)[None], k=req.k)
            assert (got[0] == r.ids[0]).all()
            assert (got[1] == r.dists[0]).all()
            continue
        rect = (
            np.concatenate([payload, payload])
            if kind == "point" else payload
        )
        r = ref.region(np.asarray(rect, np.float32)[None])
        if kind == "count":
            assert got == int(r.hits[0].sum())
        else:
            assert (got.hits == r.hits[0]).all()
            assert (got.visits == r.visits_per_level[0]).all()


@pytest.mark.parametrize("backend", ["host", "serve"])
def test_every_served_answer_bit_identical_to_direct(backend):
    data = _data()
    opts = {"backoff": 0.0} if backend == "serve" else {}
    front = _front(
        query_block=4,
        tenants=[{"name": "t", "backend": backend, "backend_opts": opts}],
        data=data,
    )
    rects = rect_workload(data_extent(data), 24, seed=11, sel=0.2)
    served = _drive_mixed(front, "t", rects)
    assert all(r.done for r, _, _ in served)
    # the acceptance criterion: served == the tenant's own index, direct
    _assert_parity(front, "t", served)
    # and region hits also match an INDEPENDENT host-backend reference
    # (cross-backend hit parity is the repo-wide invariant)
    ref = SpatialIndex.build(data, backend="host")
    for req, kind, payload in served:
        if kind == "region":
            r = ref.region(np.asarray(payload, np.float32)[None])
            assert (front.result(req).hits == r.hits[0]).all()


def test_parity_survives_mid_run_forced_degradation():
    """FaultPlan starts killing the pallas rung partway through the run:
    answers stay bit-identical, the ladder records the degradation."""
    data = _data()
    front = _front(
        query_block=4,
        tenants=[{"name": "t", "backend": "serve",
                  "backend_opts": {"backoff": 0.0, "max_retries": 0}}],
        data=data,
    )
    front.warmup()
    plan = FaultPlan(fail_launches=10 ** 9, fail_from_launch=3,
                     fail_rungs=("pallas",))
    front.bind_fault_plan(plan)

    rects = rect_workload(data_extent(data), 20, seed=13, sel=0.2)
    served = _drive_mixed(front, "t", rects, knn_every=0)
    assert all(r.done for r, _, _ in served)          # zero user-visible errors
    _assert_parity(front, "t", served)
    # the fault landed: healthy pallas batches first, lax degradation after
    assert plan.launch_failures > 0
    stats = front.stats("t")
    assert stats.degraded_batches > 0
    assert stats.rung_dispatches.get("pallas", 0) > 0
    assert stats.rung_dispatches.get("lax", 0) > 0


def test_fail_from_launch_arms_after_n_attempts():
    plan = FaultPlan(fail_launches=2, fail_from_launch=2)
    plan.launch("lax")        # not a failing rung: not even counted
    plan.launch("pallas")     # 1st pallas attempt: healthy
    plan.launch("pallas")     # 2nd: healthy
    with pytest.raises(InjectedFailure):
        plan.launch("pallas")  # 3rd: countdown armed
    with pytest.raises(InjectedFailure):
        plan.launch("pallas")
    plan.launch("pallas")     # countdown exhausted
    assert plan.launches_seen == 5
    assert plan.launch_failures == 2


# ---------------------------------------------------------------------------
# multi-tenant isolation: epochs and caches
# ---------------------------------------------------------------------------


def test_tenant_mutation_bumps_only_its_own_epoch_and_cache():
    data = _data()
    front = _front(
        query_block=2,
        tenants=[
            {"name": "a", "backend": "serve", "capacity": 32,
             "backend_opts": {"backoff": 0.0}},
            {"name": "b", "backend": "serve",
             "backend_opts": {"backoff": 0.0}},
        ],
        data=data,
    )
    rect = [0.0, 0.0, 0.6, 0.6]

    def ask(tenant):
        r = front.submit(tenant, "region", rect)
        front.drain()
        return front.result(r)

    first_a, first_b = ask("a"), ask("b")
    b_server = front.tenants["b"].spatial._backend.server
    hits_before = b_server.stats.cache_hits

    # tenant A mutates: insert inside the query rect, then merge
    gid = front.insert("a", np.array([[0.1, 0.1, 0.2, 0.2]], np.float32))
    front.flush("a")
    assert front.tenants["a"].epoch > 0
    assert front.tenants["b"].epoch == 0    # B untouched

    second_a, second_b = ask("a"), ask("b")
    # A sees its new object; B's answer is bit-identical to before...
    assert second_a.hits[int(gid[0])]
    assert (second_b.hits == first_b.hits).all()
    assert (second_b.visits == first_b.visits).all()
    # ...and was served from B's still-valid epoch-tagged cache
    assert b_server.stats.cache_hits == hits_before + 1
    # fresh reference agrees with the cached answer
    ref = SpatialIndex.build(data, backend="host")
    assert (second_b.hits == ref.region(
        np.asarray(rect, np.float32)[None]).hits[0]).all()


def test_durable_tenant_recovers_across_front_end_restart(tmp_path):
    data = _data()
    root = str(tmp_path / "tenant_a")
    tenants = [{"name": "a", "backend": "host", "durable_root": root,
                "capacity": 32}]
    front = _front(tenants=tenants, data=data)
    res = front.insert("a", np.array([[0.3, 0.3, 0.4, 0.4]], np.float32))
    assert res.applied
    gid = res.ids
    req = front.submit("a", "region", [0.25, 0.25, 0.45, 0.45])
    front.drain()
    want = front.result(req)

    # restart: same config, NO dataset needed — recovery, not rebuild
    front2 = _front(tenants=tenants, data=data)
    assert front2.tenants["a"].index.recovered_ops == 1
    req2 = front2.submit("a", "region", [0.25, 0.25, 0.45, 0.45])
    front2.drain()
    got = front2.result(req2)
    assert got.hits[int(gid[0])]
    assert (got.hits == want.hits).all()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_latency_histogram_quantiles():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0
    rng = np.random.default_rng(5)
    samples = rng.lognormal(-5.0, 1.0, size=4000)
    for s in samples:
        h.record(s)
    for q in (0.5, 0.9, 0.99, 0.999):
        exact = float(np.quantile(samples, q))
        # log-bucketed: within one 7% growth factor of the exact quantile
        assert exact / 1.07 <= h.quantile(q) <= exact * 1.07
    assert h.quantile(0.5) <= h.quantile(0.99) <= h.quantile(0.999)
    assert h.mean == pytest.approx(samples.mean(), rel=1e-6)
    ms = h.quantiles_ms()
    assert set(ms) == {"p50_ms", "p99_ms", "p999_ms"}


def test_poisson_arrivals_and_snapshot_shape():
    arr = poisson_arrivals(200.0, 1.0, seed=3)
    assert (np.diff(arr) > 0).all() and arr[-1] < 1.0
    assert 120 < len(arr) < 300      # ~200 ± slack
    front = _front()
    front.submit("a", "region", [0, 0, 1, 1])
    front.drain()
    snap = front.telemetry.snapshot()
    for key in ("submitted", "completed", "shed", "p50_ms", "p99_ms",
                "p999_ms", "avg_batch", "slo_violations"):
        assert key in snap
    assert snap["completed"] == 1


# ---------------------------------------------------------------------------
# layering: one documented entry point, no private cross-imports
# ---------------------------------------------------------------------------


def test_no_private_cross_imports_between_serving_layers():
    """The front end uses only PUBLIC surface of the serving engine, and
    nothing outside repro/serve imports its `_`-private symbols — the
    same grep contract the kernel package enforces."""
    root = pathlib.Path(__file__).resolve().parents[1]
    pats = [
        # _-private imports from either launch serving module
        re.compile(
            r"from\s+repro\.launch\.(?:spatial_serve|serve)\s+import"
            r"\s+[^\n]*\b_\w+"
        ),
        re.compile(r"\bspatial_serve\._\w+"),
        # _-private imports from the front-end package, outside it
        re.compile(r"from\s+repro\.serve(?:\.\w+)?\s+import\s+[^\n]*\b_\w+"),
    ]
    offenders = []
    for sub in ("src", "tests", "benchmarks", "examples"):
        for f in sorted((root / sub).rglob("*.py")):
            inside_serve = "serve" in f.parts  # src/repro/serve/*
            text = f.read_text()
            for i, pat in enumerate(pats):
                if i == 2 and inside_serve:
                    continue  # the package may use its own privates
                for m in pat.finditer(text):
                    offenders.append(f"{f.relative_to(root)}: {m.group(0)}")
    assert not offenders, "\n".join(offenders)


def test_serving_layers_document_each_other():
    import repro.serve as front
    from repro.launch import spatial_serve as engine

    assert "repro.serve" in (engine.__doc__ or "")
    assert "front end" in (engine.__doc__ or "").lower()
    assert "front end" in (front.__doc__ or "").lower()
