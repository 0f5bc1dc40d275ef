"""Observability layer (DESIGN.md §13): spans, ledger parity, metrics.

The contract under test:

1. spans nest and ALWAYS close — normal exit, exceptions, and the
   fault harness's BaseException kills all leave a complete ("X") event
   with the error type stamped in args;
2. the exported trace.json is Perfetto-loadable: valid Chrome trace
   schema, facade -> backend spans contained per thread, degradation
   rung transitions visible as instants;
3. the per-launch counter ledger discloses EXACTLY the numbers the §12
   bench computes — ``RegionResult.launch_report.bytes_streamed`` is
   bit-for-bit the bench's "bytes-streamed-skip-uint16" row;
4. the metrics registry renders well-formed Prometheus text and JSON,
   with per-tenant latency quantiles;
5. stage spans feed always-on counters, the ring buffer only while the
   tracer is on, and the JAX profiler only while a session collects;
   across a served launch the counters equal the bytes staged and
   fetched and the kNN rounds run.
"""

from __future__ import annotations

import glob
import json
import re
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

from repro.core import datasets
from repro.ft import FaultPlan, KillPoint
from repro.index import SpatialIndex
from repro.kernels import ops
from repro.obs import counters as obs_counters
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import ServerConfig, ServingFrontEnd
from repro.serve.telemetry import LatencyHistogram


@pytest.fixture
def tracer():
    """A fresh, enabled process tracer; the previous one is restored."""
    old = obs_trace.get_tracer()
    t = obs_trace.set_tracer(obs_trace.Tracer())
    t.enabled = True
    yield t
    obs_trace.set_tracer(old)


@pytest.fixture
def ledger():
    obs_counters.collect_launch_reports(True)
    yield
    obs_counters.collect_launch_reports(False)


def _index(**backend_opts):
    data = datasets.uniform_squares(220, seed=41)
    queries = datasets.region_queries(data, 8, seed=42).astype(np.float32)
    idx = SpatialIndex.build(data, structure="pyramid", backend="pallas",
                             build="device", backend_opts=backend_opts)
    return idx, queries


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpans:
    def test_disabled_tracing_returns_shared_null_span(self):
        old = obs_trace.get_tracer()
        t = obs_trace.set_tracer(obs_trace.Tracer())
        try:
            assert t.enabled is False
            assert obs_trace.span("x") is obs_trace.NULL_SPAN
            assert t.span("x") is obs_trace.NULL_SPAN
            with obs_trace.span("x", a=1) as s:
                s.annotate(b=2)
                s.event("inner")
            obs_trace.instant("i")
            obs_trace.counter("c", v=1)
            assert t.events() == []
        finally:
            obs_trace.set_tracer(old)

    def test_spans_nest_by_containment(self, tracer):
        with obs_trace.span("outer"):
            with obs_trace.span("inner"):
                pass
        ev = {e["name"]: e for e in tracer.events()}
        out, inn = ev["outer"], ev["inner"]
        assert out["ph"] == inn["ph"] == "X"
        assert out["tid"] == inn["tid"]
        assert out["ts"] <= inn["ts"]
        assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"]

    def test_span_closes_under_exception_and_records_error(self, tracer):
        with pytest.raises(ValueError):
            with obs_trace.span("boom", n=3):
                raise ValueError("nope")
        (e,) = tracer.events()
        assert e["name"] == "boom" and e["ph"] == "X"
        assert e["args"]["error"] == "ValueError"
        assert e["args"]["n"] == 3

    def test_span_closes_under_base_exception_kill(self, tracer):
        # the fault harness's KillPoint subclasses BaseException
        with pytest.raises(KillPoint):
            with obs_trace.span("killed"):
                raise KillPoint("simulated crash")
        (e,) = tracer.events()
        assert e["args"]["error"] == "KillPoint"

    def test_ring_buffer_bounds_and_counts_drops(self):
        t = obs_trace.Tracer(capacity=4)
        t.enabled = True
        for i in range(10):
            t.instant(f"e{i}")
        ev = t.events()
        assert len(ev) == 4
        assert [e["name"] for e in ev] == ["e6", "e7", "e8", "e9"]
        assert t.dropped == 6

    def test_annotate_and_nested_instant(self, tracer):
        with obs_trace.span("s") as s:
            s.annotate(rows=7)
            s.event("mark", k=1)
        names = {e["name"]: e for e in tracer.events()}
        assert names["s"]["args"]["rows"] == 7
        assert names["mark"]["ph"] == "i"
        assert names["mark"]["args"] == {"k": 1}


# ---------------------------------------------------------------------------
# stage spans
# ---------------------------------------------------------------------------


def _host_line_names(log_dir):
    """Event names on the profiler's host line of the main thread."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                if "/" not in line.name:  # worker threads carry "/<tid>"
                    names |= {ev.name for ev in line.events}
    return names


class _Refused:
    def __init__(self, *a, **k):
        raise AssertionError("the off path built a span or annotation")


class TestStages:
    def test_off_path_shares_one_object_and_builds_nothing(
            self, monkeypatch):
        old = obs_trace.get_tracer()
        obs_trace.set_tracer(obs_trace.Tracer())
        try:
            assert not obs_trace.profiler_collecting()
            monkeypatch.setattr(obs_trace, "Span", _Refused)
            monkeypatch.setattr(obs_trace, "_Annotation", _Refused)
            import jax.profiler
            monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
            obs_trace.drain_counters()
            a = obs_trace.stage("test.off", "off_s")
            assert type(a) is obs_trace.Stage
            assert obs_trace.stage("test.off", "off_s") is a
            with a:
                # held open: a second entrant gets an object of its own
                b = obs_trace.stage("test.off", "off_s")
                assert b is not a and type(b) is obs_trace.Stage
                with b:
                    pass
            assert obs_trace.stage("test.off", "off_s") is a
            obs_trace.add("off_bytes", 7)
            got = obs_trace.drain_counters()
            assert set(got) == {"off_s", "off_bytes"}
            assert got["off_s"] >= 0 and got["off_bytes"] == 7
            assert obs_trace.get_tracer().events() == []
            assert obs_trace.drain_counters() == {}
        finally:
            obs_trace.set_tracer(old)

    def test_tracer_on_records_an_x_event(self, tracer):
        obs_trace.drain_counters()
        with pytest.raises(KeyError):
            with obs_trace.stage("engine.prepare", "prepare_s"):
                raise KeyError("x")
        (e,) = tracer.events()
        assert e["name"] == "engine.prepare" and e["ph"] == "X"
        assert e["args"]["error"] == "KeyError"
        assert obs_trace.drain_counters()["prepare_s"] >= 0

    def test_only_stages_reach_a_profiler_session(self, tracer, tmp_path):
        import jax
        import jax.numpy as jnp

        obs_trace.drain_counters()
        with jax.profiler.trace(str(tmp_path)):
            assert obs_trace.profiler_collecting()
            with obs_trace.span("layer.span"):
                with obs_trace.stage("engine.prepare", "prepare_s"):
                    x = jnp.arange(8) + 1
                with obs_trace.stage("engine.wait", "wait_s"):
                    x.block_until_ready()
        assert not obs_trace.profiler_collecting()
        names = _host_line_names(tmp_path)
        assert {"engine.prepare", "engine.wait"} <= names
        assert "layer.span" not in names
        assert {"engine.prepare", "engine.wait", "layer.span"} <= {
            e["name"] for e in tracer.events()}
        assert set(obs_trace.drain_counters()) == {"prepare_s", "wait_s"}

    def test_obs_imports_and_stages_without_jax(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = sys.modules['jaxlib'] = None\n"
            "from repro import obs\n"
            "with obs.stage('engine.prepare', 'prepare_s'):\n"
            "    pass\n"
            "assert set(obs.drain_counters()) == {'prepare_s'}\n"
        )
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True)
        assert out.returncode == 0, out.stderr


STAGE_FIELDS = ("prepare_s", "wait_s", "fetch_s", "finish_s")
TENANTS = {
    "pallas": {"structure": "pyramid", "backend": "pallas",
               "build": "device",
               "backend_opts": {"stream": True, "autotune": "off"}},
    "serve": {"structure": "mqr", "backend": "serve"},
}


def _front(kind):
    data = np.asarray(datasets.uniform_squares(240, seed=61), np.float32)
    front = ServingFrontEnd.build(
        {"query_block": 4, "tenants": [dict(TENANTS[kind], name="t")]},
        {"t": data})
    return front, front.tenants["t"], data


def _timed_launch(front, submit):
    """One launch of the front end; returns (stats delta, wall seconds)."""
    rt = front.tenants["t"]
    obs_trace.drain_counters()
    before = rt.stats.to_dict()
    tickets = submit()
    t0 = time.perf_counter()
    assert front.drain() == 1
    wall = time.perf_counter() - t0
    assert all(t.status == "done" for t in tickets)
    return rt.stats.diff(before), wall


class TestLaunchStages:
    @pytest.mark.parametrize("kind", sorted(TENANTS))
    def test_region_launch_counts_its_copies(self, kind):
        front, rt, data = _front(kind)
        warm = datasets.region_queries(data, 4, seed=62)
        queries = datasets.region_queries(data, 4, seed=63)
        for qs in (warm, queries):
            delta, wall = _timed_launch(front, lambda: [
                front.submit("t", "region", q) for q in qs])
        n, levels = rt.index.n_objects, rt.index.schedule.levels
        if kind == "pallas":
            # the schedule and its parent windows stay resident from the
            # warm launch on
            rows = queries.shape[0]
            staged = [queries.astype(np.float32)]
        else:
            # the server's padded query block; its schedule stays resident
            rows = rt.index._backend.server.query_block
            staged = [np.zeros((1, rows, 4), np.float32)]
        # 64-bit host arrays go to the device as 32-bit (x64 off)
        assert delta["h2d_bytes"] == sum(
            a.size * min(a.dtype.itemsize, 4) for a in staged)
        # plus each query's two object-test sums where the pyramid
        # leaves objects sharing their deepest group
        confirm = rows * 2 * 4 if rt.index.schedule.n_shared else 0
        if kind == "pallas":
            # a pyramid's launch returns its hits as id lanes (each query's
            # offset, the overflow flag, the lanes) in place of the mask
            backend = rt.index._backend
            caps = ops.ids_caps(backend._staged, rows, backend.block_w or 128)
            hits = (rows + 1) * 4 + 1 + caps[2] * caps[3] * 4
        else:
            hits = rows * n
        assert delta["d2h_bytes"] == hits + rows * levels * 4 + confirm
        assert all(delta[f] > 0 for f in STAGE_FIELDS)
        assert sum(delta[f] for f in STAGE_FIELDS) <= wall

    @pytest.mark.parametrize("kind", sorted(TENANTS))
    def test_knn_launch_counts_its_rounds(self, kind, monkeypatch):
        from repro.index import knn as knn_mod

        real = knn_mod.knn_expanding
        seen = []

        def counted(region_fn, *args, **kw):
            calls = []

            def fn(qs):
                calls.append(qs.shape[0])
                return region_fn(qs)

            out = real(fn, *args, **kw)
            seen.append((out[3], len(calls)))
            return out

        monkeypatch.setattr(knn_mod, "knn_expanding", counted)
        front, rt, data = _front(kind)
        points = data[:4, :2] + 0.5
        for pts in (points, points + 3.0):
            delta, wall = _timed_launch(front, lambda: [
                front.submit("t", "knn", p, k=5) for p in pts])
        rounds, calls = seen[-1]
        assert rounds == calls >= 2   # radius rounds + the confirming one
        assert delta["knn_rounds"] == rounds
        assert delta["h2d_bytes"] > 0 and delta["d2h_bytes"] > 0
        assert all(delta[f] > 0 for f in STAGE_FIELDS)
        assert sum(delta[f] for f in STAGE_FIELDS) <= wall


# ---------------------------------------------------------------------------
# Perfetto export + instrumented facade
# ---------------------------------------------------------------------------


def _validate_chrome_trace(doc):
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "metadata"}
    for e in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid"} <= set(e), e
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "tid" in e
        elif e["ph"] == "i":
            assert e["s"] == "t" and "tid" in e
        else:
            assert e["ph"] == "C", e


class TestPerfettoExport:
    def test_facade_trace_nests_and_exports(self, tracer, tmp_path):
        idx, queries = _index(autotune="off")
        idx.region(queries)
        idx.knn(queries[:, :2][:4], 3)
        path = tmp_path / "trace.json"
        tracer.export_chrome_trace(path)
        doc = json.loads(path.read_text())
        _validate_chrome_trace(doc)
        assert doc["metadata"]["dropped_events"] == 0
        by_name = {}
        for e in doc["traceEvents"]:
            by_name.setdefault(e["name"], []).append(e)
        assert "index.region" in by_name and "backend.pallas" in by_name
        assert "index.knn" in by_name
        region = by_name["index.region"][0]
        backend = by_name["backend.pallas"][0]
        # facade span contains the backend span on the same thread
        assert region["tid"] == backend["tid"]
        assert region["ts"] <= backend["ts"]
        assert (backend["ts"] + backend["dur"]
                <= region["ts"] + region["dur"] + 1e-6)
        assert region["args"]["backend"] == "pallas"

    def test_degradation_rungs_appear_as_span_errors_and_instants(
            self, tracer):
        data = datasets.uniform_squares(200, seed=31)
        queries = datasets.region_queries(data, 8, seed=32)
        plan = FaultPlan(fail_launches=10**9, fail_rungs=("pallas",))
        idx = SpatialIndex.build(
            data, backend="serve", fault_plan=plan,
            query_block=4, cache_size=0, backoff=0.0,
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            idx.region(queries)
        ev = tracer.events()
        failed = [e for e in ev if e["name"] == "serve.rung"
                  and e["args"].get("error") == "InjectedFailure"]
        assert failed and all(e["ph"] == "X" for e in failed)
        assert all(e["args"]["rung"] == "pallas" for e in failed)
        degrades = [e for e in ev if e["name"] == "serve.degrade"]
        assert degrades and degrades[0]["ph"] == "i"
        assert degrades[0]["args"]["from"] == "pallas"
        assert degrades[0]["args"]["to"] == "lax"
        # the lax rung then answered: a clean serve.rung span exists
        ok = [e for e in ev if e["name"] == "serve.rung"
              and "error" not in e["args"] and e["args"]["rung"] == "lax"]
        assert ok


# ---------------------------------------------------------------------------
# the counter ledger: production == bench, bit for bit
# ---------------------------------------------------------------------------


class TestLaunchLedger:
    def test_region_report_matches_bench_disclosure_bit_for_bit(
            self, ledger):
        idx, queries = _index(autotune="off", stream=True,
                              precision="compact")
        res = idx.region(queries)
        rep = res.launch_report
        assert rep is not None and rep.kind == "compact" and rep.stream
        assert rep.backend == "pallas"

        # the bench's computation, reproduced independently from the
        # SAME artifacts (benchmarks/jax_bench.py::bench_stream_scan)
        q16 = idx.artifacts.quantized
        sched = idx.artifacts.schedule
        g16 = np.asarray(q16.mbr_q, np.int64)
        p16 = np.asarray(q16.parent_q, np.int64)
        qq = obs_counters.quantize_queries_grid(
            queries, q16.origin, q16.inv_cell, q16.cells)
        win_off, win_w = ops.parent_windows(
            p16, np.asarray(sched.n_real, np.int64), block_w=128)
        tile_b, mask_b, fetched, n_tiles, surv = \
            obs_counters.stream_fetch_bytes(
                g16, p16, qq, win_off, win_w, block_w=128,
                root_unconditional=sched.root_unconditional,
            )
        assert rep.bytes_streamed == tile_b          # bit for bit
        assert rep.mask_bytes == mask_b
        assert rep.tiles_fetched == fetched
        assert rep.tiles_total == n_tiles
        assert rep.survivors_per_level == surv
        assert rep.queries == queries.shape[0]
        # the survivors ledger IS the kernel's own visit accounting
        assert surv == tuple(int(x) for x in
                             np.asarray(res.visits_per_level).sum(axis=0))

    def test_reports_fold_into_access_stats(self, ledger):
        idx, queries = _index(autotune="off", stream=True,
                              precision="compact")
        per_call = idx.region(queries).launch_report
        idx.region(queries)
        s = idx.stats
        assert s.launch_reports == 2
        assert s.bytes_streamed == 2 * per_call.bytes_streamed
        assert s.mask_bytes == 2 * per_call.mask_bytes
        assert s.tiles_fetched == 2 * per_call.tiles_fetched
        assert s.tiles_skipped == 2 * per_call.tiles_skipped

    def test_no_collection_no_report(self):
        obs_counters.collect_launch_reports(False)
        idx, queries = _index(autotune="off", stream=True,
                              precision="compact")
        res = idx.region(queries)
        assert res.launch_report is None
        assert idx.stats.launch_reports == 0

    def test_merge_reports_sums_and_adds_survivors(self):
        a = obs_counters.LaunchReport("compact", True, 4, 128, 100.0,
                                      mask_bytes=10.0, tiles_fetched=3,
                                      tiles_total=8,
                                      survivors_per_level=(1, 2))
        b = obs_counters.LaunchReport("compact", True, 4, 128, 50.0,
                                      mask_bytes=5.0, tiles_fetched=2,
                                      tiles_total=8,
                                      survivors_per_level=(3, 4))
        m = obs_counters.merge_reports([a, b])
        assert m.queries == 8 and m.launches == 2
        assert m.bytes_streamed == 150.0 and m.mask_bytes == 15.0
        assert m.tiles_fetched == 5 and m.tiles_total == 16
        assert m.tiles_skipped == 11
        assert m.survivors_per_level == (4, 6)
        assert obs_counters.merge_reports([]) is None
        d = m.to_dict()
        assert d["tiles_skipped"] == 11
        assert d["survivors_per_level"] == [4, 6]


# ---------------------------------------------------------------------------
# AccessStats snapshots / deltas
# ---------------------------------------------------------------------------


class TestAccessStatsDict:
    def test_to_dict_and_diff(self):
        idx, queries = _index(autotune="off")
        idx.region(queries)
        before = idx.stats.to_dict()
        assert before["queries"] == queries.shape[0]
        assert isinstance(before["rung_dispatches"], dict)
        idx.region(queries)
        delta = idx.stats.diff(before)
        assert delta["queries"] == queries.shape[0]
        assert delta["node_accesses"] > 0
        # diff accepts the live object too
        assert idx.stats.diff(idx.stats)["queries"] == 0


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------


class TestLatencyHistogram:
    def test_quantile_clamps_out_of_range_q(self):
        h = LatencyHistogram()
        assert h.quantile(0.5) == 0.0  # empty
        for v in (0.001, 0.002, 0.004, 0.008):
            h.record(v)
        assert h.quantile(-1.0) == h.quantile(0.0)
        assert h.quantile(1.0) == h.max
        assert h.quantile(2.0) == h.max

    def test_merge_and_to_dict_roundtrip_counts(self):
        a, b = LatencyHistogram(), LatencyHistogram()
        for v in (0.001, 0.004):
            a.record(v)
        for v in (0.002, 0.064):
            b.record(v)
        out = a.merge(b)
        assert out is a
        assert a.n == 4
        assert a.max == pytest.approx(0.064)
        assert a.total == pytest.approx(0.071)
        d = a.to_dict()
        assert d["n"] == 4
        assert sum(d["counts"].values()) == 4

    def test_merge_rejects_mismatched_buckets(self):
        a = LatencyHistogram()
        b = LatencyHistogram(lo=1e-3)
        with pytest.raises(ValueError, match="merge"):
            a.merge(b)


# ---------------------------------------------------------------------------
# metrics registry + exposition
# ---------------------------------------------------------------------------

_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9].*$")


def _check_prometheus(text):
    seen_type = set()
    for line in text.strip().splitlines():
        if line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _, _, fam, mtype = line.split(maxsplit=3)
            assert mtype in ("counter", "gauge", "summary"), line
            seen_type.add(fam)
            continue
        assert _PROM_SAMPLE.match(line), f"malformed sample: {line!r}"
        fam = re.split(r"[{ ]", line)[0]
        base = re.sub(r"_(sum|count)$", "", fam)
        assert fam in seen_type or base in seen_type, \
            f"sample before TYPE: {line!r}"


class TestMetrics:
    def test_registry_families_and_escaping(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("ops total", 3, labels={"tenant": 'a"b\\c'},
                    help="ops")
        reg.gauge("depth", 2.5)
        text = reg.to_prometheus()
        _check_prometheus(text)
        assert 'repro_ops_total{tenant="a\\"b\\\\c"} 3' in text
        assert "repro_depth 2.5" in text
        with pytest.raises(ValueError, match="registered as"):
            reg.gauge("ops total", 1)

    def test_index_metrics_snapshot(self):
        idx, queries = _index(autotune="off")
        idx.region(queries)
        reg = idx.metrics(tenant="t0")
        text = reg.to_prometheus()
        _check_prometheus(text)
        assert 'repro_index_queries{tenant="t0"} 8' in text
        assert 'repro_index_launches{tenant="t0"} 1' in text
        doc = reg.to_json()
        names = {m["name"] for m in doc["metrics"]}
        assert "repro_index_queries" in names

    def test_index_metrics_export_the_stage_counters(self):
        idx, queries = _index(autotune="off")
        idx.region(queries)
        text = idx.metrics(tenant="t0").to_prometheus()
        _check_prometheus(text)
        for field in STAGE_FIELDS + ("h2d_bytes", "d2h_bytes"):
            assert f"# TYPE repro_index_{field} counter" in text
            m = re.search(rf'^repro_index_{field}{{tenant="t0"}} (\S+)$',
                          text, re.M)
            # a bare pallas index has no host work after its fetch; the
            # front end's hand-out is what feeds finish_s
            assert m and (float(m.group(1)) > 0) == (field != "finish_s")

    def test_front_end_metrics_with_per_tenant_quantiles(self):
        data = np.asarray(
            datasets.uniform_squares(160, seed=51), np.float32)
        cfg = ServerConfig.from_dict({
            "tenants": [{"name": "a", "backend": "host"},
                        {"name": "b", "backend": "host"}],
            "classes": [{"name": "interactive", "deadline_ms": 50.0,
                         "overload": "shed", "max_queue": 64}],
            "query_block": 4,
        })
        front = ServingFrontEnd.build(cfg, {"a": data, "b": data})
        rect = np.array([0.0, 0.0, 50.0, 50.0], np.float32)
        for tenant in ("a", "b"):
            for _ in range(4):
                front.submit(tenant, "region", rect)
        front.drain()
        text = front.metrics().to_prometheus()
        _check_prometheus(text)
        assert "repro_serve_submitted 8" in text
        assert "repro_serve_completed 8" in text
        for tenant in ("a", "b"):
            for q in ("0.5", "0.99", "0.999"):
                assert (f'repro_serve_tenant_latency_seconds{{'
                        f'quantile="{q}",tenant="{tenant}"}}') in text
            assert (f'repro_index_queries{{tenant="{tenant}"}} 4'
                    in text)
        assert 'slo_class="interactive"' in text
