"""One run of one benchmark cell, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix;
the harness finds everything else by those names under the benchmark's
own directory:

* ``configs/<config>.json``: the deployment (data, tenant stack, query
  block, SLO class, comparison limits); its ``dataset`` names the data's
  maker in ``datasets/``;
* ``traffic/<traffic>.json``: the mix; its ``generator`` names the
  request maker in ``generators/`` (see :mod:`loadgen`);
* ``metrics/<metric>.py``: one reader per metric, ``read(run)`` returning
  a number or None (nothing to read in this run).

A run builds the front end from the seed's data, warms every batch shape
the window will use, drives ``ServingFrontEnd.submit``/``pump`` for
``seconds``, serves what is still queued, then checks every answer
against the plain reference and prints the result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import json
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

from . import loadgen, reference, tracereduce

SLO = "batch"
TRACE_DIR = ".bench_trace"
# Safety stop when serving what is queued after the window.
DRAIN_LIMIT_S = 120.0
# Lowering of a new program: the event JAX reports once per compilation
# that the process had not done before.
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Cells, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: dict            # "end_to_end" / "per_layer" -> [metric entry]
    bench_dir: str


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(root: str, workload: str, overrides: Optional[dict] = None
              ) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = os.path.join(root, bench["paths"][0])
    overrides = overrides or {}
    config = _merge(_load_json(os.path.join(root, configs[w["config"]]["file"])),
                    overrides.get("config"))
    traffic = _merge(_load_json(os.path.join(
        bench_dir, "traffic", f"{w['traffic']}.json")), overrides.get("traffic"))

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    metrics = {kind: [m for m in bench[kind] if applies(m)]
               for kind in ("end_to_end", "per_layer")}
    return Cell(workload, int(w["chips"]), config, traffic, metrics,
                bench_dir)


def metric_reader(bench_dir: str, name: str):
    return loadgen.module("metrics", name, bench_dir).read


# ---------------------------------------------------------------------------
# What a run records, for the metric readers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Ticket:
    kind: str
    t_arrival: float
    t_launch: Optional[float]
    t_complete: Optional[float]
    done: bool


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    loop: str                     # "open" | "closed"
    setup_s: float
    window_start: float
    window_end: float             # open: start + seconds; closed: last pump
    tickets: List[Ticket]         # every request sent in the window
    stats: dict                   # the engine's counters over the window
    trace: Optional[tracereduce.TraceSummary] = None

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def latencies_s(self) -> np.ndarray:
        """Arrival (as scheduled) to completion.  Open loop: every
        request sent in the window, also those answered after it.
        Closed loop: the requests completed in the window."""
        ts = [t for t in self.tickets if t.done and (
            self.loop == "open" or t.t_complete <= self.window_end)]
        return np.array([t.t_complete - t.t_arrival for t in ts])

    def launches(self):
        """``(t_launch, t_done, size)`` of every launch that served a
        request of the window."""
        by = {}
        for t in self.tickets:
            if t.done:
                d = by.setdefault(t.t_launch, [t.t_launch, t.t_complete, 0])
                d[1] = max(d[1], t.t_complete)
                d[2] += 1
        return sorted(tuple(v) for v in by.values())


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts the programs JAX lowers from the moment it is made."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1


class GcPauses:
    """Records how long each garbage collection of the process takes."""

    def __init__(self):
        self.started = None
        self.pauses = []

    def __call__(self, phase, info):
        if phase == "start":
            self.started = time.perf_counter()
        elif self.started is not None:
            self.pauses.append(time.perf_counter() - self.started)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def enable_compile_cache(root: str) -> str:
    """The persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one (JAX reads it)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def _span_factory(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def _groups(sent: set, traffic: dict, query_block: int):
    """Batch sizes the window can launch, per coalescing group, each as
    the kind used to warm it; ``sent`` holds the kinds the window sends."""
    kinds = []
    if sent & {"region", "point", "count"}:
        kinds.append("region")
    if "knn" in sent:
        kinds.append("knn")
    if (traffic["loop"] == "closed" and len(kinds) == 1
            and traffic["clients"] % query_block == 0):
        sizes = [query_block]   # one group, every launch a full block
    else:
        sizes = list(range(1, query_block + 1))
    return [(kind, b) for kind in kinds for b in sizes]


def _serve_now(front, kind_sizes, maker, rng, tenant, k):
    tickets = []
    for kind, b in kind_sizes:
        reqs = maker.make(b, rng, kinds=[kind])
        tickets += [front.submit(tenant, kd, p, k=k if kd == "knn" else None,
                                 slo=SLO) for kd, p in reqs]
        front.drain()
    if not all(t.status == "done" for t in tickets):
        raise RuntimeError("a warm-up request was not answered")


def _open_loop(front, tenant, reqs, offsets, seconds, k, span):
    clock = front.clock
    tickets, late = [], []
    with span("bench.window"):
        t0 = clock()
        for (kind, payload), off in zip(reqs, offsets):
            target = t0 + float(off)
            while True:
                now = clock()
                if now >= target:
                    break
                with span("bench.pump"):
                    launched = front.pump()
                if not launched:
                    with span("bench.wait"):
                        time.sleep(min(target - now, 1e-3))
            late.append(clock() - target)
            with span("bench.submit"):
                tickets.append(front.submit(
                    tenant, kind, payload, k=k if kind == "knn" else None,
                    slo=SLO, t_arrival=target))
            with span("bench.pump"):
                front.pump()
        end = t0 + seconds
        _serve_until(front, tickets, end, span)
    return tickets, t0, end, late


def _serve_until(front, tickets, end, span):
    """Pump as the serving loop would, through the window's end and then
    until every request has its answer."""
    clock = front.clock
    stop = max(end, clock()) + DRAIN_LIMIT_S
    while clock() < end or any(t.status == "pending" for t in tickets):
        if clock() > stop:
            with span("bench.drain"):
                front.drain()
            break
        with span("bench.pump"):
            launched = front.pump()
        if not launched:
            with span("bench.wait"):
                time.sleep(1e-3)


def _closed_loop(front, tenant, reqs, clients, seconds, k, span):
    clock = front.clock
    taken = itertools.count()

    def submit():
        kind, payload = reqs[next(taken) % len(reqs)]
        with span("bench.submit"):
            return front.submit(tenant, kind, payload,
                                k=k if kind == "knn" else None, slo=SLO)

    with span("bench.window"):
        t0 = clock()
        live = [submit() for _ in range(clients)]
        tickets = list(live)
        while True:
            with span("bench.pump"):
                launched = front.pump()
            if not launched:
                with span("bench.wait"):
                    time.sleep(1e-4)
            done = sum(t.status != "pending" for t in live)
            live = [t for t in live if t.status == "pending"]
            if clock() - t0 >= seconds:
                break
            for _ in range(done):
                t = submit()
                live.append(t)
                tickets.append(t)
        end = clock()
    with span("bench.drain"):
        front.drain()
    return tickets, t0, end, []


def _peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclasses.dataclass
class Inputs:
    """What a run sends, made from the seed alone (no program, no JAX)."""

    cell: Cell
    data: np.ndarray
    maker: object                 # the traffic's generator
    rng_req: np.random.Generator
    rng_warm: np.random.Generator

    @property
    def tenant(self) -> str:
        return self.cell.config["name"]

    @property
    def k(self):
        return self.cell.config.get("knn_k")

    def window_requests(self, seconds: float, rate: Optional[float] = None):
        """``(requests, arrival offsets)``; offsets are None in a closed
        loop, where the pool is taken in turn."""
        traffic = self.cell.traffic
        if traffic["loop"] == "open":
            rate = float(traffic["rate_per_s"]) if rate is None else rate
            offsets = loadgen.arrivals(traffic, rate, seconds, self.rng_req,
                                       self.cell.bench_dir)
            return self.maker.make(offsets.shape[0], self.rng_req), offsets
        return self.maker.make(int(traffic["pool"]), self.rng_req), None


def make_inputs(cell: Cell, seed: int) -> Inputs:
    config = cell.config
    rng_data, rng_req, rng_warm = loadgen.seeded(seed)
    data = loadgen.make_data(config, rng_data, cell.bench_dir)
    maker = loadgen.request_maker(cell.traffic, config, data, cell.bench_dir)
    return Inputs(cell, data, maker, rng_req, rng_warm)


def build_front(inp: Inputs):
    """The system under test: one tenant behind ``ServingFrontEnd``."""
    from repro.serve import ServingFrontEnd

    config = inp.cell.config
    tenant = dict(config["tenant"], name=inp.tenant)
    return ServingFrontEnd.build(
        {"query_block": int(config["query_block"]), "tenants": [tenant]},
        data={inp.tenant: inp.data})


def warm(front, inp: Inputs, reqs) -> int:
    """Launch every batch shape the window's requests ``reqs`` can use;
    returns their count."""
    shapes = _groups({kind for kind, _ in reqs}, inp.cell.traffic,
                     int(inp.cell.config["query_block"]))
    _serve_now(front, shapes, inp.maker, inp.rng_warm, inp.tenant, inp.k)
    return len(shapes)


def drive(front, inp: Inputs, reqs, offsets, seconds: float, span):
    """The measured window; returns ``(tickets, start, end, lateness)``."""
    traffic = inp.cell.traffic
    if traffic["loop"] == "open":
        return _open_loop(front, inp.tenant, reqs, offsets, seconds, inp.k,
                          span)
    return _closed_loop(front, inp.tenant, reqs, int(traffic["clients"]),
                        seconds, inp.k, span)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: Optional[float] = None,
             require_chip: bool = True, cache: bool = True,
             overrides: Optional[dict] = None,
             trace_dir: Optional[str] = None) -> dict:
    """One run; returns the result line's object.  Raises :class:`NoChip`
    when ``require_chip`` and JAX finds no TPU or too few chips."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload, overrides)
    config = cell.config
    import jax

    if cache:
        log(f"compile cache {enable_compile_cache(root)}")
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform!r} device(s)")
    compiles = CompileCounter()
    inp = make_inputs(cell, seed)
    t0 = time.perf_counter()
    front = build_front(inp)
    rt = front.tenants[inp.tenant]
    build_s = time.perf_counter() - t0
    reqs, offsets = inp.window_requests(seconds)
    t0 = time.perf_counter()
    n_shapes = warm(front, inp, reqs)
    warm_s = time.perf_counter() - t0
    warm_compiles = compiles.count

    span = _span_factory(trace)
    tdir = None
    if trace:
        # In the checkout: a whole window's trace is tens of MB, more
        # than a small temporary file system holds.
        os.makedirs(os.path.join(root, TRACE_DIR), exist_ok=True)
        tdir = trace_dir or tempfile.mkdtemp(dir=os.path.join(root, TRACE_DIR))
        opts = jax.profiler.ProfileOptions()
        # Level 1 keeps the benchmark's annotations and JAX's own calls
        # on the Python thread; level 2 adds every runtime task of every
        # thread, and over a whole window the host tracer then drops
        # events, the window's own annotation among them.
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    stats0 = rt.stats.to_dict()
    setup_s = time.perf_counter() - t_start
    compiles_before = compiles.count
    try:
        with GcPauses() as gc_pauses:
            tickets, w0, w1, late = drive(front, inp, reqs, offsets, seconds,
                                          span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = compiles.count - compiles_before
    peak = _peak_bytes(devices[:cell.chips])
    stats = rt.stats.diff(stats0)
    run = Run(
        loop=cell.traffic["loop"], setup_s=setup_s,
        window_start=w0, window_end=w1,
        tickets=[Ticket(t.kind, t.t_arrival, t.t_launch, t.t_complete,
                        t.status == "done") for t in tickets],
        stats=stats,
    )
    launches = run.launches()
    sizes = [s for _, _, s in launches]
    log(f"setup: data n={inp.data.shape[0]} build_s={build_s} "
        f"warm_s={warm_s} warm_shapes={n_shapes} "
        f"warm_compiles={warm_compiles} setup_s={setup_s}")
    log(f"window: loop={run.loop} seconds={run.window_s} sent={len(tickets)} "
        f"completed={sum(t.done for t in run.tickets)} "
        f"launches={len(launches)} "
        f"mean_batch={np.mean(sizes) if sizes else 0} "
        f"deadline_launches={front.telemetry.deadline_launches} "
        f"compiles_in_window={window_compiles} "
        f"gc_pauses={len(gc_pauses.pauses)} "
        f"gc_total_ms={sum(gc_pauses.pauses) * 1e3} "
        f"gc_max_ms={max(gc_pauses.pauses, default=0.0) * 1e3}")
    if late:
        log(f"generator lateness: mean_ms={np.mean(late) * 1e3} "
            f"p95_ms={np.percentile(late, 95) * 1e3} "
            f"max_ms={np.max(late) * 1e3}")
    log(f"device: peak_bytes_in_use={peak} visits={stats['node_accesses']} "
        f"launches={stats['launches']} knn_rounds={stats['knn_rounds']} "
        f"rungs={stats['rung_dispatches']} "
        f"degraded={stats['degraded_batches']}")

    breakdown = None
    if trace:
        xplane = tracereduce.find_xplane(tdir)
        trace_bytes = os.path.getsize(xplane)
        run.trace = tracereduce.summarize(xplane)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
            with contextlib.suppress(OSError):  # another run may use it
                os.rmdir(os.path.join(root, TRACE_DIR))
        breakdown = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in run.trace.gaps[:10]],
        }
        log(f"trace: window_s={run.trace.window_s} "
            f"busy_s={run.trace.busy_s} devices={run.trace.n_devices} "
            f"xplane_bytes={trace_bytes}")

    # The program's state goes before the reference runs.
    answers = [t.result if t.status == "done" else None for t in tickets]
    sent = [(t.kind, t.payload[:2] if t.kind == "point" else t.payload)
            for t in tickets]
    failed = sum(a is None for a in answers)
    del front, rt, tickets
    checks = reference.compare(sent, answers, reference.Reference(inp.data),
                               inp.k, config["limits"])
    del answers
    correct = all(v <= lim for _, v, lim in checks)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        value = metric_reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
    for name, value, limit in checks:
        print(f"check {name} {value} limit {limit}", file=sys.stderr,
              flush=True)
    result = {"correct": correct, "attempted": len(sent), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result
