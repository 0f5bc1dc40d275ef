"""Smoke run of the served spatial-search path on one TPU chip.

Drives the system through the entry points a user calls — a
``ServingFrontEnd`` fed by ``submit``/``pump``/``result`` — and checks
every answer against references written here, independent of the code
under test:

* map-search tenants (``structure="pyramid"``, ``build="device"``,
  ``backend="pallas"`` with the HBM-streamed sweep) over ``--n`` uniform
  squares (coverage ~1, the paper's setting) in float32 and
  ``precision="compact"``, plus a uniform-points twin (the zero-overlap
  case).  Every region/point/count answer must equal a brute-force numpy
  overlap over all n objects; per-level visits must equal the numpy twin
  of ``kernels/fallback.py`` on a few queries;
* the paper tenant (``structure="mqr"``, ``backend="serve"``, the
  VMEM-resident sweep) at ``min(--n, PAPER_N)`` objects: region and kNN
  requests, a join against a second tree, then inserts and deletes
  through the delta buffer and the same again.  Answers must equal the
  host pointer oracle, brute force and the nested-loop join oracle.

The run fails (non-zero exit, no result line) when JAX finds no TPU,
when any phase raises, when any answer differs, when a serving or join
ladder rung other than ``pallas`` answered, or when the autotuner refused
a tile candidate.  The timings printed on the way are smoke timings of
one cold run, not benchmark numbers.

``--chips 4`` runs only the multi-chip path: a ``SpatialServer`` that
``pmap``s query blocks over all four chips, against the same server on
one chip, both checked against brute force.

``--cpu-rehearsal`` lets the run proceed on the CPU (Pallas in interpret
mode) to rehearse the control flow at a tiny ``--n``; it relaxes the
platform check and nothing else.

    python chip_smoke.py                      # one chip, n = 10,000,000
    python chip_smoke.py --chips 4            # four chips, pmap path only
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --n 3000

The last line of standard output is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# The paper tenant's host mqr build is per-object Python (~1.3 ms per
# object on a current x86 core); this keeps it under a minute.
PAPER_N = 30_000
QUERY_BLOCK = 16
KNN_K = 8
EXTENT = 1000.0


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def f32(a) -> np.ndarray:
    """Snap to float32-representable coordinates, so the float64 inputs
    and the float32 device path agree at every box boundary."""
    return np.float64(np.float32(a))


def squares(n: int, seed: int) -> np.ndarray:
    side = EXTENT * np.sqrt(1.0 / n)  # coverage ~1: n squares of area 1e6/n
    ll = np.random.default_rng(seed).uniform(0.0, EXTENT - side, (n, 2))
    return f32(np.concatenate([ll, ll + side], axis=1))


def points(n: int, seed: int) -> np.ndarray:
    p = np.random.default_rng(seed).uniform(0.0, EXTENT, (n, 2))
    return f32(np.concatenate([p, p], axis=1))


def brute_hits(table: np.ndarray, alive: np.ndarray, q) -> np.ndarray:
    """Closed-boundary overlap of one query with every object of a
    float32 (n, 4) table."""
    q = np.asarray(q, np.float32)
    return ((table[:, 0] <= q[2]) & (q[0] <= table[:, 2])
            & (table[:, 1] <= q[3]) & (q[1] <= table[:, 3]) & alive)


def brute_pairs(ta, aa, tb, ab) -> np.ndarray:
    """Nested-loop join: every live pair whose boxes overlap."""
    a = ta.astype(np.float32)[:, None, :]
    b = tb.astype(np.float32)[None, :, :]
    ov = ((a[..., 0] <= b[..., 2]) & (b[..., 0] <= a[..., 2])
          & (a[..., 1] <= b[..., 3]) & (b[..., 1] <= a[..., 3]))
    return ov & aa[:, None] & ab[None, :]


def rect_requests(data: np.ndarray, count: int, seed: int):
    """(kind, payload) for ``count`` region, point and count requests:
    region rects holding ~4 objects, points at object centres."""
    rng = np.random.default_rng(seed)
    n = data.shape[0]
    side = EXTENT * np.sqrt(4.0 / n)
    out = []
    for i in range(count):
        c = data[rng.integers(0, n)]
        cx, cy = (c[0] + c[2]) / 2, (c[1] + c[3]) / 2
        kind = ("region", "point", "count")[i % 3]
        if kind == "point":
            out.append((kind, np.array([cx, cy], np.float32)))
        else:
            out.append((kind, np.array(
                [cx - side / 2, cy - side / 2, cx + side / 2, cy + side / 2],
                np.float32)))
    return out


def as_rect(kind: str, payload) -> np.ndarray:
    p = np.asarray(payload, np.float32)
    return np.concatenate([p, p]) if kind == "point" else p


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "n/a"
    return str(stats["peak_bytes_in_use"])


def serve_requests(front, tenant: str, reqs, *, k=None):
    """Submit and answer the first query block, then the rest; returns
    (tickets, seconds of the first block — compile and autotuning
    included —, seconds of the rest or None)."""
    def wave(part):
        tickets = [front.submit(tenant, kind, payload, k=k, slo="batch")
                   for kind, payload in part]
        t0 = time.perf_counter()
        for t in tickets:
            front.result(t)
        return tickets, time.perf_counter() - t0

    first, first_s = wave(reqs[:QUERY_BLOCK])
    rest, rest_s = wave(reqs[QUERY_BLOCK:]) if reqs[QUERY_BLOCK:] else ([], None)
    tickets = first + rest
    check(all(t.status == "done" for t in tickets),
          f"{tenant}: not every request completed")
    return tickets, first_s, rest_s


def secs(s) -> str:
    return "n/a" if s is None else f"{s:.2f}"


def check_rect_answers(tenant, tickets, reqs, table, alive) -> int:
    found = 0
    for t, (kind, payload) in zip(tickets, reqs):
        want = brute_hits(table, alive, as_rect(kind, payload))
        if kind == "count":
            check(t.result == int(want.sum()),
                  f"{tenant}: count {t.result} != brute force {want.sum()}")
            found += t.result
        else:
            # Ids past the table are unused id space (delta capacity).
            got = t.result.hits
            check(np.array_equal(got[:want.shape[0]], want)
                  and not got[want.shape[0]:].any(),
                  f"{tenant}: {kind} hits differ from brute force")
            found += int(got.sum())
    return found


def check_ladder(tenant: str, stats) -> dict:
    rungs = dict(stats.rung_dispatches)
    check(not stats.degraded and stats.launch_failures == 0
          and set(rungs) <= {"pallas"},
          f"{tenant}: a fallback answered or a launch failed "
          f"(rungs={rungs}, failures={stats.launch_failures})")
    return rungs


def check_refusals(tenant: str, artifacts) -> None:
    for (key, cfg), err in artifacts.tune_refusals.items():
        log(f"{tenant}: autotune refused {cfg} at {key}: {err}")
    check(not artifacts.tune_refusals,
          f"{tenant}: the autotuner refused {len(artifacts.tune_refusals)} "
          f"candidate(s)")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def map_search_phase(n: int, dev) -> None:
    from repro.core import bulk
    from repro.kernels import fallback
    from repro.serve import ServingFrontEnd

    maps = squares(n, seed=1)
    datasets = {"maps": maps, "maps-compact": maps, "pois": points(n, seed=2)}
    # Four levels past ``bulk.default_levels`` split uniform data down to
    # one object a group, so the sweep prunes a viewport to its own
    # objects; answers are exact at any depth (DESIGN.md §3.1).
    tenant = {"structure": "pyramid", "build": "device", "backend": "pallas",
              "levels": bulk.default_levels(n) + 4,
              "backend_opts": {"stream": True}}
    cfg = {
        "query_block": QUERY_BLOCK,
        "tenants": [
            dict(tenant, name="maps"),
            dict(tenant, name="maps-compact", precision="compact"),
            dict(tenant, name="pois"),
        ],
    }
    t0 = time.perf_counter()
    front = ServingFrontEnd.build(cfg, data=datasets)
    log(f"map-search: built 3 tenants of n={n} in "
        f"{time.perf_counter() - t0:.1f}s (device build)")

    alive = np.ones((n,), bool)
    for seed, (name, count) in enumerate(
            (("maps", 32), ("maps-compact", 16), ("pois", 16))):
        data = datasets[name]
        rt = front.tenants[name]
        idx = rt.spatial
        sched = idx.artifacts.schedule
        compact = rt.config.precision == "compact"
        tiles = (idx.artifacts.quantized.mbr_q if compact else sched.mbr_cm)
        reqs = rect_requests(data, count, seed=10 + seed)
        tickets, first_s, rest_s = serve_requests(front, name, reqs)
        found = check_rect_answers(name, tickets, reqs,
                                   data.astype(np.float32), alive)

        # Per-level visits against the numpy twin on a few queries.
        probe = [(t, kind, p) for t, (kind, p) in zip(tickets, reqs)
                 if kind != "count"][:2]
        qs = np.stack([as_rect(kind, p) for _, kind, p in probe])
        common = dict(obj_level=sched.obj_level, obj_slot=sched.obj_slot,
                      obj_id=sched.obj_id, n_objects=sched.n_objects,
                      root_unconditional=sched.root_unconditional,
                      stream=True)
        if compact:
            q = idx.artifacts.quantized
            _, visits = fallback.fused_search_compact_np(
                qs, q.mbr_q, q.parent_q, q.confirm_mbr, origin=q.origin,
                inv_cell=q.inv_cell, cells=q.cells, **common)
        else:
            _, visits, _ = fallback.fused_search_np(
                qs, sched.mbr_cm, sched.parent, sched.obj_mbr,
                test_object_mbr=sched.test_object_mbr,
                n_shared=sched.n_shared, **common)
        got = np.stack([t.result.visits for t, _, _ in probe])
        check(np.array_equal(got, visits),
              f"{name}: per-level visits differ from the numpy twin")

        check_refusals(name, idx.artifacts)
        rungs = check_ladder(name, rt.stats)
        tuned = sorted(str(c) for c in idx.artifacts.tuned.values())
        log(f"{name}: n={n} levels={sched.levels} W={sched.width} "
            f"tile_bytes={tiles.nbytes} precision="
            f"{'compact' if compact else 'float32'} stream=True "
            f"requests={count} objects_found={found} "
            f"first_block_s={secs(first_s)} (compile+autotune) "
            f"next_block_s={secs(rest_s)} peak_bytes_in_use="
            f"{peak_bytes(dev)} rungs={rungs or 'none (no ladder)'} "
            f"launches={rt.stats.launches} tuned={tuned}")
        log(f"{name}: {count} answers == brute force over {n} objects; "
            f"visits == numpy twin on {len(probe)} queries")


def paper_phase(n: int, dev) -> None:
    from repro.index import SpatialIndex
    from repro.serve import ServingFrontEnd

    data = squares(n, seed=3)
    t0 = time.perf_counter()
    front = ServingFrontEnd.build(
        {"query_block": QUERY_BLOCK,
         "tenants": [{"name": "paper", "structure": "mqr"}]},
        data={"paper": data},
    )
    build_s = time.perf_counter() - t0
    rt = front.tenants["paper"]
    idx = rt.spatial
    sched = idx.artifacts.schedule
    other = SpatialIndex.build(squares(max(n // 16, 1), seed=4),
                               structure="mqr", backend="host")
    table = data.copy()
    alive = np.ones((n,), bool)
    rng = np.random.default_rng(5)

    def round_(tag: str, seed: int):
        host = idx.with_backend("host")  # the pointer oracle, same live state
        reqs = rect_requests(table[alive], 16, seed=seed)
        tickets, first_s, _ = serve_requests(front, "paper", reqs)
        check_rect_answers("paper", tickets, reqs, table.astype(np.float32),
                           alive)
        rects = np.stack([as_rect(k, p) for k, p in reqs])
        ref = host.region(rects)
        for i, (t, (kind, _)) in enumerate(zip(tickets, reqs)):
            if kind != "count":
                check(np.array_equal(t.result.visits, ref.visits_per_level[i]),
                      "paper: per-level visits differ from the host oracle")

        pts = rng.uniform(0.0, EXTENT, (16, 2)).astype(np.float32)
        knn = [("knn", p) for p in pts]
        ktickets, kfirst_s, _ = serve_requests(front, "paper", knn, k=KNN_K)
        kref = host.knn(pts, KNN_K)
        for i, t in enumerate(ktickets):
            ids, dists = t.result
            check(np.array_equal(ids, kref.ids[i])
                  and np.allclose(dists, kref.dists[i], atol=1e-4),
                  "paper: kNN differs from the host oracle")

        t1 = time.perf_counter()
        res = idx.join(other)
        join_s = time.perf_counter() - t1
        want = brute_pairs(table, alive, other.artifacts.mbrs,
                           np.ones((other.n_objects,), bool))
        got = res.pairs[: table.shape[0]]
        check(got.shape == want.shape and np.array_equal(got, want)
              and not res.pairs[table.shape[0]:].any(),
              "paper: join pairs differ from the nested-loop oracle")
        rungs = check_ladder("paper", rt.stats)
        log(f"paper[{tag}]: n_live={int(alive.sum())} levels={sched.levels} "
            f"W={sched.width} tile_bytes={sched.mbr_cm.nbytes} "
            f"region_block_s={secs(first_s)} knn_block_s={secs(kfirst_s)} "
            f"join_s={join_s:.2f} join_pairs={res.n_pairs} "
            f"peak_bytes_in_use={peak_bytes(dev)} rungs={rungs}")

    log(f"paper: host mqr build of n={n} in {build_s:.1f}s")
    round_("pristine", seed=6)

    new = squares(64, seed=7)
    gids = np.asarray(front.insert("paper", new))
    check(gids.shape == (64,), "paper: insert did not return 64 ids")
    table = np.concatenate([table, np.zeros((gids.max() + 1 - n, 4))])
    alive = np.concatenate([alive, np.zeros((gids.max() + 1 - n,), bool)])
    table[gids] = new
    alive[gids] = True
    dead = np.concatenate([rng.choice(n, 48, replace=False), gids[:16]])
    front.delete("paper", dead)
    alive[dead] = False
    check(rt.stats.flushes == 0, "paper: mutations merged instead of buffering")
    round_("live", seed=8)
    log(f"paper: {rt.stats.inserts} inserts, {rt.stats.deletes} deletes "
        f"buffered; answers == host oracle, brute force, join oracle")


def multichip_phase(n: int, dev) -> None:
    import jax

    from repro.core import bulk
    from repro.kernels import ops
    from repro.launch.spatial_serve import SpatialServer

    n_dev = len(jax.devices())
    data = squares(n, seed=9)
    sched = ops.device_schedule(data.astype(np.float32),
                                levels=bulk.default_levels(n) + 4)
    queries = np.stack([as_rect(k, p) for k, p in
                        rect_requests(data, 2 * n_dev * QUERY_BLOCK,
                                      seed=10)])
    nb = queries.shape[0] // QUERY_BLOCK
    check(nb % n_dev == 0, "multichip: blocks must split over every chip")

    many = SpatialServer(sched, query_block=QUERY_BLOCK, cache_size=0)
    check(many._pmapped is not None, "multichip: no pmap path was built")
    pmapped = many._pmapped
    calls = []

    def counted(*a):  # proves the batch went through the pmap replicas
        calls.append(1)
        return pmapped(*a)

    many._pmapped = counted
    t0 = time.perf_counter()
    hits_p, visits_p = many.search(queries)
    pmap_s = time.perf_counter() - t0
    check(calls == [1], "multichip: the batch did not take the pmap path")

    one = SpatialServer(sched, query_block=QUERY_BLOCK, cache_size=0)
    t0 = time.perf_counter()
    with jax.default_device(jax.devices()[0]):
        parts = [one.search(queries[i:i + QUERY_BLOCK])
                 for i in range(0, queries.shape[0], QUERY_BLOCK)]
    one_s = time.perf_counter() - t0
    hits_1 = np.concatenate([h for h, _ in parts])
    visits_1 = np.concatenate([v for _, v in parts])
    check(np.array_equal(hits_p, hits_1) and np.array_equal(visits_p, visits_1),
          "multichip: pmap answers differ from the one-chip server")
    alive = np.ones((n,), bool)
    table = data.astype(np.float32)
    for i, q in enumerate(queries):
        check(np.array_equal(hits_p[i], brute_hits(table, alive, q)),
              "multichip: hits differ from brute force")
    for name, srv in (("pmap", many), ("one-chip", one)):
        s = srv.stats
        check(s.degraded_batches == 0 and set(k for k, v in
              s.rung_dispatches.items() if v) <= {"pallas"},
              f"multichip: {name} server fell back ({s.rung_dispatches})")
    log(f"multichip: pyramid n={n} levels={sched.levels} W={sched.width} "
        f"queries={queries.shape[0]} blocks={nb} over {n_dev} chips "
        f"pmap_s={pmap_s:.2f} one_chip_s={one_s:.2f} (both incl. compile) "
        f"peak_bytes_in_use={peak_bytes(dev)}; pmap == one chip == brute "
        f"force")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=10_000_000,
                    help="objects per map-search tenant")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pmap path over four chips")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow the CPU (interpret mode) to rehearse the "
                         "control flow; relaxes only the platform check")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    platform_ok = dev.platform == "tpu" or (
        args.cpu_rehearsal and dev.platform == "cpu")
    if not platform_ok:
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}; smoke timings of one cold run, not "
        f"benchmark numbers")
    t0 = time.perf_counter()
    if args.chips == 4:
        multichip_phase(min(args.n, 131_072), dev)
    else:
        map_search_phase(args.n, dev)
        paper_phase(min(args.n, PAPER_N), dev)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
