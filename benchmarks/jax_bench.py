"""TPU-adaptation benchmarks: vectorized search, kernels, spatial serving.

``REPRO_BENCH_TINY=1`` shrinks every object count to smoke sizes so the
CI bench-smoke job can exercise the whole harness in seconds.
"""

from __future__ import annotations

import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import datasets, flat, mqrtree
from repro.kernels import ops
from repro.obs import counters as obs_counters
from repro.obs import trace as obs_trace

TINY = os.environ.get("REPRO_BENCH_TINY", "0") == "1"


def _timeit(fn, *args, iters=5, warm=True):
    if warm:  # settle jit compilation; skip for pure-host one-pass timings
        fn(*args)
    t0 = time.time()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.time() - t0) / iters


def bench_flat_search():
    data = datasets.uniform_squares(300 if TINY else 2000, seed=1)
    tree = mqrtree.build(data)
    ft = flat.flatten(tree)
    qs = jnp.asarray(datasets.region_queries(data, 32, seed=2), jnp.float32)
    t_host = _timeit(
        lambda: [tree.region_search(np.asarray(q)) for q in qs], iters=2
    )
    t_jax = _timeit(lambda: flat.region_search_batch(ft, qs), iters=2)
    return [
        (t_host / 32, {"impl": "host-pointer", "queries": 32}),
        (t_jax / 32, {"impl": "jax-levelized", "queries": 32}),
    ]


def bench_pyramid_build():
    """Build throughput, host pointer insertion vs the device bulk build.

    Both pipelines end at a query-ready ``LevelSchedule`` (the host side
    pays build + flatten + level_schedule, the device side ONE launch of
    the bulk fixed point, DESIGN.md §7); objects/sec at every n sits in
    one derived dict per impl so the crossover reads off a single row.
    """
    ns = (200, 400) if TINY else (1_000, 10_000, 100_000)

    def host_build(data):
        return flat.level_schedule(flat.flatten(mqrtree.build(data)))

    rows = []
    for impl, build in (
        ("host-pointer-build", host_build),
        ("device-bulk-build", lambda d: ops.device_schedule(d)),
    ):
        objs_per_sec, t_last = {}, 0.0
        device = impl.startswith("device")
        for n in ns:
            data = datasets.uniform_squares(n, seed=3)
            # the host pointer build is O(minutes) at n=1e5: time ONE pass,
            # no warm call (nothing to compile on the pure-host path)
            iters = 3 if (device and n <= 10_000) else 1
            t_last = _timeit(build, data, iters=iters, warm=device)
            objs_per_sec[str(n)] = round(n / t_last)
        rows.append((t_last, {"impl": impl, "objects_per_sec": objs_per_sec,
                              "n_max": ns[-1]}))
    return rows


def bench_compact_scan():
    """Bytes-per-query of the fused sweep: float32 tiles vs conservative
    uint16 tiles (+ exact confirming pass).  Hit sets are asserted
    identical; the bytes ratio is the streamed (mbr tiles + parent rows)
    HBM traffic of one launch, which the compact path halves."""
    n, n_q = (256, 8) if TINY else (4096, 32)
    data = datasets.uniform_squares(n, seed=1)
    sched = ops.device_schedule(data)
    qsched = ops.quantize_schedule(sched)
    qs = datasets.region_queries(data, n_q, seed=2)

    t_f = _timeit(lambda: ops.pyramid_scan(sched, qs), iters=3)
    t_c = _timeit(lambda: ops.pyramid_scan_compact(qsched, qs), iters=3)
    hits_f, visits_f = ops.pyramid_scan(sched, qs)
    hits_c, visits_c = ops.pyramid_scan_compact(qsched, qs)
    assert np.array_equal(np.asarray(hits_f), np.asarray(hits_c))
    bytes_f = sched.mbr_cm.nbytes + sched.parent.nbytes
    bytes_c = qsched.streamed_bytes
    return [
        (t_f, {"impl": "float32-tiles", "q/s": round(n_q / t_f),
               "bytes/query": round(bytes_f / n_q),
               "accesses": int(np.asarray(visits_f).sum())}),
        (t_c, {"impl": "compact-uint16-tiles", "q/s": round(n_q / t_c),
               "bytes/query": round(bytes_c / n_q),
               "bytes_ratio": round(bytes_c / bytes_f, 3),
               "accesses": int(np.asarray(visits_c).sum())}),
    ]


def bench_mbr_scan_kernel():
    n = 512 if TINY else 8192
    lo = jnp.asarray(np.random.default_rng(0).uniform(0, 1000, (n, 2)), jnp.float32)
    mbrs = jnp.concatenate([lo, lo + 10.0], axis=1)
    qs = jnp.asarray(datasets.region_queries(np.asarray(mbrs), 8, seed=1), jnp.float32)
    t_k = _timeit(lambda: ops.mbr_scan(mbrs, qs), iters=3)
    t_r = _timeit(lambda: ops.mbr_scan_ref(mbrs, qs), iters=3)
    return [
        (t_k, {"impl": "pallas-interpret", "n": n}),
        (t_r, {"impl": "jnp-ref", "n": n}),
    ]


def bench_pyramid_scan():
    """The paper's Section 5 disk-access comparison, on-accelerator: fused
    single-launch level sweep vs one-kernel-per-level vs host pointers."""
    n, n_q = (300, 8) if TINY else (2000, 32)
    data = datasets.uniform_squares(n, seed=1)
    tree = mqrtree.build(data)
    sched = flat.level_schedule(flat.flatten(tree))
    qs = datasets.region_queries(data, n_q, seed=2)
    qj = jnp.asarray(qs, jnp.float32)

    t_fused = _timeit(lambda: ops.pyramid_scan(sched, qj), iters=3)
    t_level = _timeit(lambda: ops.per_level_region_search(sched, qj), iters=3)
    t_host = _timeit(
        lambda: [tree.region_search(np.asarray(q)) for q in qs], iters=2
    )
    _, visits = ops.pyramid_scan(sched, qj)
    accesses = int(jnp.sum(visits))
    _, _, launches = ops.per_level_region_search(sched, qj)
    return [
        (t_fused, {"impl": "pyramid-scan-fused", "launches": 1,
                   "q/s": round(n_q / t_fused), "accesses": accesses}),
        (t_level, {"impl": "per-level-mbr-scan", "launches": launches,
                   "q/s": round(n_q / t_level), "accesses": accesses}),
        (t_host, {"impl": "host-pointer", "launches": 0,
                  "q/s": round(n_q / t_host), "accesses": accesses}),
    ]


def bench_index_api():
    """Façade overhead: `SpatialIndex.region` vs calling the fused kernel
    directly must be <5%; plus a first-class knn row (DESIGN.md §6).

    Both sides deliver host-side numpy results (what a caller consumes);
    timing interleaves the two and keeps the per-impl minimum so container
    scheduling jitter does not swamp the microseconds of façade work.
    """
    from repro.index import SpatialIndex

    n, n_q, k = (300, 8, 4) if TINY else (2000, 32, 8)
    data = datasets.uniform_squares(n, seed=1)
    idx = SpatialIndex.build(data, structure="mqr", backend="pallas")
    sched = idx.schedule
    qs = datasets.region_queries(data, n_q, seed=2)

    # Apples-to-apples: both sides take the same numpy queries and deliver
    # host-side numpy results (what a caller consumes).
    def direct():
        hits, visits = ops.pyramid_scan(sched, qs)
        return np.asarray(hits), np.asarray(visits)

    def facade():
        return idx.region(qs).hits

    direct(), facade()  # warm / compile
    # Paired timing: each iteration measures both back-to-back, so the
    # slowly-drifting container noise cancels in the per-pair delta.
    ds, fs = [], []
    for _ in range(80):
        t0 = time.time()
        direct()
        t1 = time.time()
        facade()
        t2 = time.time()
        ds.append(t1 - t0)
        fs.append(t2 - t1)
    t_direct = float(np.median(ds))
    t_facade = float(np.median(fs))
    overhead = float(np.median(np.array(fs) - np.array(ds))) / t_direct

    pts = np.random.default_rng(3).uniform(100, 900, (n_q, 2))
    idx.knn(pts, k)  # warm the expanding-radius round shapes
    before = idx.stats.to_dict()
    t_knn = _timeit(lambda: idx.knn(pts, k).ids, iters=3)
    delta = idx.stats.diff(before)  # windowed deltas, not lifetime totals
    accesses = delta["node_accesses"] / max(delta["knn_queries"], 1)
    # Facade build throughput: `SpatialIndex.build(structure="pyramid",
    # build="device")` objects/sec across the crossover sizes, one row.
    build_ns = (200, 400) if TINY else (1_000, 10_000, 100_000)
    build_objs, t_build = {}, 0.0
    for bn in build_ns:
        bdata = datasets.uniform_squares(bn, seed=4)
        t_build = _timeit(
            lambda d=bdata: SpatialIndex.build(
                d, structure="pyramid", backend="pallas", build="device"
            ),
            iters=1,
        )
        build_objs[str(bn)] = round(bn / t_build)

    # precision="compact": identical hits through the facade, half the
    # streamed tile bytes (see kernel_compact_scan for the byte ledger).
    cidx = idx.with_backend("pallas", precision="compact")
    res_c = cidx.region(qs)
    assert np.array_equal(res_c.hits, idx.region(qs).hits)
    t_compact = _timeit(lambda: cidx.region(qs).hits, iters=3)

    return [
        (t_direct, {"impl": "pyramid-scan-direct", "q/s": round(n_q / t_direct)}),
        (t_facade, {"impl": "spatial-index-facade", "q/s": round(n_q / t_facade),
                    "overhead": f"{overhead:+.1%}"}),
        (t_compact, {"impl": "spatial-index-compact",
                     "q/s": round(n_q / t_compact)}),
        (t_build, {"impl": "spatial-index-build-device",
                   "objects_per_sec": build_objs, "n_max": build_ns[-1]}),
        (t_knn, {"impl": "spatial-index-knn", "k": k,
                 "q/s": round(n_q / t_knn),
                 "accesses/query": round(accesses, 1)}),
    ]


def bench_live_update():
    """Live-update subsystem (DESIGN.md §8): mutation throughput and the
    query rent of an un-merged delta buffer.

    Rows: inserts/sec and deletes/sec into the device-resident buffer,
    region q/s at ~10% and ~50% buffer fill (the flat delta levels ride
    the same fused launch), and q/s after the merge compacts everything
    back into a clean base build (flush wall-time reported alongside).
    """
    from repro.index import SpatialIndex

    n, capacity, n_q = (200, 64, 8) if TINY else (4000, 1024, 32)
    data = datasets.uniform_squares(n, seed=1)
    idx = SpatialIndex.build(
        data, structure="pyramid", backend="pallas",
        merge=dict(capacity=capacity, max_fill=1.0, max_tombstone_ratio=1.0),
    )
    qs = datasets.region_queries(data, n_q, seed=2)
    rng = np.random.default_rng(3)
    rows = []

    b = max(capacity // 10, 1)
    ins1 = datasets.uniform_squares(b, seed=4)
    t0 = time.time()
    idx.insert(ins1)
    t_ins = time.time() - t0
    rows.append((t_ins, {"impl": "live-insert", "batch": b,
                         "inserts_per_sec": round(b / t_ins)}))

    t10 = _timeit(lambda: idx.region(qs).hits, iters=3)
    rows.append((t10, {"impl": "live-query-10pct-fill",
                       "q/s": round(n_q / t10),
                       "fill": round(idx._updates.fill, 2)}))

    victims = rng.choice(
        np.nonzero(idx._updates.alive)[0], size=b, replace=False
    )
    t0 = time.time()
    idx.delete(victims)
    t_del = time.time() - t0
    rows.append((t_del, {"impl": "live-delete", "batch": b,
                         "deletes_per_sec": round(b / t_del)}))

    idx.insert(datasets.uniform_squares(int(capacity * 0.4), seed=5))
    t50 = _timeit(lambda: idx.region(qs).hits, iters=3)
    rows.append((t50, {"impl": "live-query-50pct-fill",
                       "q/s": round(n_q / t50),
                       "fill": round(idx._updates.fill, 2)}))

    t0 = time.time()
    idx.flush()
    t_flush = time.time() - t0
    tpf = _timeit(lambda: idx.region(qs).hits, iters=3)
    rows.append((tpf, {"impl": "live-query-post-flush",
                       "q/s": round(n_q / tpf),
                       "flush_ms": round(t_flush * 1e3, 1),
                       "n_live": idx.n_objects}))
    return rows


def bench_durability():
    """Durability subsystem (DESIGN.md §9): what fault tolerance costs.

    Rows: snapshot save/load throughput (MB/s over the npz payload), the
    WAL tax per mutation with and without fsync, and recovery wall-time
    (snapshot load + WAL tail replay) as the tail grows.
    """
    import shutil
    import tempfile

    from repro.checkpoint import DurableIndex, load_index, save_index
    from repro.index import SpatialIndex

    n, n_mut, tail = (300, 20, (5, 20)) if TINY else (8000, 200, (50, 400))
    data = datasets.uniform_squares(n, seed=7)
    idx = SpatialIndex.build(data, backend="pallas", capacity=max(n_mut, 64))
    idx.insert(datasets.uniform_squares(n_mut // 2, seed=8))
    root = pathlib.Path(tempfile.mkdtemp(prefix="repro-bench-durable-"))
    rows = []
    try:
        t_save = _timeit(lambda: save_index(idx, root / "snap"),
                         iters=3, warm=False)
        nbytes = (root / "snap" / "arrays.npz").stat().st_size
        rows.append((t_save, {"impl": "snapshot-save", "n": idx.n_objects,
                              "MB/s": round(nbytes / t_save / 2**20, 1)}))
        t_load = _timeit(lambda: load_index(root / "snap", backend="pallas"),
                         iters=3, warm=False)
        rows.append((t_load, {"impl": "snapshot-load", "n": idx.n_objects,
                              "MB/s": round(nbytes / t_load / 2**20, 1)}))

        for sync in (False, True):
            d = DurableIndex.create(
                data, root / f"wal-{sync}", backend="pallas", sync=sync,
                capacity=max(n_mut * 4, 64),
            )
            batches = [datasets.uniform_squares(1, seed=100 + i)
                       for i in range(n_mut)]
            t0 = time.time()
            for b in batches:
                d.insert(b)
            t_mut = (time.time() - t0) / n_mut
            d.close()
            rows.append((t_mut, {
                "impl": f"wal-insert-{'fsync' if sync else 'nosync'}",
                "mutations": n_mut, "us_per_op": round(t_mut * 1e6, 1),
            }))

        for n_tail in tail:
            troot = root / f"tail-{n_tail}"
            d = DurableIndex.create(
                data, troot, backend="pallas", sync=False,
                capacity=max(n_tail * 2, 64),
            )
            for i in range(n_tail):
                d.insert(datasets.uniform_squares(1, seed=200 + i))
            d.close()
            t_rec = _timeit(
                lambda r=troot: DurableIndex.recover(
                    r, backend="pallas", sync=False
                ).close(),
                iters=2, warm=False,
            )
            rows.append((t_rec, {"impl": "recover", "wal_ops": n_tail,
                                 "ms": round(t_rec * 1e3, 1)}))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rows


def bench_join():
    """Tree-vs-tree spatial join (DESIGN.md §10) vs the nested-loop oracle.

    Rows: joins/sec for the levelized pair sweep on float32 and compact
    tiles (candidate pair counts alongside — the pruning is the point)
    against the brute-force O(n·m) host oracle on the same data.
    """
    from repro.index import SpatialIndex

    na, nb = (300, 200) if TINY else (2000, 1500)
    da = datasets.uniform_squares(na, seed=1)
    db = datasets.exponential_squares(nb, seed=2)
    left = SpatialIndex.build(da, structure="mqr", backend="pallas")
    right = SpatialIndex.build(db, structure="mqr", backend="pallas")
    compact = SpatialIndex.build(
        da, structure="mqr", backend="pallas", precision="compact"
    )

    res = left.join(right)
    a32, b32 = np.asarray(da, np.float32), np.asarray(db, np.float32)

    def brute():
        return (
            (a32[:, None, 0] <= b32[None, :, 2])
            & (b32[None, :, 0] <= a32[:, None, 2])
            & (a32[:, None, 1] <= b32[None, :, 3])
            & (b32[None, :, 1] <= a32[:, None, 3])
        )

    t_j = _timeit(lambda: left.join(right).pairs, iters=3)
    t_c = _timeit(lambda: compact.join(right).pairs, iters=3)
    t_b = _timeit(brute, iters=3)
    return [
        (t_j, {"impl": "join-pair-sweep", "n": f"{na}x{nb}",
               "joins_per_sec": round(1 / t_j, 2),
               "pairs": res.n_pairs,
               "pair_tests": int(res.pair_visits.sum())}),
        (t_c, {"impl": "join-pair-sweep-compact", "n": f"{na}x{nb}",
               "joins_per_sec": round(1 / t_c, 2)}),
        (t_b, {"impl": "join-brute-oracle", "n": f"{na}x{nb}",
               "joins_per_sec": round(1 / t_b, 2),
               "pair_tests": na * nb}),
    ]


def bench_moving():
    """Moving-object workload: delta-buffer churn vs naive rebuilds.

    Rows: ticks/sec for the live-update path (batch delete + insert per
    tick, continuous region + join queries) against the rebuild-per-tick
    baseline on the identical seeded motion.
    """
    from repro.launch.moving import MovingConfig, MovingWorkload

    ticks = 5 if TINY else 50
    cfg = MovingConfig(n_objects=64 if TINY else 256, moves_per_tick=8,
                       query_every=5, seed=1)
    live = MovingWorkload(cfg, backend="pallas", capacity=128)
    t0 = time.time()
    live.run(ticks)
    t_live = time.time() - t0

    base = MovingWorkload(cfg, backend="pallas", rebuild_per_tick=True)
    t0 = time.time()
    base.run(ticks)
    t_base = time.time() - t0
    live_stats = live.query_index.stats.to_dict()
    return [
        (t_live, {"impl": "moving-delta-buffer", "ticks": ticks,
                  "ticks_per_sec": round(ticks / t_live, 2),
                  "merges": live_stats["flushes"],
                  "joins": live_stats["joins"]}),
        (t_base, {"impl": "moving-rebuild-per-tick", "ticks": ticks,
                  "ticks_per_sec": round(ticks / t_base, 2),
                  "speedup_vs_rebuild": round(t_base / t_live, 2)}),
    ]


def bench_serving():
    """Latency under open-loop load through the serving front end.

    One row per offered-QPS level: p50/p99/p99.9 completion latency of
    single-request arrivals coalesced into ``query_block`` batches, plus
    shed / SLO-violation counters — the latency-vs-load curve the
    front end exists for (DESIGN.md §11).  Arrivals are Poisson and
    latency is measured from the SCHEDULED arrival, so the curve is
    free of coordinated omission.
    """
    from repro.launch.loadgen import demo_dataset
    from repro.serve import ServerConfig, ServingFrontEnd
    from repro.serve.loadgen import run_sweep

    levels = [25.0, 100.0, 400.0] if TINY else [50.0, 200.0, 800.0]
    duration = 0.4 if TINY else 2.0
    data = {"demo": demo_dataset(256 if TINY else 4096)}
    cfg = ServerConfig.from_dict({
        "tenants": [{"name": "demo", "backend": "serve"}],
        "query_block": 8 if TINY else 16,
    })

    def make_front():
        return ServingFrontEnd.build(cfg, data), "demo"

    rows = run_sweep(make_front, levels, duration=duration, seed=0)
    return [
        (row["mean_ms"] / 1e3,
         {"impl": "serve-frontend",
          "qps_offered": round(row["qps_offered"], 1),
          "qps_achieved": round(row["qps_achieved"], 1),
          "p50_ms": round(row["p50_ms"], 3),
          "p99_ms": round(row["p99_ms"], 3),
          "p999_ms": round(row["p999_ms"], 3),
          "shed": row["shed"],
          "slo_violations": row["slo_violations"],
          "avg_batch": row["avg_batch"],
          "deadline_launches": row["deadline_launches"]})
        for row in rows
    ]


def bench_stream_scan():
    """DESIGN.md §12 headline rows.

    1. streamed-vs-resident fused kernel: bit-identical hits, q/s both.
    2. bytes/query: uint16 compact baseline vs uint8-upper + Hilbert
       leaves, visited-tile accounting at 64-slot granularity (hit sets
       asserted bit-identical through the real kernels first).
    3. the capacity row: region search over n=1e7 objects on ONE chip via
       the memory-bounded streamed sweep — the VMEM-resident path cannot
       represent this schedule at all (mbr tiles alone are ~25x VMEM).
    """
    from repro.kernels import fallback

    rows = []

    # -- 1. streamed vs resident kernel -------------------------------
    n, n_q = (400, 8) if TINY else (4096, 16)
    data = datasets.uniform_squares(n, seed=1)
    sched = ops.device_schedule(data)
    qs = datasets.region_queries(data, n_q, seed=2)
    t_res = _timeit(lambda: ops.pyramid_scan(sched, qs), iters=3)
    t_str = _timeit(lambda: ops.pyramid_scan(sched, qs, stream=True), iters=3)
    h_r, v_r = ops.pyramid_scan(sched, qs)
    h_s, v_s = ops.pyramid_scan(sched, qs, stream=True)
    assert np.array_equal(np.asarray(h_s), np.asarray(h_r))
    assert np.array_equal(np.asarray(v_s), np.asarray(v_r))
    win_off, win_w = ops.parent_windows(sched.parent, sched.n_real,
                                        block_w=128)
    rows.append((t_res, {"impl": "vmem-resident", "n": n,
                         "q/s": round(n_q / t_res)}))
    rows.append((t_str, {"impl": "hbm-streamed", "n": n,
                         "q/s": round(n_q / t_str), "win_w": int(win_w),
                         "hits_identical": True}))

    # -- 2. bytes/query ------------------------------------------------
    # Headline: the resident uint16 compact path streams its FULL grid
    # HBM->VMEM every launch (each BlockSpec tile is DMA'd whether or not
    # any query can reach it — that is what pallas_call does); the
    # streamed sweep's dead-window skip only DMAs tiles whose parent
    # window still holds a survivor for some query in the batch.  Both
    # sides count mbr+parent tile traffic per query on the SAME uint16
    # grid, hit sets asserted bit-identical through the real kernels.
    nb, nqb = (400, 8) if TINY else (20_000, 8)
    data_b = datasets.uniform_squares(nb, seed=4)
    qs_b = datasets.region_queries(data_b, nqb, seed=5)
    plain = ops.device_schedule(data_b, engine="jnp")
    hil = ops.device_schedule(data_b, engine="jnp", order="hilbert")
    q16 = ops.quantize_schedule(plain, engine="jnp")
    q8h = ops.quantize_schedule(hil, engine="jnp", upper8=True)
    # hit sets through the real kernels: bit-identical across the board
    h16, _ = ops.pyramid_scan_compact(q16, qs_b)
    h16s, _ = ops.pyramid_scan_compact(q16, qs_b, stream=True)
    h8h, _ = ops.pyramid_scan_compact8(q8h, qs_b)
    assert np.array_equal(np.asarray(h16), np.asarray(h8h))
    assert np.array_equal(np.asarray(h16), np.asarray(h16s))

    # The ledger math lives in repro.obs.counters — the SAME functions
    # the kernel wrappers call to emit LaunchReports, so what the bench
    # discloses and what production telemetry discloses are one number.
    n_real = np.asarray(plain.n_real, np.int64)
    g16 = np.asarray(q16.mbr_q, np.int64)
    p16 = np.asarray(q16.parent_q, np.int64)
    qq16p = obs_counters.quantize_queries_grid(
        qs_b, q16.origin, q16.inv_cell, q16.cells)
    resident_bpq = q16.streamed_bytes / qs_b.shape[0]
    win_off, win_w = ops.parent_windows(p16, n_real, block_w=128)
    tile_b, mask_b, fetched, n_tiles, _ = obs_counters.stream_fetch_bytes(
        g16, p16, qq16p, win_off, win_w, block_w=128,
        root_unconditional=plain.root_unconditional,
    )
    rows.append((0.0, {"impl": "bytes-compact-uint16-resident", "n": nb,
                       "bytes/query": round(resident_bpq)}))
    rows.append((0.0, {"impl": "bytes-streamed-skip-uint16", "n": nb,
                       "bytes/query": round(tile_b / nqb),
                       "bytes_ratio": round(tile_b / nqb / resident_bpq, 4),
                       "tiles_fetched": f"{fetched}/{n_tiles}",
                       "mask_bytes/query": round(mask_b / nqb),
                       "hits_identical": True}))

    # Context rows: the paper's visited-tile disk ledger (a tile charged
    # only when one of its real slots must be tested) — the floor of
    # this model is 384/640 = 0.6x, which uint8 upper tiles + Hilbert
    # leaf order approach; the coarse u8 grid really is what the upper
    # levels test, so the accounting mixes grids per level.
    bpq16 = obs_counters.tile_bytes_per_query(
        g16, p16, n_real, qq16p, split=0,
        root_unconditional=plain.root_unconditional,
    )
    mixed = np.asarray(q8h.mbr_q, np.int64).copy()
    if q8h.split:
        mixed[:q8h.split] = np.asarray(q8h.mbr_q8, np.int64)
    bpq8h = obs_counters.tile_bytes_per_query(
        mixed, np.asarray(q8h.parent_q, np.int64),
        np.asarray(hil.n_real, np.int64),
        obs_counters.quantize_queries_grid(
            qs_b, q8h.origin, q8h.inv_cell, q8h.cells),
        split=q8h.split,
        root_unconditional=hil.root_unconditional,
        qq8=obs_counters.quantize_queries_grid(
            qs_b, q8h.origin, q8h.inv_cell8, q8h.cells8),
    )
    rows.append((0.0, {"impl": "bytes-visited-uint16", "n": nb,
                       "bytes/query": round(bpq16)}))
    rows.append((0.0, {"impl": "bytes-compact8-hilbert", "n": nb,
                       "bytes/query": round(bpq8h),
                       "bytes_ratio": round(bpq8h / bpq16, 3),
                       "hits_identical": True}))

    # -- 3. the 1e7 capacity row (streamed twin; VMEM path impossible) -
    n_big = 5_000 if TINY else 10_000_000
    data_big = datasets.uniform_points(n_big, seed=3)
    sched_big = ops.device_schedule(data_big, engine="jnp")
    qs_big = datasets.region_queries(data_big, 4, seed=6).astype(np.float32)
    t_big = _timeit(
        lambda: fallback.fused_search_np(
            qs_big, sched_big.mbr_cm, sched_big.parent, sched_big.obj_mbr,
            sched_big.obj_level, sched_big.obj_slot, sched_big.obj_id,
            n_objects=sched_big.n_objects,
            root_unconditional=sched_big.root_unconditional,
            test_object_mbr=sched_big.test_object_mbr,
            n_shared=sched_big.n_shared,
            stream=True,
        ),
        iters=1, warm=False,
    )
    mbr_mb = sched_big.mbr_cm.nbytes / 2**20
    rows.append((t_big, {"impl": "streamed-twin-1e7", "n": n_big,
                         "q/s": round(4 / t_big, 2),
                         "levels": int(sched_big.parent.shape[0]),
                         "mbr_mb": round(mbr_mb, 1),
                         # ~16 MB VMEM/core: the resident kernel cannot
                         # even bind this schedule; streaming holds one
                         # (4, block_w) tile pair + two mask windows
                         "fits_vmem": bool(mbr_mb < 16)}))
    return rows


def bench_autotune():
    """Autotuned tiling vs the historical fixed block_w=128 (DESIGN.md
    §12).  Interpreted, larger tiles mean fewer Python kernel-body
    invocations per launch, so the tuner's win is visible on CPU too;
    natively it tracks VMEM/lane utilisation instead.  Hits are asserted
    bit-identical — the tuner only ever changes WHICH config runs."""
    from repro.index import SpatialIndex

    n, n_q = (640, 8) if TINY else (4096, 32)
    data = datasets.uniform_squares(n, seed=1)
    qs = datasets.region_queries(data, n_q, seed=2).astype(np.float32)
    fixed = SpatialIndex.build(data, structure="pyramid", backend="pallas",
                               build="device",
                               backend_opts={"autotune": "off"})
    tuned = fixed.with_backend("pallas", autotune="on")
    ref = fixed.region(qs)          # fixed 128-wide tiles
    res = tuned.region(qs)          # tunes on first batch, then cached
    assert np.array_equal(res.hits, ref.hits)
    t_fixed = _timeit(lambda: fixed.region(qs), iters=3)
    t_tuned = _timeit(lambda: tuned.region(qs), iters=3)
    (key, cfg), = tuned.artifacts.tuned.items()
    return [
        (t_fixed, {"impl": "fixed-block-128", "n": n,
                   "q/s": round(n_q / t_fixed, 1)}),
        (t_tuned, {"impl": "autotuned", "n": n,
                   "q/s": round(n_q / t_tuned, 1),
                   "block_w": cfg.block_w,
                   "query_block": cfg.query_block,
                   "levels_in_grid": cfg.levels_in_grid,
                   "speedup": round(t_fixed / t_tuned, 2),
                   "hits_identical": True}),
    ]


def bench_obs():
    """Observability tax (DESIGN.md §13): the <2% guarantee, measured.

    Rows: fused region q/s with tracing disabled vs enabled, plus the
    analytic overhead of the disabled fast path — per-call cost of a
    no-op ``span()`` (one enabled check returning the shared null span)
    times the two spans every ``region()`` call opens (facade +
    backend), as a percent of one region call.  The CI guard checks the
    analytic number: a direct A/B at smoke sizes is swamped by
    scheduler noise, the per-span cost is not.
    """
    from repro.index import SpatialIndex

    n, n_q = (640, 8) if TINY else (4096, 32)
    data = datasets.uniform_squares(n, seed=1)
    qs = datasets.region_queries(data, n_q, seed=2).astype(np.float32)
    idx = SpatialIndex.build(data, structure="pyramid", backend="pallas",
                             build="device",
                             backend_opts={"autotune": "off"})
    obs_trace.disable()
    t_off = _timeit(lambda: idx.region(qs).hits, iters=3)
    obs_trace.enable(capacity=1 << 16)
    try:
        t_on = _timeit(lambda: idx.region(qs).hits, iters=3)
    finally:
        obs_trace.disable()

    # per-span cost of the disabled fast path, amortized over K spans
    k = 20_000

    def noop_spans():
        for _ in range(k):
            with obs_trace.span("bench.noop"):
                pass

    t_span = _timeit(noop_spans, iters=3) / k
    spans_per_region = 2  # index.region + backend.<name>
    overhead_pct = 100.0 * spans_per_region * t_span / t_off
    return [
        (t_off, {"impl": "fused-tracing-off", "n": n,
                 "q/s": round(n_q / t_off, 1)}),
        (t_on, {"impl": "fused-tracing-on", "n": n,
                "q/s": round(n_q / t_on, 1),
                "qs_ratio": round((n_q / t_on) / (n_q / t_off), 4)}),
        (t_span * spans_per_region,
         {"impl": "disabled-span-tax",
          "per_span_ns": round(t_span * 1e9, 1),
          "spans_per_region": spans_per_region,
          "overhead_pct": round(overhead_pct, 4)}),
    ]


JAX_BENCHES = {
    "jax_flat_search": bench_flat_search,
    "jax_pyramid_build": bench_pyramid_build,
    "kernel_mbr_scan": bench_mbr_scan_kernel,
    "kernel_pyramid_scan": bench_pyramid_scan,
    "kernel_compact_scan": bench_compact_scan,
    "bench_stream_scan": bench_stream_scan,
    "bench_autotune": bench_autotune,
    "bench_obs": bench_obs,
    "index_api": bench_index_api,
    "live_update": bench_live_update,
    "durability": bench_durability,
    "join": bench_join,
    "moving": bench_moving,
    "serving": bench_serving,
}
