"""CPU rehearsal of every cell of BENCHMARK.json: the whole run, from the
seed's data through the front end to the comparison with the reference,
at a tiny size with the Pallas kernels in interpret mode.  The timings a
CPU run prints are not device numbers; what is checked here is that every
cell runs, answers correctly and reports its metrics."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
# Tiny and quick on the CPU: a few thousand objects, small batches.
SMALL = {"config": {"n": 2000, "query_block": 4}}


def small(cell):
    traffic = harness.load_cell(ROOT, cell).traffic
    over = {"config": dict(SMALL["config"])}
    if traffic["loop"] == "open":
        over["traffic"] = {"rate_per_s": 12.0}
    else:
        over["traffic"] = {"clients": 8}
    return over


def expected(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(cell):
    r = harness.run_cell(ROOT, cell, 2**31 + 17, 1.5, False,
                         require_chip=False, cache=False,
                         overrides=small(cell))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == expected("end_to_end", cell)
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        r["device"])


def test_traced_run_reports_host_side_layers():
    cell = "paper-mqr-30k.nearest"
    r = harness.run_cell(ROOT, cell, 5, 1.5, True, require_chip=False,
                         cache=False, overrides=small(cell))
    assert r["correct"], r["checks"]
    # The CPU has no device plane: device-trace metrics find nothing to
    # read and are left out, never reported as 0.
    assert set(r["metrics"]) == {"queue_wait_ms", "service_ms"}
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_run_without_a_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_same_seed_same_inputs_other_seed_same_multiset():
    cell = harness.load_cell(ROOT, "paper-mqr-30k.nearest", small(
        "paper-mqr-30k.nearest"))
    a = harness.make_inputs(cell, 3)
    b = harness.make_inputs(cell, 3)
    c = harness.make_inputs(cell, 2**31 + 3)
    ra, oa = a.window_requests(5.0)
    rb, ob = b.window_requests(5.0)
    rc, oc = c.window_requests(5.0)
    assert (a.data == b.data).all() and (oa == ob).all()
    assert all(x[0] == y[0] and (x[1] == y[1]).all() for x, y in zip(ra, rb))
    assert len(rc) == len(ra)
    assert sorted(k for k, _ in rc) == sorted(k for k, _ in ra)
    assert abs(oc[-1] - oa[-1]) < 1e-9
    assert not (a.data == c.data).all()
