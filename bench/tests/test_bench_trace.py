"""The trace reduction, checked on a small trace recorded on one TPU v5e
chip by ``record_trace.py`` (the render cell cut to n = 20,000 objects,
16 clients and a half-second window)."""

import json
import os

import pytest

from bench import tracereduce
from bench.kernelnames import SWEEP_KERNELS

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PB = os.path.join(DATA, "render_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "render_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def summary():
    return tracereduce.summarize(PB)


def test_reduction_repeats_the_recorded_run(summary, recorded):
    dev = recorded["device"]
    assert summary.busy_s == dev["busy_s"]
    assert summary.window_s == dev["window_s"]
    assert [[n, s] for n, s in summary.top_ops(10)] == \
        recorded["breakdown"]["device_ops"]
    assert [s for _, s in summary.gaps[:10]] == \
        [s for _, s in recorded["breakdown"]["idle_gaps"]]


def test_busy_lies_inside_the_window(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s < summary.window_s
    assert sum(summary.op_seconds.values()) >= summary.busy_s * (1 - 1e-9)


def test_sweep_kernels_are_found_by_name(summary):
    secs, count = summary.ops_matching(SWEEP_KERNELS)
    # the recorded window held 67 launches of 16 queries, one sweep each
    assert count == 67
    assert 0 < secs <= summary.busy_s


def test_gaps_are_named_by_the_host_annotations(summary):
    names = {n.split(" / ")[0] for n, _ in summary.gaps}
    assert names <= {"bench.window", "bench.pump", "bench.submit",
                     "bench.wait", "bench.drain"}
    lengths = [s for _, s in summary.gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert sum(lengths) <= summary.window_s - summary.busy_s + 1e-9


def test_union_merges_overlaps():
    assert tracereduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == \
        [[0, 3], [5, 9]]
    assert tracereduce._clip([(0, 10), (12, 15)], 5, 13) == \
        [(5, 10), (12, 13)]
