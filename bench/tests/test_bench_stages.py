"""The stage-counter readers: per-launch values from the window's
``AccessStats`` deltas, nothing without a device plane, nothing from a
program that lacks the counter."""

import os
import types

import pytest

from bench import harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = {"prepare_ms": "prepare_s", "wait_ms": "wait_s",
          "fetch_ms": "fetch_s", "finish_ms": "finish_s"}


def _run(stats, *, devices=1, kinds=("knn", "knn", "region", "knn")):
    # one launch per distinct t_launch: here knn@0 (2 requests), region@1,
    # knn@2
    launch_at = [0.0, 0.0, 1.0, 2.0]
    tickets = [harness.Ticket(kind, t - 0.5, t, t + 0.25, True)
               for kind, t in zip(kinds, launch_at)]
    trace = types.SimpleNamespace(n_devices=devices)
    return harness.Run(loop="open", setup_s=1.0, window_start=0.0,
                       window_end=3.0, tickets=tickets, stats=stats,
                       trace=trace)


def _read(name, run):
    return harness.metric_reader(BENCH_DIR, name)(run)


STATS = {"prepare_s": 0.03, "wait_s": 0.006, "fetch_s": 0.0015,
         "finish_s": 0.009, "h2d_bytes": 4_500_000, "knn_rounds": 10}


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_ms_per_launch(name):
    assert _read(name, _run(STATS)) == pytest.approx(
        STATS[STAGES[name]] * 1e3 / 3)


def test_h2d_mb_per_launch():
    assert _read("h2d_mb", _run(STATS)) == pytest.approx(1.5)


def test_knn_rounds_per_knn_launch():
    assert _read("knn_rounds", _run(STATS)) == pytest.approx(5.0)
    assert _read("knn_rounds", _run(STATS, kinds=("region",) * 4)) is None


@pytest.mark.parametrize("name", sorted(STAGES) + ["h2d_mb", "knn_rounds"])
def test_no_reading_without_device_or_counter(name):
    assert _read(name, _run(STATS, devices=0)) is None
    run = _run(STATS)
    run.trace = None
    assert _read(name, run) is None
    parent = {k: v for k, v in STATS.items()
              if k not in set(STAGES.values()) | {"h2d_bytes", "knn_rounds"}}
    assert _read(name, _run(parent)) is None
