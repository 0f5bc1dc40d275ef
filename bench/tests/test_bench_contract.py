"""BENCHMARK.json against the rules it is written to: names, units,
files found by name, and every metric with its reader."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"] == ["python3", "bench/run.py"]


def test_names_units_and_uniqueness():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            dataset = json.load(f)["dataset"]
        with open(os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            generator = json.load(f)["generator"]
        assert os.path.exists(os.path.join(
            ROOT, "bench", "datasets", dataset + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "generators", generator + ".py"))
    assert used == set(configs)


def test_every_metric_has_a_reader_and_sound_arrows():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        # every cell that reports the layer metric reports what it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


def test_configs_state_their_limits():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
        assert {"unanswered", "hits_wrong"} <= set(cfg["limits"])
