"""A new cell, a new traffic mix and a new per-layer metric are added by
new files alone: the harness finds them by the names in BENCHMARK.json
and in the cell's files.  A mix is a data file for the general generator,
or names a generator of its own."""

import json
import os
import shutil

import numpy as np
import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Skewed, bursty traffic as a data file alone: 4 hot requests placed anew
# every 16, sent four times as fast in the first quarter of every second.
SKEWED = {"loop": "open", "rate_per_s": 40.0, "generator": "general",
          "mix": {"region": 0.5, "knn": 0.5}, "objects_per_view": [16, 64],
          "hot": {"items": 4, "zipf": 0.99, "move_every": 16},
          "burst": {"period_s": 1.0, "on_s": 0.25, "factor": 4.0},
          "why": "hot and bursty"}
# A generator of its own, as a later cell would bring it: viewports
# around a few hot centres drawn with Zipf weights, in bursts.
HOTSPOT = '''
import numpy as np

from bench.loadgen import MULTISET_SEED


class Maker:
    def __init__(self, traffic, config, data):
        self.traffic, self.data = traffic, data

    def make(self, count, rng, kinds=None):
        hot = self.data[rng.integers(0, self.data.shape[0],
                                     self.traffic["hot"])]
        w = 1.0 / np.arange(1, hot.shape[0] + 1) ** self.traffic["zipf"]
        pick = np.random.default_rng(MULTISET_SEED).choice(
            hot.shape[0], count, p=w / w.sum())
        pick = pick[rng.permutation(count)]
        c = (hot[pick, :2] + hot[pick, 2:]) / 2
        h = self.traffic["half"]
        return [("region", np.float32([x - h, y - h, x + h, y + h]))
                for x, y in c]


def arrivals(traffic, rate, seconds, rng):
    # every request of a second arrives at its start
    n = int(rate * seconds)
    return np.floor(np.arange(n) / rate)
'''
MIXES = {
    "skewed": {"bench/traffic/skewed.json": json.dumps(SKEWED)},
    "hotspot": {"bench/traffic/hotspot.json": json.dumps(
        {"loop": "open", "rate_per_s": 10.0, "generator": "hotspot",
         "hot": 5, "zipf": 0.99, "half": 3.0, "why": "hot viewports"}),
        "bench/generators/hotspot.py": HOTSPOT},
}
METRIC = '''
def read(run):
    queries = run.stats["queries"]
    return run.stats["node_accesses"] / queries if queries else None
'''


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_new_cell_mix_and_metric_from_new_files(tmp_path, mix):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "bench", "configs",
                           "paper-mqr-30k.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-mqr", n=1500, query_block=4)
    files = dict(MIXES[mix], **{
        "bench/configs/tiny-mqr.json": json.dumps(cfg),
        "bench/metrics/visits_per_query.py": METRIC,
    })
    for rel, text in files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    cell = f"tiny-mqr.{mix}"
    bench["configs"].append({"name": "tiny-mqr", "source": "test",
                             "file": "bench/configs/tiny-mqr.json",
                             "reduced": ["n"], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-mqr",
                               "traffic": mix, "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "visits_per_query", "unit": "nodes", "better": "lower",
        "source": "program_counter", "layer": "front end",
        "moves": "p50_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    inp = harness.make_inputs(harness.load_cell(root, cell), 11)
    reqs, offsets = inp.window_requests(1.5)
    sent = offsets.shape[0]
    assert len(reqs) == sent > 10
    # hot requests repeat
    assert len({r.tobytes() for _, r in reqs}) < len(reqs) / 2
    # bursts: more than half of the arrivals in the first quarter second
    assert np.mean(offsets % 1.0 < 0.25) > 0.5

    r = harness.run_cell(root, cell, 11, 1.5, True, require_chip=False,
                         cache=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] == sent
    assert r["metrics"]["visits_per_query"]["value"] > 0
