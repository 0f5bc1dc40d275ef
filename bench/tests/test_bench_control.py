"""The comparison that decides ``correct`` refuses what it must.

* the control, the reference computed in bfloat16 in the program's
  place, comes out not correct for every cell;
* a run on the CPU whose timed path is broken underneath (an answer
  altered where the program produces it) comes out not correct;
* the reference itself agrees with a brute-force loop.
"""

import numpy as np
import pytest

from bench import control, harness, loadgen, reference
from bench.tests.test_bench_cells import CELLS, ROOT, small


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_refused(cell):
    checks = control.control_checks(ROOT, cell, 7, 4.0, small(cell))
    assert any(v > lim for _, v, lim in checks), checks


def _flip_one_hit(monkeypatch):
    from repro.index.api import SpatialIndex

    real = SpatialIndex.region

    def region(self, queries):
        res = real(self, queries)
        hits = np.array(res.hits)
        hits[0, 0] = ~hits[0, 0]
        object.__setattr__(res, "hits", hits)
        return res

    monkeypatch.setattr(SpatialIndex, "region", region)


def _shift_one_neighbour(monkeypatch):
    from repro.index.api import SpatialIndex

    real = SpatialIndex.knn

    def knn(self, points, k):
        res = real(self, points, k)
        ids = np.array(res.ids)
        ids[0, -1] = (ids[0, -1] + 1) % self.n_objects
        if ids[0, -1] in ids[0, :-1]:
            ids[0, -1] = (ids[0, -1] + 7) % self.n_objects
        object.__setattr__(res, "ids", ids)
        return res

    monkeypatch.setattr(SpatialIndex, "knn", knn)


FAULTS = [("paper-mqr-30k.nearest", _flip_one_hit),
          ("paper-mqr-30k.nearest", _shift_one_neighbour),
          ("map-pyramid-4m.render", _flip_one_hit)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_altered_answer_is_refused(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = harness.run_cell(ROOT, cell, 3, 1.5, False, require_chip=False,
                         cache=False, overrides=small(cell))
    assert not r["correct"], r["checks"]


def test_reference_matches_a_plain_loop():
    rng = np.random.default_rng(0)
    data = loadgen.module("datasets", "squares").squares(500, 100.0, 1.0,
                                                       rng)
    ref = reference.Reference(data)
    for _ in range(50):
        c = rng.uniform(0, 100, 2)
        h = rng.uniform(0, 10)
        q = np.float32([c[0] - h, c[1] - h, c[0] + h, c[1] + h])
        want = [i for i, m in enumerate(data)
                if m[0] <= q[2] and q[0] <= m[2] and m[1] <= q[3]
                and q[1] <= m[3]]
        assert ref.overlap_ids(q.astype(np.float64)).tolist() == want
        p = q[:2]
        ids, d = ref.knn(p, 5)
        dist = [np.hypot(max(m[0] - p[0], 0, p[0] - m[2]),
                         max(m[1] - p[1], 0, p[1] - m[3])) for m in data]
        order = sorted(range(len(data)), key=lambda i: (dist[i], i))[:5]
        assert ids.tolist() == order
        assert np.allclose(d, [dist[i] for i in order])


def test_kind_multiset_is_exact():
    kinds = loadgen.kind_multiset({"region": 0.7, "point": 0.2,
                                   "count": 0.1}, 33)
    assert len(kinds) == 33
    assert (kinds.count("region"), kinds.count("point"),
            kinds.count("count")) == (23, 7, 3)
