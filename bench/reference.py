"""The plain reference and the comparison that decides ``correct``.

The reference shares no code with the program.  It answers the same
requests over the same objects with closed-boundary overlap (region,
point, count) and Euclidean point-to-box distance (kNN), in float64 over
the float32-snapped coordinates that both sides receive, so its overlap
tests are exact.  ``Reference(..., precision="bfloat16")`` computes the
same in bfloat16, one step below the float32 that the configurations
state: that is the control, which the comparison must refuse.
"""

from __future__ import annotations

import numpy as np

PRECISIONS = ("float64", "bfloat16")


def _round(a, precision: str) -> np.ndarray:
    a = np.asarray(a, np.float64)
    if precision == "float64":
        return a
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


class Reference:
    """Brute-force answers over an (n, 4) box table."""

    def __init__(self, mbrs: np.ndarray, precision: str = "float64"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.mbrs = _round(mbrs, precision)
        self.order = np.argsort(self.mbrs[:, 0], kind="stable")
        self.lx = self.mbrs[self.order, 0]
        self.wmax = float((self.mbrs[:, 2] - self.mbrs[:, 0]).max())

    def rect(self, kind: str, payload) -> np.ndarray:
        p = _round(payload, self.precision).reshape(-1)
        return np.concatenate([p, p]) if kind == "point" else p

    def overlap_ids(self, q: np.ndarray) -> np.ndarray:
        """Sorted ids of every box that overlaps ``q`` (closed bounds).
        Candidates are the boxes whose low x lies in
        ``[q.lx - widest box, q.hx]``; the pad only widens that set."""
        lo = np.searchsorted(self.lx, q[0] - self.wmax - 1e-3, "left")
        hi = np.searchsorted(self.lx, q[2], "right")
        cand = self.order[lo:hi]
        m = self.mbrs[cand]
        ok = ((m[:, 0] <= q[2]) & (q[0] <= m[:, 2])
              & (m[:, 1] <= q[3]) & (q[1] <= m[:, 3]))
        return np.sort(cand[ok])

    def knn(self, point, k: int):
        """``(ids, dists)`` of the ``k`` nearest boxes, nearest first,
        lowest id first among equal distances."""
        p = _round(point, self.precision).reshape(2)
        m = self.mbrs
        dx = np.maximum(np.maximum(m[:, 0] - p[0], p[0] - m[:, 2]), 0.0)
        dy = np.maximum(np.maximum(m[:, 1] - p[1], p[1] - m[:, 3]), 0.0)
        d = _round(np.sqrt(dx * dx + dy * dy), self.precision)
        part = np.argpartition(d, k - 1)[:k]
        kth = d[part].max()
        cand = np.nonzero(d <= kth)[0]
        ids = cand[np.lexsort((cand, d[cand]))][:k]
        return ids, d[ids]

    def distances(self, point, ids) -> np.ndarray:
        p = np.asarray(point, np.float64).reshape(2)
        m = self.mbrs[np.asarray(ids)]
        dx = np.maximum(np.maximum(m[:, 0] - p[0], p[0] - m[:, 2]), 0.0)
        dy = np.maximum(np.maximum(m[:, 1] - p[1], p[1] - m[:, 3]), 0.0)
        return np.sqrt(dx * dx + dy * dy)

    def answer(self, kind: str, payload, k=None):
        """The answer in the front end's own form: sorted hit ids for
        region/point, an int for count, ``(ids, dists)`` for knn."""
        if kind == "knn":
            return self.knn(payload, k)
        ids = self.overlap_ids(self.rect(kind, payload))
        return int(ids.shape[0]) if kind == "count" else ids


def hit_ids(answer, n: int):
    """Sorted ids of a region/point answer, or None when it marks an id
    outside ``[0, n)``."""
    hits = np.asarray(answer.hits)
    if hits[n:].any():
        return None
    return np.nonzero(hits[:n])[0]


NUMBERS = ("unanswered", "hits_wrong", "counts_wrong", "knn_bad_ids",
           "knn_rank_dist_err", "knn_id_dist_err")


def compare(requests, answers, ref: Reference, k, limits: dict):
    """Compare every answer with the reference.

    ``requests`` are ``(kind, payload)``, ``answers`` the program's
    answers (None where none came).  Returns ``[(name, value, limit)]``
    for the numbers this mix has:

    * ``unanswered``: requests with no answer;
    * ``hits_wrong``: region/point hit sets that differ from the exact
      overlap;
    * ``counts_wrong``: counts that differ;
    * ``knn_bad_ids``: kNN answers with a repeated or unknown id;
    * ``knn_rank_dist_err``: widest gap, at any rank, between the
      distance the program reports and the reference's distance at that
      rank (so ties may be broken either way);
    * ``knn_id_dist_err``: widest gap between the distance the program
      reports for an id and that id's true distance.
    """
    n = ref.mbrs.shape[0]
    out = {"unanswered": 0}
    kinds = {kind for kind, _ in requests}
    if kinds & {"region", "point"}:
        out["hits_wrong"] = 0
    if "count" in kinds:
        out["counts_wrong"] = 0
    if "knn" in kinds:
        out.update(knn_bad_ids=0, knn_rank_dist_err=0.0, knn_id_dist_err=0.0)
    for (kind, payload), got in zip(requests, answers):
        if got is None:
            out["unanswered"] += 1
            continue
        want = ref.answer(kind, payload, k)
        if kind == "count":
            out["counts_wrong"] += int(int(got) != want)
        elif kind == "knn":
            ids = np.asarray(got[0]).astype(np.int64)
            dists = np.asarray(got[1], np.float64)
            if (ids.shape != want[0].shape or len(set(ids.tolist())) != ids.size
                    or ids.min() < 0 or ids.max() >= n):
                out["knn_bad_ids"] += 1
                continue
            out["knn_rank_dist_err"] = max(
                out["knn_rank_dist_err"], float(np.abs(dists - want[1]).max()))
            out["knn_id_dist_err"] = max(
                out["knn_id_dist_err"],
                float(np.abs(dists - ref.distances(payload, ids)).max()))
        else:
            got_ids = hit_ids(got, n)
            out["hits_wrong"] += int(
                got_ids is None or not np.array_equal(got_ids, want))
    return [(name, out[name], float(limits[name]))
            for name in NUMBERS if name in out]


def control_answers(requests, ref_low: Reference, k, n: int):
    """The control's answers, in the program's form, so that
    :func:`compare` judges them exactly as it judges the program's."""
    out = []
    for kind, payload in requests:
        a = ref_low.answer(kind, payload, k)
        if kind in ("region", "point"):
            hits = np.zeros((n,), bool)
            hits[a] = True
            a = _Hits(hits)
        out.append(a)
    return out


class _Hits:
    def __init__(self, hits):
        self.hits = hits
