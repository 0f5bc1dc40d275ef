"""Engine: share of the object test's candidates that were hits, in %,
from `AccessStats.confirm_hits` / `confirm_candidates` (the entries a
pyramid leaves sharing their deepest group, tested against their own
boxes in the hit epilogue).  A program without the counters, or a window
whose launches tested no entry, gives no reading."""

from bench.stages import counter


def read(run):
    cand = counter(run, "confirm_candidates")
    hits = counter(run, "confirm_hits")
    if not cand or hits is None:
        return None
    return hits / cand * 100.0
