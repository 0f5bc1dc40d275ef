"""Engine: share of the launched query slots that were padding, in %,
from `AccessStats.padded_queries` over the window's queries plus the
padding (short batches padded up to the query block).  A program without
the counter gives no reading."""

from bench.stages import counter


def read(run):
    pad = counter(run, "padded_queries")
    queries = counter(run, "queries")
    if pad is None or not queries:
        return None
    return pad / (queries + pad) * 100.0
