"""Median latency, scheduled arrival to completion (see Run.latencies_s)."""

import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 50) * 1e3) if lat.size else None
