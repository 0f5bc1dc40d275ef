"""95th-percentile latency, scheduled arrival to completion."""

import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 95) * 1e3) if lat.size else None
