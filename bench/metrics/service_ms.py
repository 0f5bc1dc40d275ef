"""Engine: mean time from a launch to its last answer."""

import numpy as np


def read(run):
    launches = run.launches()
    if not launches:
        return None
    return float(np.mean([done - t for t, done, _ in launches]) * 1e3)
