"""Front end: mean wait from scheduled arrival to launch."""

import numpy as np


def read(run):
    waits = [t.t_launch - t.t_arrival for t in run.tickets if t.done]
    return float(np.mean(waits) * 1e3) if waits else None
