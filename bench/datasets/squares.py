"""Uniform squares: the paper's setting.

``n`` axis-aligned squares of one side, uniform over
``[0, extent]^2``, whose areas sum to ``coverage * extent^2`` (coverage
~1 in the paper and in Spider's uniform boxes), snapped to float32 so the
float64 reference and the float32 device path agree at every box
boundary.
"""

import numpy as np

from bench.loadgen import f32


def squares(n: int, extent: float, coverage: float, rng) -> np.ndarray:
    """(n, 4) float64 of float32 values."""
    side = extent * np.sqrt(coverage / n)
    ll = rng.uniform(0.0, extent - side, (n, 2))
    return f32(np.concatenate([ll, ll + side], axis=1))


def make(config: dict, rng) -> np.ndarray:
    return squares(int(config["n"]), float(config["extent"]),
                   float(config["coverage"]), rng)
