"""Spider's ``bit`` distribution as boxes: a skewed, duplicate-heavy layer.

Spider (Katiyar, Vu, Eldawy et al., "SpiderWeb: A Spatial Data Generator
on the Web", SIGSPATIAL 2020) draws each coordinate of a ``bit`` point as
a sum of ``digits`` bits, bit ``i`` worth ``2^-i`` and set with
``probability``.  Points therefore fall on a ``2^digits`` x ``2^digits``
lattice crowded toward one corner, many objects to a lattice point, as
addresses in one building or shops in one mall are.

Each object is a box of width and height uniform in ``[0, max_side]``,
centred on its point.  The lattice is scaled into ``[max_side / 2,
extent - max_side / 2]`` so that no box leaves the extent (clipping would
move the centroids of the edge rows apart).  Centres and half sides are
snapped to multiples of the float32 step at the extent's scale, so every
coordinate is float32-exact and the boxes of one lattice point keep one
float32 centroid, as they have one point.
"""

import math

import numpy as np


def bit_boxes(n: int, extent: float, digits: int, probability: float,
              max_side: float, rng) -> np.ndarray:
    """(n, 4) float64 of float32 values."""
    lattice = np.zeros((n, 2), np.int64)
    for i in range(digits):  # the most significant bit first
        lattice |= (rng.random((n, 2)) < probability).astype(np.int64) << (
            digits - 1 - i)
    # Multiples of `step` below the extent's power of two are float32
    # values, and so are their sums and differences used here.
    step = 2.0 ** (math.ceil(math.log2(extent)) - 24)
    centre = max_side / 2 + lattice / (2 ** digits - 1) * (extent - max_side)
    centre = np.round(centre / step) * step
    half = np.round(rng.uniform(0.0, max_side, (n, 2)) / 2 / step) * step
    return np.concatenate([centre - half, centre + half], axis=1)


def make(config: dict, rng) -> np.ndarray:
    return bit_boxes(int(config["n"]), float(config["extent"]),
                     int(config["digits"]), float(config["probability"]),
                     float(config["max_side"]), rng)
