"""The general request generator: a new mix of these parameters is a
traffic file alone.

A request is ``(kind, payload)``: a (4,) float32 viewport for
``region``/``count``, a (2,) float32 point for ``point``/``knn``.
Parameters of a traffic file:

* ``mix``: the share of each kind;
* ``objects_per_view``: ``[lo, hi]``, the objects a viewport holds on
  average, log-uniform between them.  Viewports are centred on a uniform
  point inside a uniformly drawn object; points lie inside a drawn
  object; kNN points are uniform over the extent (reverse geocoding);
* ``hot`` (optional): ``{"items": H, "zipf": s, "move_every": M}``: every
  M requests, H distinct requests are placed anew and sent with Zipf(s)
  frequencies, so that requests repeat.  Without it no two requests
  repeat, and no result cache answers one;
* ``burst`` (optional): ``{"period_s": P, "on_s": B, "factor": F}``: in
  an open loop, arrivals come F times as fast during the first B seconds
  of every P as in the rest, at the same mean rate.

The kinds, viewport sizes, repeat counts and arrival gaps are one
multiset drawn from a fixed seed; the run's generator only orders it and
places it on the data, so every seed sends the same work.
"""

import numpy as np

from bench.loadgen import MULTISET_SEED, apportion, kind_multiset, \
    open_loop_offsets


class Maker:
    def __init__(self, traffic: dict, config: dict, data: np.ndarray):
        self.traffic = traffic
        self.data = data
        self.extent = float(config["extent"])
        n = data.shape[0]
        # one object's side
        self.side = self.extent * np.sqrt(float(config["coverage"]) / n)
        self.n = n

    def _view_side(self, m):
        """Viewport side whose expected overlap count is ``m`` objects:
        an object overlaps when its lower-left corner lies in a square
        of side ``view + side``."""
        return np.maximum(self.extent * np.sqrt(m / self.n) - self.side, 0.0)

    def make(self, count: int, rng, kinds=None) -> list:
        """``count`` requests; ``kinds`` replaces the mix by one list of
        kinds, taken in turn, with no repeats."""
        hot = self.traffic.get("hot")
        if hot is None or kinds is not None:
            return self._distinct(count, rng, kinds, shuffle=True)
        items, every = int(hot["items"]), int(hot["move_every"])
        weights = 1.0 / np.arange(1, items + 1) ** float(hot["zipf"])
        out = []
        for start in range(0, count, every):
            m = min(every, count - start)
            # the most frequent item is the first of the fixed multiset
            placed = self._distinct(items, rng, None, shuffle=False)
            epoch = [r for r, c in zip(placed, apportion(weights, m))
                     for _ in range(c)]
            out += [epoch[i] for i in rng.permutation(m)]
        return out

    def _distinct(self, count, rng, kinds, shuffle):
        fixed = np.random.default_rng(MULTISET_SEED)
        if kinds is None:
            kinds = kind_multiset(self.traffic["mix"], count)
        else:
            kinds = [kinds[i % len(kinds)] for i in range(count)]
        lo, hi = self.traffic["objects_per_view"]
        views = np.exp(fixed.uniform(np.log(lo), np.log(hi), count))
        if shuffle:
            perm = rng.permutation(count)
            kinds = [kinds[i] for i in perm]
            views = views[rng.permutation(count)]
        obj = self.data[rng.integers(0, self.n, count)]
        u = rng.uniform(0.0, 1.0, (count, 2))
        cx = obj[:, 0] + u[:, 0] * (obj[:, 2] - obj[:, 0])
        cy = obj[:, 1] + u[:, 1] * (obj[:, 3] - obj[:, 1])
        half = self._view_side(views) / 2
        anywhere = rng.uniform(0.0, self.extent, (count, 2))
        out = []
        for i, kind in enumerate(kinds):
            if kind == "knn":
                out.append((kind, anywhere[i].astype(np.float32)))
            elif kind == "point":
                out.append((kind, np.array([cx[i], cy[i]], np.float32)))
            else:
                out.append((kind, np.array(
                    [cx[i] - half[i], cy[i] - half[i],
                     cx[i] + half[i], cy[i] + half[i]], np.float32)))
        return out


def arrivals(traffic: dict, rate: float, seconds: float, rng) -> np.ndarray:
    """Open-loop arrival offsets: the Poisson gaps of
    :func:`open_loop_offsets`, their clock warped by ``burst``."""
    t = open_loop_offsets(rate, seconds, rng)
    burst = traffic.get("burst")
    if burst is None:
        return t
    period, on = float(burst["period_s"]), float(burst["on_s"])
    factor = float(burst["factor"])
    if not (0 < on < period and factor > 0):
        raise ValueError(f"burst needs 0 < on_s < period_s, factor > 0: "
                         f"{burst}")
    edges = np.unique(np.concatenate([np.arange(0.0, seconds, period),
                                      np.arange(on, seconds, period),
                                      [seconds]]))
    rel = np.where(edges[:-1] % period < on, factor, 1.0)
    # expected arrivals by each edge, scaled so the window's mean is kept
    cum = np.concatenate([[0.0], np.cumsum(rel * np.diff(edges))])
    cum *= seconds / cum[-1]
    return np.interp(t, cum, edges)
