"""Data and request generation from the run's seed.

Everything a run sends is made from ``--seed`` and the cell's
configuration and traffic files, and nothing is read from the program.
Both are found by name, as the metric readers are:

* a configuration's ``dataset`` names ``datasets/<name>.py``, whose
  ``make(config, rng)`` returns the (n, 4) float64 box table;
* a traffic file's ``generator`` names ``generators/<name>.py``, whose
  ``Maker(traffic, config, data)`` has ``make(count, rng, kinds=None)``,
  returning ``count`` requests ``(kind, payload)`` (``kinds``, a list of
  kinds to take in turn, is how the warm-up asks for one kind at a
  time; a generator of one kind may ignore it).  The module may define
  ``arrivals(traffic, rate, seconds, rng)`` for an open loop's offsets;
  without it they are the Poisson gaps of :func:`open_loop_offsets`.

A generator should draw the amount of work (kinds, sizes, gaps) from a
fixed seed and let the run's seed only order and place it, so that two
seeds send the same work and a difference between them is noise.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("region", "point", "count", "knn")
# The multiset of kinds, sizes and gaps comes from this seed alone.
MULTISET_SEED = 20_121_469


def seeded(seed: int):
    """Independent generators for the data, the window's requests and
    the warm-up's requests."""
    ss = np.random.SeedSequence(int(seed) % 2**64)
    return tuple(np.random.default_rng(s) for s in ss.spawn(3))


def f32(a) -> np.ndarray:
    """Snap to float32-representable coordinates (kept in float64)."""
    return np.float64(np.float32(a))


def module(kind: str, name: str, root: str = HERE):
    """``<root>/<kind>/<name>.py``, imported by its path."""
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_data(config: dict, rng, root: str = HERE) -> np.ndarray:
    return module("datasets", config["dataset"], root).make(config, rng)


def request_maker(traffic: dict, config: dict, data: np.ndarray,
                  root: str = HERE):
    return module("generators", traffic["generator"], root).Maker(
        traffic, config, data)


def arrivals(traffic: dict, rate: float, seconds: float, rng,
             root: str = HERE) -> np.ndarray:
    gen = module("generators", traffic["generator"], root)
    if hasattr(gen, "arrivals"):
        return gen.arrivals(traffic, rate, seconds, rng)
    return open_loop_offsets(rate, seconds, rng)


def poisson_arrivals(qps: float, duration: float, *, seed: int = 0
                     ) -> np.ndarray:
    """Arrival offsets (seconds from start) of a Poisson process at
    ``qps`` over ``duration``: exponential inter-arrival gaps."""
    if qps <= 0:
        return np.zeros((0,), np.float64)
    rng = np.random.default_rng(seed)
    # mean count + 4 sigma, then clip to the window
    n = int(qps * duration + 4 * np.sqrt(qps * duration)) + 8
    gaps = rng.exponential(1.0 / qps, size=n)
    t = np.cumsum(gaps)
    return t[t < duration]


def apportion(weights, count: int) -> list:
    """Exactly ``count`` whole shares in proportion to ``weights``
    (largest remainder)."""
    total = float(sum(weights))
    exact = [count * w / total for w in weights]
    whole = [int(x) for x in exact]
    order = sorted(range(len(exact)), key=lambda i: whole[i] - exact[i])
    for i in order[:count - sum(whole)]:
        whole[i] += 1
    return whole


def kind_multiset(mix: dict, count: int) -> list:
    """Exactly ``count`` kinds in the mix's shares."""
    kinds = [k for k in KINDS if mix.get(k, 0) > 0]
    unknown = sorted(set(mix) - set(KINDS))
    if unknown:
        raise ValueError(f"unknown request kinds in mix: {unknown}")
    whole = apportion([mix[k] for k in kinds], count)
    return [k for k, c in zip(kinds, whole) for _ in range(c)]


def open_loop_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Arrival offsets of a Poisson process at ``rate`` over ``seconds``:
    the gaps are one fixed draw, ordered by ``rng``, so every seed sends
    the same number of requests over the same span."""
    fixed = poisson_arrivals(rate, seconds, seed=MULTISET_SEED)
    gaps = np.diff(np.concatenate([[0.0], fixed]))
    return np.cumsum(gaps[rng.permutation(gaps.shape[0])])
