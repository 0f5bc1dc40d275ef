"""Autotuned tiling for the fused region-search sweep (DESIGN.md §12).

The fused kernels historically ran with a hardcoded ``block_w=128``.  The
best tile shape actually depends on the schedule: wide pyramid levels
amortize per-step overhead with bigger tiles, narrow tree grids waste
VMEM (and, interpreted, Python kernel-body invocations) on them, and for
some shapes the per-level launch plan beats the fused grid outright.
This module times a small candidate grid of

* ``block_w``        — slot-tile width of the sweep grid,
* ``query_block``    — split the query batch into chunks of this many
                       rows (``None`` = whole batch in one launch),
* ``levels_in_grid`` — the fused single-launch sweep (True) vs the
                       per-level launch baseline (False; float32
                       non-streamed paths only),

on a probe slice of the first real query batch and returns the winner as
a :class:`TileConfig`.  The caller (``repro.index.backends.PallasBackend``)
caches winners in ``BuildArtifacts.tuned`` keyed by :func:`shape_key`, so
every backend sharing the artifacts — ``with_backend`` twins included —
reuses the measurement instead of re-timing.

Timing is wall-clock over the backend's own runner, after one warm-up
call (so jit/lowering cost is excluded), best-of-``iters``.  A candidate
that raises (e.g. a tile shape the runtime rejects) is not timed; it is
returned among the refusals with its first error, which the caller
records in ``BuildArtifacts.tune_refusals``.  The fixed default
``TileConfig()`` is always in the candidate grid, so the tuned pick can
only match or beat it.
"""

from __future__ import annotations

import dataclasses
import time

__all__ = [
    "TileConfig",
    "DEFAULT_BLOCK_WS",
    "AUTO_MIN_WIDTH",
    "shape_key",
    "candidates",
    "tune",
]

# Whole multiples of the chip's 128-lane vreg width: Mosaic refuses a
# narrower tile of the (…, 4, W) level arrays.
DEFAULT_BLOCK_WS = (128, 256, 512)

# autotune="auto" only spends tuning time when the slot grid is at least
# this wide; narrower schedules sweep in microseconds at any tile shape.
AUTO_MIN_WIDTH = 1024

# Probe slice of the first query batch used for timing.
PROBE_QUERIES = 16


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One point of the tiling candidate grid (the default is the
    historical fixed configuration)."""

    block_w: int = 128
    query_block: int | None = None
    levels_in_grid: bool = True


def _bucket(v: int) -> int:
    """Next power of two ≥ v (≥ 1) — coarse enough that e.g. every query
    batch of 65..128 rows shares one cached measurement."""
    return 1 << max(int(v - 1).bit_length(), 0) if v > 1 else 1


def shape_key(width: int, levels: int, n_queries: int, precision: str,
              stream: bool):
    """Cache key of a tuning measurement in ``BuildArtifacts.tuned``.

    Width and query count are bucketed to the next power of two; levels,
    precision and the streaming flag are exact — those change the kernel
    being launched, not just its extent.
    """
    return (_bucket(width), int(levels), _bucket(n_queries), str(precision),
            bool(stream))


def candidates(width: int, n_queries: int, *, precision: str = "float32",
               stream: bool = False, live: bool = False,
               block_ws=DEFAULT_BLOCK_WS):
    """The candidate grid for one shape.  Always contains the fixed
    default :class:`TileConfig`, so tuning never loses to it."""
    bws = [bw for bw in block_ws if bw <= max(_bucket(width), 128)]
    if not bws:
        bws = [128]
    qbs = [None]
    if n_queries > 32:
        qbs.append(32)
    out = []
    for bw in bws:
        for qb in qbs:
            out.append(TileConfig(bw, qb, True))
            # The per-level launch plan only exists for the plain float32
            # sweep (no delta levels, no quantized tiles, no streaming).
            if precision == "float32" and not stream and not live:
                out.append(TileConfig(bw, qb, False))
    default = TileConfig()
    if default not in out:
        out.insert(0, default)
    return out


def tune(make_run, cands, *, iters: int = 2):
    """Time every candidate; returns ``(best_cfg, {cfg: seconds},
    {cfg: first error})``.

    ``make_run(cfg)`` returns a zero-argument callable executing the
    search under that configuration (the caller blocks on the result so
    the measurement covers real work).  One warm-up call per candidate
    excludes jit/lowering cost; the score is the best of ``iters`` timed
    calls.  A candidate that raises is refused and reported with its
    error; if all are, the fixed default wins by fiat.
    """
    timings: dict[TileConfig, float] = {}
    refused: dict[TileConfig, str] = {}
    best = None
    for cfg in cands:
        try:
            fn = make_run(cfg)
            fn()  # warm-up: compile/lower outside the measurement
            t = min(
                _timed(fn) for _ in range(max(iters, 1))
            )
        except Exception as e:  # noqa: BLE001 — reported, not hidden
            refused[cfg] = f"{type(e).__name__}: {e}"
            continue
        timings[cfg] = t
        if best is None or t < timings[best]:
            best = cfg
    if best is None:
        best = TileConfig()
    return best, timings, refused


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
