"""Batched spatial query serving over the fused region-search kernel.

Production shape of the paper's region search (DESIGN.md §3.3): a
:class:`SpatialServer` holds one immutable :class:`repro.core.flat.
LevelSchedule` on device and answers streams of query rectangles with

* an LRU result cache — repeated regions (hot map tiles, dashboard
  refreshes) are answered without touching the device at all;
* query batching — cache misses are deduplicated, padded to fixed-size
  blocks, and dispatched as ONE fused kernel launch per block batch;
* ``vmap`` over query blocks within a device, and ``pmap`` fan-out across
  devices when more than one is attached (single-device falls back to the
  vmapped path transparently).

  PYTHONPATH=src python -m repro.launch.spatial_serve --n 2000 --queries 256

Where this sits in the serving stack (one entry point per layer):

* THIS module is the low-level single-index serving ENGINE — cache,
  dedupe, padding, ladder.  It is what ``backend="serve"`` builds under
  a :class:`repro.index.SpatialIndex`.
* :mod:`repro.serve` is the user-facing serving FRONT END — continuous
  batching of single arrivals, SLO admission control, the multi-tenant
  registry.  New serving features land there, on top of this engine.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time
import warnings
from collections import OrderedDict
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.flat import NEVER_MBR, LevelSchedule
from repro.core.mbr import validate_queries
from repro.kernels import fallback, ops
from repro.obs import trace as _obs_trace

LADDER = ("pallas", "lax", "host")


@dataclasses.dataclass
class ServeStats:
    queries_served: int = 0
    cache_hits: int = 0           # answered from the LRU of a previous call
    dedup_hits: int = 0           # duplicates within one batch, computed once
    batches_dispatched: int = 0
    kernel_launches: int = 0      # one fused launch per dispatched block
    node_accesses: int = 0        # sum of per-level visit counts ("disk accesses")
    retries: int = 0              # failed launches retried on the same rung
    degraded_batches: int = 0     # batches answered below the top rung
    rung_dispatches: dict = dataclasses.field(
        default_factory=lambda: {r: 0 for r in LADDER}
    )
    rung_failures: dict = dataclasses.field(
        default_factory=lambda: {r: 0 for r in LADDER}
    )

    @property
    def cache_hit_rate(self) -> float:
        return self.cache_hits / max(self.queries_served, 1)


class SpatialServer:
    """Serve batched region searches from one level schedule.

    Args:
      schedule: the tree/pyramid level schedule (see ``flat.level_schedule``).
      query_block: queries per kernel launch; misses are padded up to this.
      cache_size: LRU capacity in distinct query rectangles (0 disables).
      block_w: kernel lane-tile width.
      interpret: run the Pallas kernel in interpreter mode (None = auto:
        interpret off TPU, compile on TPU — same policy as ``kernels.ops``).
      precision: ``"float32"`` streams exact tiles; ``"compact"`` streams
        the conservatively quantized uint16 tile form at half the bytes
        per query with an exact confirming pass — hit sets are identical,
        visit counts are the compact sweep's own (DESIGN.md §7).
      quantized: optionally a pre-built ``QuantizedSchedule`` for
        ``precision="compact"`` (quantized here when omitted).
      live: optionally the live-update array bundle
        (``repro.update.AugmentedArrays``, DESIGN.md §8): the server then
        dispatches the LIVE fused sweep — base levels + delta-buffer flat
        levels + tombstone mask — and supports :meth:`rebind` to swap in
        a new mutation epoch's arrays; the LRU is epoch-tagged so entries
        cached under an older epoch are never served after a mutation.
      ladder: health ladder walked when a rung's launch fails (DESIGN.md
        §9).  Each rung answers with the identical sweep semantics —
        ``pallas`` is the fused kernel, ``lax`` the plain-XLA twin,
        ``host`` the numpy twin — so degradation changes latency, never
        answers.
      max_retries: failed launches retried per rung (with exponential
        backoff) before falling to the next rung.
      backoff: base retry sleep in seconds; attempt ``k`` waits
        ``backoff * 2**k``, capped at ``backoff_cap``.
      fault_plan: optional :class:`repro.ft.FaultPlan`; its
        :meth:`~repro.ft.FaultPlan.launch` hook fires before every rung
        dispatch so tests can force launch failures deterministically.
    """

    def __init__(
        self,
        schedule: LevelSchedule,
        *,
        query_block: int = 16,
        cache_size: int = 4096,
        block_w: int = 128,
        interpret: bool | None = None,
        precision: str = "float32",
        quantized=None,
        live=None,
        ladder: Tuple[str, ...] = LADDER,
        max_retries: int = 2,
        backoff: float = 0.05,
        backoff_cap: float = 1.0,
        fault_plan=None,
    ):
        if interpret is None:
            interpret = ops.interpret_default()
        if precision not in ("float32", "compact", "compact8"):
            raise ValueError(f"unknown precision {precision!r}")
        ladder = tuple(ladder)
        bad = [r for r in ladder if r not in LADDER]
        if not ladder or bad:
            raise ValueError(
                f"ladder rungs must be drawn from {LADDER}, got {ladder!r}"
            )
        self.schedule = schedule
        self.precision = precision
        self.query_block = int(query_block)
        self.cache_size = int(cache_size)
        self.ladder = ladder
        self.max_retries = int(max_retries)
        self.backoff = float(backoff)
        self.backoff_cap = float(backoff_cap)
        self.fault_plan = fault_plan
        self._rung_floor = 0   # sticky: index of the lowest healthy rung
        self.stats = ServeStats()
        self._health_mark = (0, 0, {r: 0 for r in LADDER}, {r: 0 for r in LADDER})
        self.epoch = 0
        self._cache: "OrderedDict[bytes, Tuple[int, Tuple[np.ndarray, np.ndarray]]]" = (
            OrderedDict()
        )
        self._n_out = schedule.n_objects
        self._levels_out = schedule.levels
        if live is not None:
            if live.precision != precision:
                raise ValueError(
                    f"live bundle is {live.precision!r}, server asked for "
                    f"{precision!r}"
                )
            self._n_out = live.n_objects
            self._levels_out = live.levels
            self._arrays = tuple(jnp.asarray(a) for a in live.arrays)
            fn = (
                ops.fused_search_compact_live
                if precision == "compact"
                else ops.fused_search_live
            )
            kwargs = dict(block_w=block_w, interpret=interpret, **live.statics)
        elif precision == "compact8":
            # Hierarchical uint8-upper/uint16-lower tile form (DESIGN.md
            # §12); hit sets bit-identical, upper-level bytes halved again.
            # Live mutation normalizes compact8 -> compact upstream (delta
            # levels ride the fine grid), so this branch is base-only.
            qs = quantized
            if qs is None:
                qs = ops.quantize_schedule(
                    schedule, interpret=interpret, upper8=True
                )
            if not qs.hierarchical and schedule.levels > 1:
                raise ValueError(
                    "precision='compact8' needs a hierarchical quantized "
                    "schedule (quantize_schedule(..., upper8=True))"
                )
            split = qs.split
            mbr_q8 = qs.mbr_q8
            inv_cell8 = qs.inv_cell8
            if mbr_q8 is None:  # single-level schedule: degenerate split=0
                mbr_q8 = np.zeros((0, 4, qs.width), np.uint8)
                inv_cell8 = qs.inv_cell
            self._arrays = (
                jnp.asarray(mbr_q8),
                jnp.asarray(qs.mbr_q[split:]),
                jnp.asarray(qs.parent_q),
                jnp.asarray(qs.confirm_mbr),
                jnp.asarray(schedule.obj_level),
                jnp.asarray(schedule.obj_slot),
                jnp.asarray(schedule.obj_id),
                jnp.asarray(qs.origin),
                jnp.asarray(qs.inv_cell),
                jnp.asarray(inv_cell8),
            )
            fn = ops.fused_search_compact8
            kwargs = dict(
                n_objects=schedule.n_objects,
                cells=qs.cells,
                cells8=qs.cells8,
                split=split,
                block_w=block_w,
                root_unconditional=schedule.root_unconditional,
                interpret=interpret,
            )
        elif precision == "compact":
            qs = quantized
            if qs is None:
                qs = ops.quantize_schedule(schedule, interpret=interpret)
            self._arrays = (
                jnp.asarray(qs.mbr_q),
                jnp.asarray(qs.parent_q),
                jnp.asarray(qs.confirm_mbr),
                jnp.asarray(schedule.obj_level),
                jnp.asarray(schedule.obj_slot),
                jnp.asarray(schedule.obj_id),
                jnp.asarray(qs.origin),
                jnp.asarray(qs.inv_cell),
            )
            fn = ops.fused_search_compact
            kwargs = dict(
                n_objects=schedule.n_objects,
                cells=qs.cells,
                block_w=block_w,
                root_unconditional=schedule.root_unconditional,
                interpret=interpret,
            )
        else:
            self._arrays = (
                jnp.asarray(schedule.mbr_cm),
                jnp.asarray(schedule.parent),
                jnp.asarray(schedule.obj_mbr),
                jnp.asarray(schedule.obj_level),
                jnp.asarray(schedule.obj_slot),
                jnp.asarray(schedule.obj_id),
            )
            fn = ops.fused_search
            kwargs = dict(
                n_objects=schedule.n_objects,
                block_w=block_w,
                root_unconditional=schedule.root_unconditional,
                test_object_mbr=schedule.test_object_mbr,
                n_shared=schedule.n_shared,
                interpret=interpret,
            )
        inner = functools.partial(fn, **kwargs)
        # Signature-compatible degradation twins: same statics, no pallas.
        fb_lax, fb_np = fallback.FALLBACKS[(precision, live is not None)]
        self._inner_lax = functools.partial(fb_lax, **kwargs)
        self._inner_np = functools.partial(fb_np, **kwargs)
        self._batch_axes = batch_axes = (0,) + (None,) * len(self._arrays)
        self._vmapped = jax.jit(jax.vmap(inner, in_axes=batch_axes))
        self._vmapped_lax = None   # jit'd lazily, on first lax-rung dispatch
        self._np_arrays = None     # host copies, materialized on first use
        self._pmapped = None
        if jax.device_count() > 1:
            self._pmapped = jax.pmap(
                jax.vmap(inner, in_axes=batch_axes), in_axes=batch_axes
            )

    # ------------------------------------------------------------------
    def rebind(self, arrays, *, epoch: int) -> None:
        """Swap the device-resident schedule arrays for a new mutation
        epoch (live-update servers only; DESIGN.md §8).

        The replacement must be shape-identical — delta contents and the
        alive mask change per mutation, the compiled program does not; a
        merge changes shapes and therefore needs a fresh server.  The
        epoch tag advances so LRU entries cached under older epochs stop
        matching (and are evicted on touch) instead of being served
        stale.
        """
        arrays = tuple(jnp.asarray(a) for a in arrays)
        if len(arrays) != len(self._arrays) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(arrays, self._arrays)
        ):
            raise ValueError(
                "rebind requires shape/dtype-identical arrays; a merge "
                "(base rebuild) needs a new SpatialServer"
            )
        self._arrays = arrays
        self._np_arrays = None
        self.epoch = int(epoch)

    def bind_fault_plan(self, plan) -> None:
        """Attach (or detach, with ``None``) a fault-injection plan."""
        self.fault_plan = plan

    def reset_health(self) -> None:
        """Forget sticky degradation: the next batch starts back at the
        top rung (call after the underlying fault is known fixed)."""
        self._rung_floor = 0

    @property
    def current_rung(self) -> str:
        return self.ladder[min(self._rung_floor, len(self.ladder) - 1)]

    def drain_health(self) -> dict:
        """Return health-ladder counter deltas since the previous drain
        (retries, degraded batches, per-rung dispatches/failures) — the
        façade folds these into ``AccessStats`` per query call."""
        s = self.stats
        m_ret, m_deg, m_disp, m_fail = self._health_mark
        out = {
            "retries": s.retries - m_ret,
            "degraded_batches": s.degraded_batches - m_deg,
            "rung_dispatches": {
                r: s.rung_dispatches.get(r, 0) - m_disp.get(r, 0)
                for r in LADDER
            },
            "rung_failures": {
                r: s.rung_failures.get(r, 0) - m_fail.get(r, 0)
                for r in LADDER
            },
            "rung": self.current_rung,
        }
        self._health_mark = (
            s.retries,
            s.degraded_batches,
            dict(s.rung_dispatches),
            dict(s.rung_failures),
        )
        return out

    # ------------------------------------------------------------------
    def search(self, queries) -> Tuple[np.ndarray, np.ndarray]:
        """Answer (Q, 4) query rectangles.

        Returns ``(hits, visits)`` exactly as :func:`repro.kernels.ops.
        pyramid_scan` would per query — the cache and batching are
        result-transparent.

        The boundary is hardened: NaN/±inf/inverted rectangles raise the
        typed :class:`repro.core.mbr.InvalidQueryError` BEFORE any of them
        can be cached or poison a padded batch's neighbours.

        Validation, keys, the cache lookup, dedupe and padding are the
        ``engine.prepare`` stage; the cache fill and unstacking after the
        fetch are ``engine.finish``.
        """
        with _obs_trace.stage("engine.prepare", "prepare_s"):
            queries = validate_queries(queries, what="served queries")
            nq = queries.shape[0]
            if nq == 0:
                return (
                    np.zeros((0, max(self._n_out, 1)), bool),
                    np.zeros((0, self._levels_out), np.int32),
                )
            self.stats.queries_served += nq

            keys = [queries[i].tobytes() for i in range(nq)]
            fresh: dict = {}   # results computed for THIS call; immune to LRU
            miss_rows: list[np.ndarray] = []
            for i, k in enumerate(keys):
                if k in fresh:  # duplicate within this batch: computed once
                    self.stats.dedup_hits += 1
                elif k in self._cache:
                    tag, value = self._cache[k]
                    if tag == self.epoch:
                        fresh[k] = value
                        self._cache.move_to_end(k)
                        self.stats.cache_hits += 1
                    else:
                        # cached under an older mutation epoch: stale — drop
                        # and recompute (epoch-tagged invalidation, §8)
                        del self._cache[k]
                        fresh[k] = None
                        miss_rows.append(queries[i])
                else:
                    fresh[k] = None  # placeholder, filled after dispatch
                    miss_rows.append(queries[i])

        if miss_rows:
            block_hits, block_visits = self._dispatch(np.stack(miss_rows))
        with _obs_trace.stage("engine.finish", "finish_s"):
            if miss_rows:
                j = 0
                for k, v in fresh.items():
                    if v is None:
                        fresh[k] = (block_hits[j], block_visits[j])
                        self._put(k, fresh[k])
                        j += 1

            hits = np.stack([fresh[k][0] for k in keys])
            visits = np.stack([fresh[k][1] for k in keys])
        return hits, visits

    # ------------------------------------------------------------------
    def _dispatch(self, miss: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        qb = self.query_block
        n = miss.shape[0]
        with _obs_trace.stage("engine.prepare", "prepare_s"):
            pad = (-n) % qb
            if pad:
                # pad with never-overlapping null queries (results discarded)
                miss = np.concatenate(
                    [miss, np.broadcast_to(NEVER_MBR, (pad, 4))], axis=0
                )
            blocks = miss.reshape(-1, qb, 4)
        if pad:
            _obs_trace.add("padded_queries", pad)
        hits, visits, launches = self._run_ladder(blocks)
        self.stats.batches_dispatched += 1
        self.stats.kernel_launches += launches
        self.stats.node_accesses += int(visits[:n].sum())
        return hits[:n], visits[:n]

    def _run_ladder(
        self, blocks: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Dispatch one padded block batch down the health ladder.

        Starts at the sticky rung floor (a rung that exhausted its retry
        budget earlier stays skipped until :meth:`reset_health`), retries
        each rung ``max_retries`` times with bounded exponential backoff,
        then degrades to the next rung.  A simulated SIGKILL
        (``repro.ft.KillPoint``) derives from ``BaseException`` so it is
        NOT absorbed as a rung failure.
        """
        last_exc: Exception | None = None
        start = min(self._rung_floor, len(self.ladder) - 1)
        for ri in range(start, len(self.ladder)):
            rung = self.ladder[ri]
            for attempt in range(self.max_retries + 1):
                try:
                    with _obs_trace.span("serve.rung", rung=rung,
                                         attempt=attempt,
                                         blocks=blocks.shape[0]):
                        if self.fault_plan is not None:
                            self.fault_plan.launch(rung)
                        out = self._dispatch_rung(rung, blocks)
                except Exception as exc:
                    last_exc = exc
                    self.stats.rung_failures[rung] += 1
                    _obs_trace.instant("serve.rung_failure", rung=rung,
                                       attempt=attempt,
                                       error=type(exc).__name__)
                    if attempt < self.max_retries:
                        self.stats.retries += 1
                        if self.backoff > 0:
                            time.sleep(
                                min(self.backoff * 2**attempt, self.backoff_cap)
                            )
                    continue
                self.stats.rung_dispatches[rung] += 1
                if ri > 0:
                    self.stats.degraded_batches += 1
                return out
            # Retry budget exhausted: degrade, and stay degraded (sticky
            # floor) so subsequent batches skip the broken rung.
            if ri + 1 < len(self.ladder):
                self._rung_floor = max(self._rung_floor, ri + 1)
                _obs_trace.instant(
                    "serve.degrade",
                    **{"from": rung, "to": self.ladder[ri + 1],
                       "failures": self.max_retries + 1},
                )
                warnings.warn(
                    f"SpatialServer: rung {rung!r} failed "
                    f"{self.max_retries + 1}x ({last_exc!r}); degrading to "
                    f"{self.ladder[ri + 1]!r}",
                    RuntimeWarning,
                    stacklevel=3,
                )
        raise RuntimeError(
            f"SpatialServer: every ladder rung {self.ladder!r} failed"
        ) from last_exc

    def _dispatch_rung(
        self, rung: str, blocks: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """One attempt on one rung; returns flat (hits, visits, launches)."""
        nb, qb, _ = blocks.shape
        if rung in ("pallas", "lax"):
            with _obs_trace.stage("engine.prepare", "prepare_s"):
                n_dev = jax.device_count()
                if rung == "lax":
                    if self._vmapped_lax is None:
                        self._vmapped_lax = jax.jit(
                            jax.vmap(self._inner_lax,
                                     in_axes=self._batch_axes)
                        )
                    out = self._vmapped_lax(
                        ops.to_device(blocks), *self._arrays
                    )
                elif self._pmapped is not None and nb % n_dev == 0:
                    sharded = blocks.reshape(n_dev, nb // n_dev, qb, 4)
                    out = self._pmapped(
                        ops.to_device(sharded), *self._arrays
                    )
                else:
                    out = self._vmapped(
                        ops.to_device(blocks), *self._arrays
                    )
            # the float32 entries return a pyramid's object-test sums third
            hits, visits, *confirm = ops.fetch(
                *(a for a in out if a is not None))
            if confirm:
                ops.count_confirm(confirm[0])
            return hits.reshape(nb * qb, -1), visits.reshape(nb * qb, -1), nb
        # host: pure numpy, zero device launches
        if self._np_arrays is None:
            self._np_arrays = tuple(np.asarray(a) for a in self._arrays)
        hits, visits, *confirm = self._inner_np(
            blocks.reshape(nb * qb, 4), *self._np_arrays
        )
        if confirm and confirm[0] is not None:
            ops.count_confirm(confirm[0])
        return np.asarray(hits), np.asarray(visits), 0

    def _put(self, key: bytes, value) -> None:
        if self.cache_size <= 0:  # caching disabled
            return
        self._cache[key] = (self.epoch, value)
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)


# ---------------------------------------------------------------------------


def main():
    from repro.core import datasets, flat, mqrtree
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--repeat-frac", type=float, default=0.5,
                    help="fraction of queries drawn from a small hot set")
    ap.add_argument("--query-block", type=int, default=16)
    args = ap.parse_args()

    data = datasets.uniform_squares(args.n, seed=0)
    tree = mqrtree.build(data)
    sched = flat.level_schedule(flat.flatten(tree))
    server = SpatialServer(sched, query_block=args.query_block)

    rng = np.random.default_rng(0)
    cold = datasets.region_queries(data, args.queries, seed=1)
    hot = datasets.region_queries(data, 8, seed=2)
    mask = rng.random(args.queries) < args.repeat_frac
    stream = np.where(mask[:, None], hot[rng.integers(0, 8, args.queries)], cold)

    t0 = time.time()
    chunks = [
        server.search(stream[i : i + args.query_block])
        for i in range(0, args.queries, args.query_block)
    ]
    hits = np.concatenate([h for h, _ in chunks])
    dt = time.time() - t0
    s = server.stats
    print(
        f"[spatial-serve] {args.queries} queries in {dt:.3f}s "
        f"({args.queries / dt:.0f} q/s) | cache hit rate "
        f"{s.cache_hit_rate:.0%} | {s.kernel_launches} fused launches | "
        f"{s.node_accesses} node accesses | "
        f"avg {hits.sum(1).mean():.1f} objects/query"
    )


if __name__ == "__main__":
    main()
