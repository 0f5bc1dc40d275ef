"""Built-in query backends of the :class:`SpatialIndex` registry.

Four engines over the same search semantics (DESIGN.md §6):

* ``host``   — the oracle: per-level pointer search over the built tree
               (numpy level sweep for the pyramid, which has no pointers);
* ``lax``    — the whole level sweep as one jit'd ``lax.scan`` (pure XLA,
               no Pallas; runs anywhere JAX does);
* ``pallas`` — the fused single-launch kernel (``kernels.ops.pyramid_scan``);
* ``serve``  — the batching :class:`SpatialServer` (LRU cache, dedupe,
               vmap/pmap fan-out) as a backend adapter.

Every adapter returns ``(hits (Q, n_obj) bool, visits (Q, L) int32,
launches int)`` with bit-identical hits and per-level access counts, so
the façade's :class:`AccessStats` ledger means the same thing everywhere.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import mbr as M
from repro.core.flat import LevelSchedule, confirm_shared
from repro.kernels import ops
from repro.obs import counters as _obs_counters
from repro.obs import trace as _obs_trace

from .registry import register_backend
from .trees import node_children, node_mbr, tree_height

ALL_STRUCTURES = ("mqr", "rtree", "pyramid")


def _overlap_np(a, b):
    """Closed-boundary rectangle intersection, broadcasting.

    Pure indexing/comparison ops, so the same function serves numpy arrays
    (host sweep) and traced jnp arrays (the jitted lax sweep) — ONE copy of
    the boundary semantics every backend's parity depends on."""
    return (
        (a[..., 0] <= b[..., 2])
        & (b[..., 0] <= a[..., 2])
        & (a[..., 1] <= b[..., 3])
        & (b[..., 1] <= a[..., 3])
    )


# ---------------------------------------------------------------------------
# host
# ---------------------------------------------------------------------------


@register_backend(
    "host",
    structures=ALL_STRUCTURES,
    artifact="pointer",
    doc="per-level pointer search (numpy sweep for the pyramid); the oracle",
)
class HostBackend:
    def __init__(self, artifacts):
        self.artifacts = artifacts
        self.tree = artifacts.pointer_tree
        if self.tree is not None:
            self.levels = tree_height(self.tree)
        else:
            self.schedule = artifacts.schedule
            self.levels = self.schedule.levels

    def region(self, queries: np.ndarray):
        with _obs_trace.span("backend.host", queries=queries.shape[0]):
            return self._region(queries)

    def _region(self, queries: np.ndarray):
        if self.tree is None:
            hits, visits = schedule_region_numpy(self.schedule, queries)
            return hits, visits, 0
        nq = queries.shape[0]
        hits = np.zeros((nq, max(self.artifacts.n_objects, 1)), bool)
        visits = np.zeros((nq, self.levels), np.int32)
        for i, q in enumerate(queries):
            qq = np.asarray(q, np.float64)
            stack = [(self.tree.root, 0)]
            while stack:
                node, d = stack.pop()
                if node_mbr(node) is None:
                    continue
                visits[i, d] += 1
                for embr, child, obj in node_children(node):
                    if not M.overlaps(embr, qq):
                        continue
                    if child is not None:
                        stack.append((child, d + 1))
                    else:
                        hits[i, obj] = True
        return hits, visits, 0


def schedule_region_numpy(schedule: LevelSchedule, queries: np.ndarray):
    """Reference level sweep over a :class:`LevelSchedule`, pure numpy.

    Same recurrence as the fused kernel: ``active[l] = active[l-1][parent]
    & overlaps`` (level 0 unconditional at the root slot for tree
    schedules), and the same object test of a pyramid's shared entries.
    Returns ``(hits, visits (Q, L))``.
    """
    queries = np.asarray(queries, np.float32)
    nq = queries.shape[0]
    levels, _, w = schedule.mbr_cm.shape
    mbr = schedule.mbr_cm.transpose(0, 2, 1)  # (L, W, 4)
    acts = np.zeros((levels, nq, w), bool)
    for l in range(levels):
        ov = _overlap_np(mbr[l][None, :, :], queries[:, None, :])
        if l == 0:
            if schedule.root_unconditional:
                act = np.zeros((nq, w), bool)
                act[:, 0] = True
            else:
                act = ov
        else:
            act = ov & acts[l - 1][:, schedule.parent[l]]
        acts[l] = act
    visits = acts.sum(axis=2).T.astype(np.int32)
    entry_act = acts[schedule.obj_level, :, schedule.obj_slot].T  # (Q, E)
    if schedule.test_object_mbr:
        entry_act = entry_act & _overlap_np(
            schedule.obj_mbr[None, :, :], queries[:, None, :]
        )
    elif schedule.n_shared:
        entry_act, confirm = confirm_shared(
            entry_act, queries, schedule.obj_mbr[:schedule.n_shared], xp=np)
        ops.count_confirm(confirm)
    hits = np.zeros((nq, max(schedule.n_objects, 1)), bool)
    np.maximum.at(hits, (slice(None), schedule.obj_id), entry_act)
    return hits, visits


# ---------------------------------------------------------------------------
# lax
# ---------------------------------------------------------------------------


@register_backend(
    "lax",
    structures=ALL_STRUCTURES,
    artifact="schedule",
    doc="whole level sweep as one jit'd lax.scan (pure XLA, no Pallas)",
)
class LaxBackend:
    def __init__(self, artifacts):
        sched = artifacts.schedule
        self._run = _make_lax_sweep(sched)

    def region(self, queries: np.ndarray):
        with _obs_trace.span("backend.lax", queries=queries.shape[0]):
            out = self._run(ops.to_device(queries, jnp.float32))
            hits, visits, *confirm = ops.fetch(
                *(a for a in out if a is not None))
            if confirm:
                ops.count_confirm(confirm[0])
            return hits, visits, 1


def _make_lax_sweep(schedule: LevelSchedule):
    mbr_rm = jnp.asarray(schedule.mbr_cm.transpose(0, 2, 1))  # (L, W, 4)
    parent = jnp.asarray(schedule.parent)
    obj_mbr = jnp.asarray(schedule.obj_mbr)
    obj_level = jnp.asarray(schedule.obj_level)
    obj_slot = jnp.asarray(schedule.obj_slot)
    obj_id = jnp.asarray(schedule.obj_id)
    levels, width, _ = mbr_rm.shape
    root_unconditional = schedule.root_unconditional
    test_object_mbr = schedule.test_object_mbr
    shared_mbr = obj_mbr[:schedule.n_shared] if schedule.n_shared else None
    n_obj = schedule.n_objects

    @jax.jit
    def run(queries):
        nq = queries.shape[0]

        def step(prev, xs):
            mbr_l, parent_l, l = xs
            ov = _overlap_np(mbr_l[None, :, :], queries[:, None, :])  # (Q, W)
            pa = jnp.take(prev, parent_l, axis=1)
            if root_unconditional:
                act0 = jnp.zeros((nq, width), bool).at[:, 0].set(True)
            else:
                act0 = ov
            act = jnp.where(l == 0, act0, pa & ov)
            return act, act

        init = jnp.zeros((nq, width), bool)
        _, acts = jax.lax.scan(
            step, init, (mbr_rm, parent, jnp.arange(levels))
        )  # acts: (L, Q, W)
        visits = jnp.transpose(acts.sum(axis=2, dtype=jnp.int32))
        hit = jnp.transpose(acts[obj_level, :, obj_slot])  # (Q, E)
        confirm = None
        if test_object_mbr:
            hit = hit & _overlap_np(obj_mbr[None, :, :], queries[:, None, :])
        elif shared_mbr is not None:
            hit, confirm = confirm_shared(hit, queries, shared_mbr)
        hits = jnp.zeros((nq, max(n_obj, 1)), jnp.bool_)
        hits = hits.at[:, obj_id].max(hit)
        return hits, visits, confirm

    return run


# ---------------------------------------------------------------------------
# pallas
# ---------------------------------------------------------------------------


def _check_precision(precision: str) -> None:
    if precision not in ("float32", "compact", "compact8"):
        raise ValueError(
            f"unknown precision {precision!r}; expected 'float32', "
            f"'compact' or 'compact8'"
        )


@register_backend(
    "pallas",
    structures=ALL_STRUCTURES,
    artifact="schedule",
    doc="fused single-launch Pallas sweep (kernels.ops.pyramid_scan); "
        "precision='compact' streams conservative uint16 tiles, "
        "'compact8' adds coarse uint8 upper-level tiles; stream=True "
        "double-buffers MBR tiles from HBM; block_w=None autotunes",
)
class PallasBackend:
    """Fused-kernel adapter with autotuned tiling (DESIGN.md §12).

    ``block_w=None`` (the default) leaves the tile width to the
    autotuner: ``autotune="auto"`` times the candidate grid of
    :mod:`repro.kernels.autotune` on the first query batch once the slot
    grid is wide enough to matter, ``"on"`` always does, ``"off"`` (or
    an explicit ``block_w``) pins the fixed configuration.  Winners are
    cached in ``BuildArtifacts.tuned`` keyed by shape, so
    ``with_backend`` twins reuse the measurement.

    ``query_block`` fixes every launch at that many queries: a larger
    batch is split into blocks, and a shorter one is padded up with
    ``flat.NEVER_MBR`` rows (counted in ``padded_queries``) that meet no
    object and are sliced off before anything is counted, so one program
    serves every batch size (DESIGN.md §12).

    The schedule is staged to the device on the first launch (the
    autotune probe or a warm-up) and stays there, with its parent-window
    plans, for the adapter's lifetime; a merge builds a new adapter
    (DESIGN.md §12).
    """

    def __init__(self, artifacts, *, block_w: int | None = None,
                 interpret=None, precision: str = "float32",
                 stream: bool = False, autotune: str = "auto",
                 query_block: int | None = None):
        _check_precision(precision)
        if autotune not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown autotune {autotune!r}; expected 'auto', 'on' or "
                f"'off'"
            )
        if stream and precision == "compact8":
            raise ValueError(
                "stream=True is not supported with precision='compact8' "
                "(the hierarchical sweep is VMEM-resident; DESIGN.md §12)"
            )
        self.precision = precision
        self.schedule = artifacts.schedule
        # Quantized once per BuildArtifacts, shared across backends.
        if precision == "compact":
            self.qschedule = artifacts.quantized
        elif precision == "compact8":
            self.qschedule = artifacts.quantized8
        else:
            self.qschedule = None
        self.block_w = block_w
        self.query_block = query_block
        self.stream = stream
        self.autotune = autotune
        self.interpret = interpret
        # Shape -> TileConfig winners, shared across backends over the
        # same artifacts (restore()'d artifacts start empty).
        self._tuned = getattr(artifacts, "tuned", None)
        if self._tuned is None:
            self._tuned = {}
        self._refusals = getattr(artifacts, "tune_refusals", {})
        self._staged = None  # the schedule on the device, from launch 1

    def _config(self, queries: np.ndarray):
        from repro.kernels.autotune import (
            AUTO_MIN_WIDTH,
            PROBE_QUERIES,
            TileConfig,
            candidates,
            shape_key,
            tune,
        )

        fixed = TileConfig(
            128 if self.block_w is None else self.block_w,
            self.query_block, True,
        )
        if self.autotune == "off" or self.block_w is not None:
            return fixed
        width = self.schedule.width
        if self.autotune == "auto" and width < AUTO_MIN_WIDTH:
            return fixed
        nq = queries.shape[0]
        key = shape_key(
            width, self.schedule.levels, nq, self.precision, self.stream
        )
        cfg = self._tuned.get(key)
        if cfg is None:
            probe = queries[:PROBE_QUERIES]
            cands = candidates(
                width, nq, precision=self.precision, stream=self.stream
            )
            if self.query_block is not None:  # the launch size is fixed
                cands = list(dict.fromkeys(
                    dataclasses.replace(c, query_block=self.query_block)
                    for c in cands))
            cfg, _, refused = tune(
                lambda c: lambda: np.asarray(self._run(probe, c)[0]), cands
            )
            self._tuned[key] = cfg
            for c, err in refused.items():
                self._refusals[(key, c)] = err
        return cfg

    def _run_one(self, queries: np.ndarray, cfg):
        """One launch; returns host ``(hits, visits, launches)``."""
        if not cfg.levels_in_grid:
            # Per-level launch plan — float32 non-streamed only (the
            # candidate grid never proposes it elsewhere); hits and
            # visits are bit-identical to the fused sweep.
            hits, visits, n_launches = ops.per_level_region_search(
                self.schedule, queries, block_w=cfg.block_w
            )
            return hits, visits, n_launches
        if self._staged is None:
            self._staged = ops.stage_schedule(
                self.schedule if self.qschedule is None else self.qschedule,
                self.precision,
            )
        n = queries.shape[0]
        size = max(n, cfg.query_block or 0)
        run = dict(block_w=cfg.block_w, interpret=self.interpret,
                   stream=self.stream, pad_to=size)
        if size > n:
            _obs_trace.add("padded_queries", size - n)
        if self._staged.members() is None:
            out = ops.scan_staged(self._staged, queries, **run)
            hits, visits, *confirm = ops.fetch(
                *(a for a in out if a is not None))
        else:
            hits, visits, confirm = self._run_ids(queries, run)
        if confirm:
            ops.count_confirm(confirm[0][:n])
        return hits[:n], visits[:n], 1

    def _run_ids(self, queries: np.ndarray, run: dict):
        """One launch over a pyramid that returns hit ids where they fit
        its capacities, and the dense rows rebuilt from them on the host
        (DESIGN.md §12); where they do not, the same program's dense
        mask.  Returns host ``(hits, visits, [confirm])``."""
        visits, confirm, offsets, overflow, ids, hits = ops.scan_staged_ids(
            self._staged, queries, **run)
        _obs_trace.add("compact_launches", 1)
        visits, offsets, overflow, ids, *confirm = ops.fetch(
            visits, offsets, overflow, ids,
            *(() if confirm is None else (confirm,)))
        if overflow:
            _obs_trace.add("compact_overflows", 1)
            return ops.fetch(hits)[0], visits, confirm
        n = queries.shape[0]
        with _obs_trace.stage("engine.fetch", "fetch_s"):
            ids = ids[:offsets[n]]
            keep = ids >= 0
            rows = np.repeat(np.arange(n), np.diff(offsets[:n + 1]))
            hits = np.zeros((n, max(self.schedule.n_objects, 1)), bool)
            hits[rows[keep], ids[keep]] = True
        return hits, visits, confirm

    def _run(self, queries: np.ndarray, cfg):
        qb = cfg.query_block
        if qb and queries.shape[0] > qb:
            hs, vs, launches = [], [], 0
            for i in range(0, queries.shape[0], qb):
                h, v, n = self._run_one(queries[i:i + qb], cfg)
                hs.append(h)
                vs.append(v)
                launches += n
            return np.concatenate(hs), np.concatenate(vs), launches
        return self._run_one(queries, cfg)

    def region(self, queries: np.ndarray):
        queries = np.asarray(queries, np.float32)
        with _obs_trace.span("backend.pallas", queries=queries.shape[0],
                             precision=self.precision, stream=self.stream):
            cfg = self._config(queries)
            if _obs_counters.collecting():
                _obs_counters.drain()  # discard autotune-probe emissions
            hits, visits, launches = self._run(queries, cfg)
        if _obs_counters.collecting():
            # The query_block chunking above emits one report per chunk;
            # re-emit them merged, stamped with the tiling actually used
            # (the façade drains this into RegionResult.launch_report).
            report = _obs_counters.merge_reports(_obs_counters.drain())
            if report is not None:
                report.query_block = cfg.query_block
                report.block_w = cfg.block_w
                report.backend = "pallas"
                if report.survivors_per_level is None:
                    report.survivors_per_level = tuple(
                        int(x) for x in visits.sum(axis=0)
                    )
                _obs_counters.emit(report)
        return hits, visits, launches


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


@register_backend(
    "serve",
    structures=ALL_STRUCTURES,
    artifact="schedule",
    doc="batching SpatialServer: LRU cache + dedupe + vmap/pmap fan-out; "
        "precision='compact' serves the quantized tile form",
)
class ServeBackend:
    def __init__(self, artifacts, *, query_block: int = 16,
                 cache_size: int = 4096, block_w: int = 128,
                 interpret=None, precision: str = "float32",
                 ladder=None, max_retries: int = 2, backoff: float = 0.05,
                 fault_plan=None):
        _check_precision(precision)
        # Imported here: launch.spatial_serve itself builds on the index
        # package's kernel API, keep the layers acyclic at import time.
        from repro.launch.spatial_serve import LADDER, SpatialServer

        if precision == "compact":
            quantized = artifacts.quantized
        elif precision == "compact8":
            quantized = artifacts.quantized8
        else:
            quantized = None
        self.server = SpatialServer(
            artifacts.schedule,
            query_block=query_block,
            cache_size=cache_size,
            block_w=block_w,
            interpret=interpret,
            precision=precision,
            quantized=quantized,
            ladder=LADDER if ladder is None else ladder,
            max_retries=max_retries,
            backoff=backoff,
            fault_plan=fault_plan,
        )

    def region(self, queries: np.ndarray):
        with _obs_trace.span("backend.serve", queries=queries.shape[0]):
            before = self.server.stats.kernel_launches
            hits, visits = self.server.search(queries)
            return hits, visits, self.server.stats.kernel_launches - before

    def bind_fault_plan(self, plan) -> None:
        self.server.bind_fault_plan(plan)

    def drain_health(self) -> dict:
        return self.server.drain_health()
