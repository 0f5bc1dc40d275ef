"""`SpatialIndex` — the one façade over every tree × backend path.

The paper's contract is a single access method: build an index over MBRs,
run a region search, count the disk accesses.  The repro grew four entry
points (pointer trees, the levelized ``lax`` sweep, the fused Pallas
kernel, the batching server) and three build paths; this module folds them
back into one config-driven surface (DESIGN.md §6):

    idx = SpatialIndex.build(mbrs, structure="mqr", backend="pallas")
    res = idx.region(queries)        # RegionResult(hits, visits_per_level)
    res = idx.point(points)          # degenerate-rectangle fast path
    cnt = idx.count(queries)         # hits per query, no mask materialized
    knn = idx.knn(points, k=8)       # k-NN as a first-class query

``structure`` picks the build path (``mqr`` | ``rtree`` | ``pyramid``),
``backend`` the query engine (``host`` | ``lax`` | ``pallas`` | ``serve``)
via the registry in :mod:`repro.index.registry`.  Every backend reports
the paper's disk-access accounting through the same :class:`AccessStats`
shape, and every advertised (structure × backend) pair returns bit-identical
hits and per-level access counts (tests/test_index_api.py).

Two orthogonal throughput options (DESIGN.md §7): ``build="device"`` runs
the pyramid's bulk fixed point on-accelerator, emitting the
``LevelSchedule`` in one launch (no host pointer tree — and
:meth:`SpatialIndex.extend` makes batch insertion one more such launch);
``precision="compact"`` streams conservatively quantized uint16 MBR tiles
through the fused sweep at half the bytes/query, with an exact float32
confirming pass keeping hit sets bit-identical.

Online mutation (DESIGN.md §8): :meth:`SpatialIndex.insert` /
:meth:`delete` / :meth:`flush` route through the live-update subsystem
(:mod:`repro.update`) — inserts land in a device-resident delta buffer
swept by the same fused launch, deletes tombstone ids masked in the scan
epilogue, and a merge policy decides when to compact into a fresh base
build.  Object ids are global and append-only, so hit masks stay
comparable (and bit-identical to the host mqr-insertion oracle) across
mutations and merges.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import bulk, mqrtree, rtree
from repro.core.flat import FlatTree, LevelSchedule, flatten, level_schedule, pyramid_schedule
from repro.core.mbr import InvalidQueryError as InvalidQueryError  # noqa: F401 (re-export)
from repro.core.mbr import validate_mbrs
from repro.core.mbr import validate_queries as validate_queries  # noqa: F401 (re-export)
from repro.obs import counters as _obs_counters
from repro.obs import trace as _obs_trace

from . import knn as _knn
from .registry import BackendSpec, get_backend

STRUCTURES = ("mqr", "rtree", "pyramid")

# Build-time options; everything else in **opts goes to the backend factory.
_BUILD_OPTS = ("levels", "max_entries", "build", "order")
# Live-update / durability options (structure-agnostic, façade-consumed).
_UPDATE_OPTS = ("capacity", "merge", "admission", "fault_plan")

# Admission policies for mutations that cannot be buffered (DESIGN.md §9).
ADMISSION_MODES = ("merge", "shed")


# ---------------------------------------------------------------------------
# Results and the shared access-accounting protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RegionResult:
    """Result of a batched region (or point) search.

    hits:             (Q, id_space) bool object-overlap mask — columns are
                      GLOBAL object ids (equal to build positions until
                      live updates begin; append-only afterwards, §8).
    visits_per_level: (Q, L) int32 — node accesses by tree level, the
                      paper's "disk accesses" broken down by depth.  Every
                      backend reports the identical numbers (DESIGN.md §6).
                      Once live updates begin, columns past ``base_levels``
                      are the delta buffer's flat-scan accesses.
    base_levels:      levels belonging to the frozen base build; None for
                      an index with no live-update state.
    launch_report:    merged :class:`repro.obs.LaunchReport` byte/tile
                      ledger for this batch's kernel launches — populated
                      only while ``repro.obs.collect_launch_reports(True)``
                      is armed and the backend path runs eagerly
                      (DESIGN.md §13); None otherwise.
    """

    hits: np.ndarray
    visits_per_level: np.ndarray
    base_levels: Optional[int] = None
    launch_report: Optional[object] = None

    @property
    def visits(self) -> np.ndarray:
        """(Q,) total accesses per query."""
        return self.visits_per_level.sum(axis=1)

    @property
    def counts(self) -> np.ndarray:
        """(Q,) number of objects found per query."""
        return self.hits.sum(axis=1)

    def ids(self, i: int) -> np.ndarray:
        """Object ids found by query ``i`` (ascending)."""
        return np.nonzero(self.hits[i])[0]

    @property
    def delta_visits(self) -> np.ndarray:
        """(Q,) delta-buffer accesses per query (all zero when the index
        has no live-update state)."""
        if self.base_levels is None:
            return np.zeros((self.visits_per_level.shape[0],), np.int64)
        return self.visits_per_level[:, self.base_levels:].sum(
            axis=1, dtype=np.int64
        )


@dataclasses.dataclass(frozen=True)
class KNNResult:
    """Result of a batched k-nearest-neighbour query.

    ids:    (Q, k) int32 object ids, nearest first.
    dists:  (Q, k) float32 Euclidean MBR min-distances, ascending.
    visits: (Q,) int64 node accesses spent answering each query (for the
            device path: summed over every expanding-radius round).
    """

    ids: np.ndarray
    dists: np.ndarray
    visits: np.ndarray


@dataclasses.dataclass
class AccessStats:
    """The paper's disk-access accounting, identical across backends.

    One instance accumulates over the lifetime of a :class:`SpatialIndex`;
    backends feed it through :meth:`record` so the ledger has the same
    meaning whether the query ran on host pointers, the ``lax`` sweep, the
    fused Pallas kernel, or the batching server.
    """

    queries: int = 0
    node_accesses: int = 0
    launches: int = 0        # device dispatches (0 for the host backend)
    knn_queries: int = 0
    knn_rounds: int = 0      # expanding-radius region rounds issued
    joins: int = 0           # tree-vs-tree join calls (DESIGN.md §10)
    # live-update ledger (DESIGN.md §8)
    inserts: int = 0
    deletes: int = 0
    flushes: int = 0         # merges (manual, policy, or overflow)
    delta_accesses: int = 0  # node_accesses spent on delta-buffer levels
    # durability / degradation ledger (DESIGN.md §9)
    launch_failures: int = 0   # rung dispatch attempts that raised
    retries: int = 0           # same-rung retries after a failure
    degraded_batches: int = 0  # batches answered below the top rung
    shed_mutations: int = 0    # objects dropped by admission="shed"
    queued_mutations: int = 0  # objects parked by DurableIndex queueing
    rung_dispatches: dict = dataclasses.field(default_factory=dict)
    # serving-front-end ledger (DESIGN.md §11)
    shed_queries: int = 0      # requests dropped by SLO admission control
    queued_queries: int = 0    # requests parked past max_queue (best-effort)
    # kernel byte/tile ledger (DESIGN.md §13); accumulates only while
    # repro.obs.collect_launch_reports(True) is armed
    bytes_streamed: float = 0.0   # mbr+parent tile HBM traffic
    mask_bytes: float = 0.0       # streamed-sweep survivor-window traffic
    tiles_fetched: int = 0
    tiles_skipped: int = 0        # dead-window DMA skips (streamed sweep)
    launch_reports: int = 0       # batches with a ledger attached
    # served-launch stages (DESIGN.md §13), always on: seconds in each
    # stage span and bytes copied between host and device
    prepare_s: float = 0.0        # host work before the dispatch
    wait_s: float = 0.0           # blocked until the outputs are ready
    fetch_s: float = 0.0          # device-to-host copies of the outputs
    finish_s: float = 0.0         # host work after the fetch
    h2d_bytes: int = 0            # host arrays staged to the device
    schedule_stagings: int = 0    # schedules copied whole to the device
    d2h_bytes: int = 0            # outputs copied back
    padded_queries: int = 0       # padding rows launched with short batches
    # the object test of a pyramid's shared entries (DESIGN.md §3.1):
    # their candidates before it and hits after it, over every query
    confirm_candidates: int = 0
    confirm_hits: int = 0
    # pyramid launches of the pallas adapter that return hit ids, and
    # those of them answered with the dense mask because a capacity
    # overflowed (DESIGN.md §12)
    compact_launches: int = 0
    compact_overflows: int = 0

    def record(self, n_queries: int, accesses: int, launches: int) -> None:
        self.queries += int(n_queries)
        self.node_accesses += int(accesses)
        self.launches += int(launches)

    def absorb_health(self, health: Optional[dict]) -> None:
        """Fold one :meth:`SpatialServer.drain_health` delta into the
        ledger (no-op for backends without a degradation ladder)."""
        if not health:
            return
        self.retries += int(health.get("retries", 0))
        self.degraded_batches += int(health.get("degraded_batches", 0))
        self.launch_failures += sum(
            int(v) for v in health.get("rung_failures", {}).values()
        )
        for rung, n in health.get("rung_dispatches", {}).items():
            if n:
                self.rung_dispatches[rung] = (
                    self.rung_dispatches.get(rung, 0) + int(n)
                )

    def absorb_launch_report(self, report) -> None:
        """Fold one merged :class:`repro.obs.LaunchReport` into the
        ledger (DESIGN.md §13)."""
        if report is None:
            return
        self.bytes_streamed += float(report.bytes_streamed)
        self.mask_bytes += float(report.mask_bytes)
        self.tiles_fetched += int(report.tiles_fetched)
        self.tiles_skipped += int(report.tiles_skipped)
        self.launch_reports += 1

    def absorb_stages(self, counters: dict) -> None:
        """Fold drained stage counters (:func:`repro.obs.trace.
        drain_counters`) into the ledger (DESIGN.md §13)."""
        for name, value in counters.items():
            setattr(self, name, getattr(self, name) + value)

    def to_dict(self) -> dict:
        """Flat snapshot of every counter (``rung_dispatches`` stays a
        nested dict) — the canonical form for metrics export and for
        windowed deltas via :meth:`diff`."""
        out = dataclasses.asdict(self)
        out["rung_dispatches"] = dict(self.rung_dispatches)
        return out

    def diff(self, prev) -> dict:
        """Counter deltas since ``prev`` (an :class:`AccessStats` or a
        previous :meth:`to_dict` snapshot) — per-window accounting
        instead of lifetime totals.  Zero rung entries are dropped."""
        prev_d = prev.to_dict() if isinstance(prev, AccessStats) else dict(prev)
        out = {}
        for k, v in self.to_dict().items():
            if isinstance(v, dict):
                pv = prev_d.get(k) or {}
                d = {r: n - pv.get(r, 0) for r, n in v.items()}
                out[k] = {r: n for r, n in d.items() if n}
            else:
                out[k] = v - prev_d.get(k, 0)
        return out

    @property
    def degraded(self) -> bool:
        """True once any batch was answered below the top rung."""
        return self.degraded_batches > 0

    @property
    def accesses_per_query(self) -> float:
        return self.node_accesses / max(self.queries, 1)


# ---------------------------------------------------------------------------
# Build artifacts: what the registry lowers a structure to, lazily
# ---------------------------------------------------------------------------


def _reject_opts(structure: str, **opts) -> None:
    """A build option the chosen structure does not use fails loudly —
    same strictness contract as the backend options."""
    bad = [k for k, v in opts.items() if v is not None]
    if bad:
        raise TypeError(
            f"structure {structure!r} does not accept option(s) {bad}"
        )


class BuildArtifacts:
    """One built structure plus its lazily lowered forms.

    A backend declares which artifact it consumes — the pointer tree, the
    :class:`FlatTree`, or the :class:`LevelSchedule` — and pulls it from
    here; each lowering is computed once and cached, so switching backends
    over the same build (``SpatialIndex.with_backend``) is cheap.
    """

    def __init__(self, structure: str, mbrs: np.ndarray, *, levels=None,
                 max_entries=None, build=None, order=None):
        self.structure = structure
        self.mbrs = validate_mbrs(mbrs)
        self.n_objects = self.mbrs.shape[0]
        if order not in (None, "none", "hilbert"):
            raise ValueError(
                f"unknown order {order!r}; expected 'hilbert' (or None)"
            )
        # original user options, so extend() can re-run the same build
        self.build_opts = dict(levels=levels, max_entries=max_entries,
                               build=build, order=order)
        self.pointer_tree = None
        self.pyramid = None
        self._flat: Optional[FlatTree] = None
        self._schedule: Optional[LevelSchedule] = None
        self._ordered = False  # Hilbert permutation applied to _schedule?
        self._quantized = None
        self._quantized8 = None
        # Autotuned TileConfig winners keyed by kernels.autotune.shape_key,
        # shared by every backend over these artifacts (DESIGN.md §12),
        # and the candidates the tuner refused: (shape_key, TileConfig)
        # -> first error.
        self.tuned: dict = {}
        self.tune_refusals: dict = {}
        if structure == "mqr":
            _reject_opts(structure, levels=levels, max_entries=max_entries,
                         build=build)
            self.pointer_tree = mqrtree.build(self.mbrs)
        elif structure == "rtree":
            _reject_opts(structure, levels=levels, build=build)
            self.pointer_tree = rtree.build(
                self.mbrs,
                max_entries=rtree.DEFAULT_M if max_entries is None else max_entries,
            )
        elif structure == "pyramid":
            _reject_opts(structure, max_entries=max_entries)
            if build not in (None, "host", "device"):
                raise ValueError(
                    f"unknown build {build!r}; expected 'host' or 'device'"
                )
            if levels is None:
                levels = bulk.default_levels(self.n_objects)
            if build == "device":
                # Device-resident bulk build: the level fixed point runs
                # on-accelerator and emits the LevelSchedule directly —
                # no host pointer tree, no flatten() (DESIGN.md §7).
                from repro.kernels import ops

                self._schedule = ops.device_schedule(
                    np.asarray(self.mbrs, np.float32), levels=levels
                )
            else:
                self.pyramid = bulk.build_pyramid(
                    np.asarray(self.mbrs, np.float32), levels=levels
                )
        else:
            raise ValueError(
                f"unknown structure {structure!r}; expected one of {STRUCTURES}"
            )

    @classmethod
    def restore(cls, structure: str, mbrs: np.ndarray, build_opts: dict,
                schedule: LevelSchedule, quantized=None) -> "BuildArtifacts":
        """Rehydrate artifacts from a checkpoint (DESIGN.md §9).

        The saved :class:`LevelSchedule` (and quantized tile form, when
        it was materialized at save time) is installed directly — load
        NEVER re-runs a device build, so an index restores even when the
        accelerator path that built it is degraded.  The host pointer
        tree (mqr/rtree only; needed by the host backend and pointer
        k-NN) is rebuilt deterministically from the object table.
        """
        self = cls.__new__(cls)
        self.structure = structure
        self.mbrs = np.asarray(mbrs, np.float64).reshape(-1, 4)
        self.n_objects = self.mbrs.shape[0]
        self.build_opts = dict(levels=None, max_entries=None, build=None,
                               order=None)
        self.build_opts.update(build_opts or {})
        self.pointer_tree = None
        self.pyramid = None
        self._flat = None
        self._schedule = schedule
        # The saved schedule was captured AFTER any build-time slot
        # ordering, so restore never re-permutes.
        self._ordered = True
        self._quantized = quantized
        self._quantized8 = None
        self.tuned = {}
        self.tune_refusals = {}
        if structure == "mqr":
            self.pointer_tree = mqrtree.build(self.mbrs)
        elif structure == "rtree":
            me = self.build_opts.get("max_entries")
            self.pointer_tree = rtree.build(
                self.mbrs,
                max_entries=rtree.DEFAULT_M if me is None else me,
            )
        return self

    @property
    def flat(self) -> FlatTree:
        if self._flat is None:
            if self.pointer_tree is None:
                raise ValueError(
                    "structure 'pyramid' has no pointer tree / FlatTree form"
                )
            self._flat = flatten(self.pointer_tree)
        return self._flat

    @property
    def schedule(self) -> LevelSchedule:
        if self._schedule is None:
            if self.pyramid is not None:
                self._schedule = pyramid_schedule(self.pyramid, self.mbrs)
            else:
                self._schedule = level_schedule(self.flat)
        if not self._ordered:
            self._ordered = True
            if self.build_opts.get("order") == "hilbert":
                # Build-time locality pass (DESIGN.md §12): permute every
                # level's real slots into Hilbert order of their MBR
                # centers.  Hits, visits and ids are bit-identical; only
                # which slots share a tile changes.
                from repro.kernels import ops

                self._schedule = ops.hilbert_permute(self._schedule)
        return self._schedule

    @property
    def unisolated_objects(self) -> int:
        """Objects that share their deepest pyramid group with another
        object, which the search confirms against their own MBR
        (``LevelSchedule.n_shared``); 0 for the trees, which test every
        object."""
        return self.schedule.n_shared

    @property
    def quantized(self):
        """Compact uint16 tile form of :attr:`schedule` (DESIGN.md §7),
        quantized once and shared by every ``precision="compact"``
        backend over these artifacts."""
        if self._quantized is None:
            from repro.kernels import ops

            self._quantized = ops.quantize_schedule(self.schedule)
        return self._quantized

    @property
    def quantized8(self):
        """Hierarchical uint8-upper/uint16-lower tile form of
        :attr:`schedule` (DESIGN.md §12) for ``precision="compact8"``
        backends, quantized once and shared like :attr:`quantized`."""
        if self._quantized8 is None:
            from repro.kernels import ops

            self._quantized8 = ops.quantize_schedule(
                self.schedule, upper8=True
            )
        return self._quantized8


# ---------------------------------------------------------------------------
# The façade
# ---------------------------------------------------------------------------


class SpatialIndex:
    """Unified build/query surface over every structure × backend path."""

    def __init__(self, artifacts: BuildArtifacts, spec: BackendSpec, **backend_opts):
        if artifacts.structure not in spec.structures:
            raise ValueError(
                f"backend {spec.name!r} does not serve structure "
                f"{artifacts.structure!r} (serves: {sorted(spec.structures)})"
            )
        self._artifacts = artifacts
        self.spec = spec
        self.stats = AccessStats()
        self._backend_opts = dict(backend_opts)
        self._backend = spec.factory(artifacts, **backend_opts)
        # live-update state (DESIGN.md §8); created on first insert/delete.
        # The log lives in a shared one-slot cell so `with_backend` twins
        # observe mutations regardless of whether the first mutation
        # happens before or after the twin is created.
        self._policy = None            # MergePolicy override from build()
        self._updates_cell = {"log": None}
        self._live_engine = None
        self._backend_base_epoch = 0   # base epoch self._backend was built at
        # durability knobs (DESIGN.md §9)
        self._admission = "merge"      # what to do with unbufferable batches
        self._fault_plan = None        # repro.ft.FaultPlan, threaded everywhere

    @property
    def _updates(self):
        return self._updates_cell["log"]

    @_updates.setter
    def _updates(self, log):
        self._updates_cell["log"] = log

    # -- construction --------------------------------------------------
    @classmethod
    def build(cls, mbrs, *, structure: str = "mqr", backend: str = "pallas",
              backend_opts: Optional[dict] = None, **opts) -> "SpatialIndex":
        """Build a spatial index over ``mbrs`` (n, 4).

        structure: ``mqr`` (paper pointer tree) | ``rtree`` (Guttman
            baseline) | ``pyramid`` (bulk bottom-up fixed point).
        backend:   ``host`` (pointer/numpy oracle) | ``lax`` (jit'd level
            sweep) | ``pallas`` (fused single-launch kernel) | ``serve``
            (batching server: LRU cache + dedupe + vmap/pmap fan-out).
        opts: build options (``levels`` and ``build="host"|"device"`` for
            pyramid — ``"device"`` runs the bulk fixed point on-device and
            emits the ``LevelSchedule`` directly, no host pointer tree;
            ``max_entries`` for rtree) plus backend options
            (``block_w``/``interpret``/``precision="float32"|"compact"``
            for pallas and serve — ``"compact"`` streams conservatively
            quantized uint16 MBR tiles with an exact confirming pass, see
            DESIGN.md §7 — plus ``query_block``/``cache_size`` for
            serve), routed by key; an option the chosen structure or
            backend does not support raises ``TypeError`` rather than
            being silently dropped.  Live-update options (DESIGN.md §8):
            ``capacity`` (delta-buffer slots) and ``merge`` (a
            ``repro.update.MergePolicy`` or kwargs dict) configure how
            :meth:`insert`/:meth:`delete` buffer and when they compact.
            Durability options (DESIGN.md §9): ``admission`` — what to do
            with a batch the delta buffer cannot absorb: ``"merge"``
            (default: fold it into a compaction; raises
            ``repro.update.BufferFullError`` instead when the merge
            policy has ``auto=False``) or ``"shed"`` (drop the batch,
            count it in ``stats.shed_mutations``); ``fault_plan`` — a
            ``repro.ft.FaultPlan`` threaded through the update engine
            and serving ladder for fault-injection tests.
        backend_opts: an explicit dict of backend-only options (e.g.
            tile/stream overrides ``{"block_w": 256, "stream": True,
            "autotune": "off"}``), merged with the backend options routed
            out of ``opts``.  Keys are strict: a key also given in
            ``opts`` raises ``TypeError`` (no silent precedence), and an
            option the backend factory does not accept raises
            ``TypeError`` from its signature.
        """
        explicit = dict(backend_opts or {})
        update_opts = {k: opts.pop(k) for k in list(opts) if k in _UPDATE_OPTS}
        build_opts = {k: v for k, v in opts.items() if k in _BUILD_OPTS}
        backend_opts = {k: v for k, v in opts.items() if k not in _BUILD_OPTS}
        for k, v in explicit.items():
            if k in backend_opts or k in build_opts or k in update_opts:
                raise TypeError(
                    f"backend_opts duplicates option {k!r} also passed "
                    f"directly"
                )
            if k in _BUILD_OPTS or k in _UPDATE_OPTS:
                raise TypeError(
                    f"backend_opts key {k!r} is a "
                    f"{'build' if k in _BUILD_OPTS else 'update'} option; "
                    f"pass it directly"
                )
            backend_opts[k] = v
        artifacts = BuildArtifacts(structure, mbrs, **build_opts)
        idx = cls(artifacts, get_backend(backend), **backend_opts)
        if "capacity" in update_opts or "merge" in update_opts:
            from repro.update import as_policy

            # validated eagerly so a bad option fails at build time
            idx._policy = as_policy(
                update_opts.get("merge"), update_opts.get("capacity")
            )
        admission = update_opts.get("admission")
        if admission is not None:
            if admission not in ADMISSION_MODES:
                raise ValueError(
                    f"unknown admission {admission!r}; expected one of "
                    f"{ADMISSION_MODES} (queueing lives in "
                    f"repro.checkpoint.DurableIndex)"
                )
            idx._admission = admission
        if update_opts.get("fault_plan") is not None:
            idx.bind_fault_plan(update_opts["fault_plan"])
        return idx

    def with_backend(self, backend: str, **backend_opts) -> "SpatialIndex":
        """A new index answering from the SAME build artifacts on another
        backend (build once, serve anywhere; lowerings are shared).  Live
        mutation state is shared too: the twin answers over the same
        base ∪ delta − tombstones, and mutations through either index are
        visible to both."""
        new = SpatialIndex(self.artifacts, get_backend(backend), **backend_opts)
        new._policy = self._policy
        new._admission = self._admission
        new._updates_cell = self._updates_cell
        if self._updates is not None:
            new._backend_base_epoch = self._updates.base_epoch
        if self._fault_plan is not None:
            new.bind_fault_plan(self._fault_plan)
        return new

    def extend(self, new_mbrs, *, flush: str = "auto") -> "SpatialIndex":
        """Batch insertion: a new index whose live set adds ``new_mbrs``.

        Routed through the live-update subsystem (DESIGN.md §8): the
        batch lands in the NEW index's delta buffer and merges by policy
        — no unconditional rebuild — while this index stays untouched.
        ``flush="always"`` restores the old eager behavior (compact
        immediately; on a never-mutated index that is exactly the legacy
        full re-build over the concatenated arrays, one device launch for
        ``build="device"``).  Batches larger than the buffer capacity
        merge directly either way.
        """
        if flush not in ("auto", "always"):
            raise ValueError(
                f"unknown flush {flush!r}; expected 'auto' or 'always'"
            )
        new_mbrs = np.asarray(new_mbrs, np.float64).reshape(-1, 4)
        if flush == "always" and self._updates is None:
            # Legacy path, bit-for-bit: a pristine re-build over the
            # concatenated object set, no live-update state attached.
            mbrs = np.concatenate([self.artifacts.mbrs, new_mbrs], axis=0)
            artifacts = BuildArtifacts(
                self.structure, mbrs, **self.artifacts.build_opts
            )
            clone = SpatialIndex(artifacts, self.spec, **self._backend_opts)
            clone._policy = self._policy
            return clone
        clone = self._snapshot()
        clone.insert(new_mbrs)
        if flush == "always":
            clone.flush()
        return clone

    def _snapshot(self) -> "SpatialIndex":
        """A new index over the same (current) base with an independent
        copy of any live-update state."""
        clone = SpatialIndex(self.artifacts, self.spec, **self._backend_opts)
        clone._policy = self._policy
        clone._admission = self._admission
        if self._updates is not None:
            clone._updates = self._updates.snapshot()
            clone._backend_base_epoch = clone._updates.base_epoch
        return clone

    # -- introspection -------------------------------------------------
    @property
    def artifacts(self) -> BuildArtifacts:
        """The CURRENT frozen base build (replaced at every merge)."""
        if self._updates is not None:
            return self._updates.base
        return self._artifacts

    @property
    def structure(self) -> str:
        return self.artifacts.structure

    @property
    def backend(self) -> str:
        return self.spec.name

    @property
    def n_objects(self) -> int:
        """Number of LIVE objects (base survivors + buffered inserts)."""
        if self._updates is not None:
            return self._updates.n_live
        return self.artifacts.n_objects

    @property
    def id_space(self) -> int:
        """Width of ``RegionResult.hits``: the dense global-id space
        ``[0, id_space)``.  Equals ``n_objects`` until live updates
        begin; append-only afterwards (deleted ids never recycle, §8)."""
        if self._updates is not None:
            return self._updates.id_capacity
        return self.artifacts.n_objects

    @property
    def schedule(self) -> LevelSchedule:
        return self.artifacts.schedule

    # -- durability / fault injection (DESIGN.md §9) -------------------
    def bind_fault_plan(self, plan) -> None:
        """Thread a :class:`repro.ft.FaultPlan` (or ``None`` to detach)
        through every layer that honors injection hooks: the update log
        (mid-merge kills, slow merges) and the serving ladder (forced
        launch failures)."""
        self._fault_plan = plan
        if self._updates is not None:
            self._updates.fault_plan = plan
        if hasattr(self._backend, "bind_fault_plan"):
            self._backend.bind_fault_plan(plan)
        if self._live_engine is not None:
            self._live_engine.bind_fault_plan(plan)

    def _drain_health(self, source) -> None:
        drain = getattr(source, "drain_health", None)
        if drain is not None:
            self.stats.absorb_health(drain())

    # -- live updates (DESIGN.md §8) -----------------------------------
    def _ensure_log(self):
        if self._updates is None:
            from repro.update import MergePolicy, UpdateLog

            structure = self._artifacts.structure
            build_opts = dict(self._artifacts.build_opts)
            self._updates = UpdateLog(
                self._artifacts,
                self._policy if self._policy is not None else MergePolicy(),
                rebuild=lambda mbrs: BuildArtifacts(
                    structure, mbrs, **build_opts
                ),
            )
            self._backend_base_epoch = self._updates.base_epoch
        if self._fault_plan is not None:
            self._updates.fault_plan = self._fault_plan
        return self._updates

    def _live(self):
        from repro.update.engine import LiveEngine

        if self._live_engine is None or self._live_engine.log is not self._updates:
            self._live_engine = LiveEngine(
                self._updates, self.spec.name, self._backend_opts
            )
            if self._fault_plan is not None:
                self._live_engine.bind_fault_plan(self._fault_plan)
        return self._live_engine

    def _current_backend(self):
        """The pristine backend adapter over the CURRENT base build,
        re-lowered lazily after a merge (possibly initiated through a
        ``with_backend`` twin sharing the same update log)."""
        if (
            self._updates is not None
            and self._backend_base_epoch != self._updates.base_epoch
        ):
            self._backend = self.spec.factory(
                self.artifacts, **self._backend_opts
            )
            self._backend_base_epoch = self._updates.base_epoch
        return self._backend

    def insert(self, new_mbrs) -> np.ndarray:
        """Insert objects ONLINE; returns their global ids.

        The batch lands in the device-resident delta buffer (O(1), no
        rebuild) and is immediately visible to every query path; the
        merge policy — or a full buffer — folds it into a fresh base
        build later.  Batches larger than the buffer capacity merge
        directly (one bulk rebuild over the live set, the §7 path).
        """
        new_mbrs = validate_mbrs(new_mbrs, what="insert batch")
        n = new_mbrs.shape[0]
        if n == 0:  # no-op: leave pristine state and epochs untouched
            return np.zeros((0,), np.int64)
        with _obs_trace.span("index.insert", n=n):
            log = self._ensure_log()
            if n > log.capacity:
                # Oversized batch: never bufferable, folds straight into
                # one merge — the documented bulk path, regardless of
                # admission.
                gids = log.merge_insert(new_mbrs)
                self.stats.flushes += 1
            elif not log.can_buffer(n):
                # Full buffer (free slots / id headroom exhausted):
                # admission control decides (DESIGN.md §9).
                if self._admission == "shed":
                    self.stats.shed_mutations += n
                    return np.zeros((0,), np.int64)
                if not log.policy.auto:
                    from repro.update import BufferFullError

                    raise BufferFullError(
                        f"delta buffer cannot absorb {n} insert(s) "
                        f"(fill {log.fill:.0%}) and the merge policy has "
                        f"auto=False; call flush() or enable auto merging"
                    )
                gids = log.merge_insert(new_mbrs)
                self.stats.flushes += 1
            else:
                gids = log.buffer_insert(new_mbrs)
                if log.policy.should_flush(
                    fill=log.fill, tombstone_ratio=log.tombstone_ratio
                ):
                    log.flush()
                    self.stats.flushes += 1
            self.stats.inserts += n
        return gids

    def delete(self, ids) -> None:
        """Delete live objects by global id (tombstone semantics, §8).

        Base objects stay physically in the frozen build, masked out of
        every hit set from this call on; buffered inserts free their
        delta slot.  Unknown or already-dead ids raise ``KeyError``.
        """
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size == 0:  # no-op: leave pristine state and epochs untouched
            return
        with _obs_trace.span("index.delete", n=ids.size):
            log = self._ensure_log()
            gids = log.delete(ids)
            self.stats.deletes += int(gids.shape[0])
            if (
                log.n_live > 0
                and log.policy.should_flush(
                    fill=log.fill, tombstone_ratio=log.tombstone_ratio
                )
            ):
                log.flush()
                self.stats.flushes += 1

    def flush(self) -> bool:
        """Manually merge buffer + tombstones into a fresh base build.

        Hit sets are bit-identical before and after (global ids are
        preserved); returns True if a merge actually ran.
        """
        if self._updates is None:
            return False
        with _obs_trace.span("index.flush"):
            if self._updates.flush():
                self.stats.flushes += 1
                return True
        return False

    def live_metrics(self):
        """Paper §5.2 structure-quality metrics (overlap, overcoverage,
        …) of the CURRENT live object set, evaluated on the mqr
        insertion-rule oracle tree — how the zero-overlap property is
        monitored under mutation (DESIGN.md §8)."""
        from repro.core import metrics as _metrics
        from repro.update.oracle import live_tree

        return _metrics.compute_metrics(live_tree(self))

    # -- observability (DESIGN.md §13) ---------------------------------
    def metrics(self, *, tenant: Optional[str] = None):
        """Snapshot :attr:`stats` into a :class:`repro.obs.MetricsRegistry`
        (render with ``.to_prometheus()`` or ``.to_json()``); ``tenant``
        adds a label to every sample."""
        from repro.obs import metrics as _obs_metrics

        reg = _obs_metrics.MetricsRegistry()
        labels = {"tenant": tenant} if tenant else None
        _obs_metrics.stats_into(reg, self.stats, labels=labels)
        _obs_metrics.build_into(reg, self.artifacts, labels=labels)
        return reg

    # -- durability (DESIGN.md §9) -------------------------------------
    def save(self, path) -> None:
        """Write a versioned on-disk snapshot of the full index state —
        base build (object table + level schedule + quantized tiles if
        materialized), delta buffer, tombstones, id space, and merge
        policy — atomically (tmp + rename).  :meth:`load` restores
        bit-identical region/point/knn/count answers on every backend.
        """
        from repro.checkpoint.spatial import save_index

        save_index(self, path)

    @classmethod
    def load(cls, path, *, backend: str = "pallas", **backend_opts
             ) -> "SpatialIndex":
        """Restore an index saved by :meth:`save` onto any backend.

        The snapshot is backend-agnostic; the level schedule is installed
        directly (no device rebuild runs at load time), so restore works
        even when the accelerator path that built the index is down.
        """
        from repro.checkpoint.spatial import load_index

        return load_index(path, backend=backend, **backend_opts)

    # -- queries -------------------------------------------------------
    def _region_raw(self, queries: np.ndarray):
        """Route a region batch: pristine backend, or the live engine
        once update state exists.  Returns
        ``(hits, visits, launches, base_levels-or-None)``."""
        if self._updates is None:
            hits, visits, launches = self._backend.region(queries)
            self._drain_health(self._backend)
            return hits, visits, launches, None
        live = self._live()
        hits, visits, launches = live.region(
            queries,
            base_region=lambda qs: self._current_backend().region(qs),
        )
        self._drain_health(live)
        return hits, visits, launches, self._updates.base.schedule.levels

    def _drain_launch_report(self, visits=None):
        """Drain + merge the kernel side channel for one logical batch;
        fills survivor counts from the sweep's own visits when the
        emitting path didn't compute them (DESIGN.md §13)."""
        if not _obs_counters.collecting():
            return None
        report = _obs_counters.merge_reports(_obs_counters.drain())
        if report is not None:
            if report.survivors_per_level is None and visits is not None:
                report.survivors_per_level = tuple(
                    int(x) for x in np.asarray(visits).sum(axis=0)
                )
            if report.backend is None:
                report.backend = self.spec.name
            self.stats.absorb_launch_report(report)
        return report

    def region(self, queries) -> RegionResult:
        """Batched region search over (Q, 4) query rectangles."""
        queries = np.asarray(queries, np.float32).reshape(-1, 4)
        with _obs_trace.span("index.region", backend=self.spec.name,
                             structure=self.structure,
                             queries=queries.shape[0]):
            hits, visits, launches, base_levels = self._region_raw(queries)
        self.stats.record(queries.shape[0], visits.sum(), launches)
        if base_levels is not None:
            self.stats.delta_accesses += int(visits[:, base_levels:].sum())
        self.stats.absorb_stages(_obs_trace.drain_counters())
        return RegionResult(
            hits=hits, visits_per_level=visits, base_levels=base_levels,
            launch_report=self._drain_launch_report(visits),
        )

    def point(self, points) -> RegionResult:
        """Point queries (Q, 2) as degenerate rectangles.

        For point data the paper's zero-overlap property (§4) makes this a
        one-path search on the mqr-tree (§5.5); all backends inherit that
        access count through the same level sweep.
        """
        points = np.asarray(points, np.float32).reshape(-1, 2)
        return self.region(np.concatenate([points, points], axis=1))

    def count(self, queries) -> np.ndarray:
        """(Q,) number of objects overlapping each query rectangle."""
        return self.region(queries).counts

    def join(self, other: "SpatialIndex", predicate: str = "intersects"):
        """Batch spatial join against another index (DESIGN.md §10).

        Sweeps both indexes' level schedules against each other in one
        launch (this index's backend/precision picks the engine; the
        ``serve`` backend walks its degradation ladder) and returns a
        :class:`repro.index.join.JoinResult` whose pair-set is
        bit-identical to the brute-force nested-loop oracle over the two
        live object sets — including mid-buffer live state and
        tombstones on either side.  Only ``predicate="intersects"``
        (closed-boundary overlap, the paper's region semantics) is
        defined.
        """
        from .join import join_impl

        with _obs_trace.span("index.join", backend=self.spec.name,
                             other_backend=other.spec.name,
                             predicate=predicate):
            result, launches = join_impl(self, other, predicate)
        self.stats.joins += 1
        self.stats.record(1, result.pair_visits.sum(), launches)
        self.stats.delta_accesses += int(result.delta_tests.sum())
        return result

    def knn(self, points, k: int) -> KNNResult:
        """k nearest neighbours of each (Q, 2) point, by MBR min-distance.

        Host backend: exact branch-and-bound over the pointer tree (brute
        force for the pyramid, which has no pointer form).  Device
        backends: expanding-radius region schedule driven through the
        backend's fused sweep until ≥k survivors, one √2-margin confirming
        round, then a top-k distance epilogue in jnp (DESIGN.md §6).
        """
        points = np.asarray(points, np.float64).reshape(-1, 2)
        if not 1 <= k <= self.n_objects:
            raise ValueError(f"k={k} outside [1, {self.n_objects}]")
        live = self._updates
        with _obs_trace.span("index.knn", backend=self.spec.name, k=k,
                             queries=points.shape[0]):
            if self.spec.name == "host":
                if live is not None:
                    # Under mutation the base pointer tree is stale; the
                    # host oracle answers exactly from the live id-space
                    # table.
                    ids, dists, visits = _knn.knn_brute_masked(
                        live.mbr_table, live.alive, points, k
                    )
                elif self.artifacts.pointer_tree is not None:
                    ids, dists, visits = _knn.knn_pointer(
                        self.artifacts.pointer_tree, points, k
                    )
                else:
                    ids, dists, visits = _knn.knn_brute(
                        self.artifacts.mbrs, points, k
                    )
                self.stats.knn_queries += points.shape[0]
                self.stats.record(points.shape[0], visits.sum(), 0)
            else:
                def region_fn(qs):
                    hits, visits, launches, base_levels = self._region_raw(qs)
                    self.stats.record(0, visits.sum(), launches)
                    if base_levels is not None:
                        self.stats.delta_accesses += int(
                            visits[:, base_levels:].sum()
                        )
                    return hits, visits

                # Live indexes rank candidates over the id-space MBR table
                # (hits already exclude tombstones, so stale rows never
                # rank).
                obj_mbrs = (live.mbr_table if live is not None
                            else self.artifacts.mbrs)
                ids, dists, visits, rounds = _knn.knn_expanding(
                    region_fn, obj_mbrs, points, k
                )
                self.stats.knn_queries += points.shape[0]
                self.stats.knn_rounds += rounds
                self.stats.queries += points.shape[0]
                # fold every expanding-radius round's kernel ledger
                self._drain_launch_report()
        self.stats.absorb_stages(_obs_trace.drain_counters())
        return KNNResult(ids=ids, dists=dists, visits=visits)
