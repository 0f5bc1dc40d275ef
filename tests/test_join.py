"""`SpatialIndex.join`: differential harness against the nested-loop oracle.

DESIGN.md §10's acceptance contract: the join pair-set is bit-identical
to the brute-force O(n·m) oracle over the two live object sets on every
structure × backend × precision — pristine AND mid-buffer live state,
tombstones excluded, degradation rungs included — and on point data the
paper's zero-overlap property (§4) makes a self-join exactly the
identity pairs.  The sweep's pair-visit ledger is backend-invariant for
float32 and conservatively larger for compact tiles.

`REPRO_JOIN_SEED` (CI matrix) salts every dataset in this module.
"""
import os

import numpy as np
import pytest

import conftest
from conftest import f32_exact
from repro.core import datasets
from repro.ft import FaultPlan
from repro.index import JoinResult, SpatialIndex
from repro.index.join import JOIN_LADDER, PREDICATES

SEED = int(os.environ.get("REPRO_JOIN_SEED", "0"))
STRUCTURES = ("mqr", "rtree", "pyramid")
BACKENDS = ("host", "lax", "pallas", "serve")


def _overlap_np(a, b):
    return (
        (a[..., 0] <= b[..., 2])
        & (b[..., 0] <= a[..., 2])
        & (a[..., 1] <= b[..., 3])
        & (b[..., 1] <= a[..., 3])
    )


def oracle_pairs(left, right) -> np.ndarray:
    """Brute-force nested-loop join over the two indexes' live object
    sets, in float32 (the device coordinate convention)."""

    def side(idx):
        log = idx._updates
        if log is None:
            t = np.asarray(idx.artifacts.mbrs, np.float32)
            return t, np.ones((t.shape[0],), bool)
        return log.mbr_table.astype(np.float32), log.alive

    ta, aa = side(left)
    tb, ab = side(right)
    ov = _overlap_np(ta[:, None, :], tb[None, :, :])
    return ov & aa[:, None] & ab[None, :]


def _data(tag: str, kind: str, n: int) -> np.ndarray:
    """Per-side dataset: ``tag`` keeps the two join sides on distinct
    deterministic streams, ``SEED`` freshens both per CI matrix leg."""
    return f32_exact(conftest.mbr_dataset(f"test_join/{tag}", kind, n,
                                          salt=SEED))


def _check(left, right):
    res = left.join(right)
    assert isinstance(res, JoinResult)
    expect = oracle_pairs(left, right)
    assert res.pairs.shape == expect.shape
    assert np.array_equal(res.pairs, expect)
    return res


# ---------------------------------------------------------------------------
# The parity matrix: structures × backends × precision
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("structure", STRUCTURES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_join_parity_matrix(structure, backend):
    """Pair sets bit-identical to the oracle on every structure ×
    backend, and the pair-visit ledger identical across float32 engines
    (the sweep recurrence is the same computation everywhere)."""
    da = _data("a", "uniform_squares", 160)
    db = _data("b", "exponential_squares", 130)
    left = SpatialIndex.build(da, structure=structure, backend=backend)
    right = SpatialIndex.build(db, structure="mqr", backend="host")
    res = _check(left, right)
    ref = SpatialIndex.build(
        da, structure=structure, backend="host"
    ).join(right)
    assert np.array_equal(res.pair_visits, ref.pair_visits), (
        f"{structure}×{backend} pair-visit parity"
    )


@pytest.mark.parametrize("symmetric", [False, True])
def test_pair_sweep_onehot_matches_column_gather(symmetric):
    """The MXU one-hot double parent gather (the TPU path) and the
    interpreter's two-stage take must produce the same pair sweep."""
    import jax.numpy as jnp

    from repro.kernels.ops import pair_sweep

    a = SpatialIndex.build(_data("onehot-a", "uniform_squares", 300), structure="mqr")
    b = a if symmetric else SpatialIndex.build(
        _data("onehot-b", "uniform_squares", 200), structure="mqr")
    k = min(a.schedule.levels, b.schedule.levels)
    args = [jnp.asarray(x) for x in (
        a.schedule.mbr_cm[:k], a.schedule.parent[:k],
        b.schedule.mbr_cm[:k], b.schedule.parent[:k],
    )]
    one = pair_sweep(*args, interpret=True, onehot_gather=True,
                     symmetric=symmetric)
    col = pair_sweep(*args, interpret=True, onehot_gather=False,
                     symmetric=symmetric)
    assert np.asarray(col).any()
    assert np.array_equal(np.asarray(one), np.asarray(col))


@pytest.mark.parametrize("structure", STRUCTURES)
def test_join_compact_parity_and_conservative_visits(structure):
    """precision="compact" joins on the joint uint16 grid: identical
    pair sets, per-level visits a conservative superset of float32."""
    da = _data("a", "uniform_squares", 160)
    db = _data("b", "uniform_squares", 130)
    right = SpatialIndex.build(db, structure="rtree", backend="host")
    exact = SpatialIndex.build(
        da, structure=structure, backend="pallas"
    ).join(right)
    left = SpatialIndex.build(
        da, structure=structure, backend="pallas", precision="compact"
    )
    res = _check(left, right)
    assert np.array_equal(res.pairs, exact.pairs)
    assert (res.pair_visits >= exact.pair_visits).all()


def test_join_mixed_structures_and_depths():
    """Left and right may differ in structure and tree height — the
    sweep runs to the shallower depth and stays exact."""
    da = _data("a", "exponential_squares", 300)   # deep mqr
    db = _data("b", "uniform_squares", 40)        # shallow
    left = SpatialIndex.build(da, structure="mqr", backend="pallas")
    right = SpatialIndex.build(db, structure="pyramid", backend="host")
    res = _check(left, right)
    assert res.base_levels == min(
        left.schedule.levels, right.schedule.levels
    )
    _check(right.with_backend("pallas"), left)  # and the transpose


def test_self_join_points_is_identity():
    """The paper's zero-overlap claim (§4) on point data: distinct
    points overlap only themselves, so a self-join is EXACTLY the
    identity pair set — on the exact and compact paths."""
    pts = f32_exact(conftest.mbr_dataset("test_join", "uniform_points",
                                         150, salt=SEED))
    assert np.unique(pts, axis=0).shape[0] == pts.shape[0]
    idx = SpatialIndex.build(pts, structure="mqr", backend="pallas")
    res = idx.join(idx)
    assert np.array_equal(res.pairs, np.eye(150, dtype=bool))
    cmp_ = SpatialIndex.build(
        pts, structure="mqr", backend="pallas", precision="compact"
    )
    assert np.array_equal(cmp_.join(cmp_).pairs, np.eye(150, dtype=bool))


# ---------------------------------------------------------------------------
# Adversarial geometry (explicit) — co-centred, degenerate, grid-aligned
# ---------------------------------------------------------------------------


def _build_all_backends(data, structure="mqr"):
    host = SpatialIndex.build(data, structure=structure, backend="host")
    return [host] + [host.with_backend(b) for b in ("lax", "pallas")]


def test_join_cocentred_stacks():
    """Co-centred boxes (the mqr CENTER-chain worst case): every pair
    overlaps within a stack; deep chains on both sides stay exact."""
    rng = np.random.default_rng(conftest.derived_seed(
        "test_join", "cocentred", SEED))
    centres = rng.uniform(100, 900, size=(6, 2))
    sides = np.arange(1, 9, dtype=np.float64)[:, None]
    da = f32_exact(np.concatenate([
        np.concatenate([c - sides, c + sides], axis=1) for c in centres
    ]))
    db = f32_exact(np.concatenate([
        np.concatenate([c - 2 * sides, c + 2 * sides], axis=1)
        for c in centres + rng.uniform(-30, 30, centres.shape)
    ]))
    right = SpatialIndex.build(db, structure="mqr", backend="host")
    for left in _build_all_backends(da):
        _check(left, right)


def test_join_degenerate_zero_area():
    """Zero-area boxes (points, horizontal/vertical segments) joined
    against squares: closed-boundary touching counts as a pair."""
    pts = np.array([[10.0, 10, 10, 10], [20, 5, 20, 25],   # point, v-seg
                    [5, 20, 25, 20], [30, 30, 30, 30]])    # h-seg, point
    boxes = np.array([[0.0, 0, 10, 10],    # corner-touches the point
                      [15, 0, 20, 30],     # edge-touches the v-segment
                      [26, 26, 29, 29]])   # disjoint from everything
    da, db = f32_exact(pts), f32_exact(boxes)
    right = SpatialIndex.build(db, structure="rtree", backend="host")
    for left in _build_all_backends(da):
        res = _check(left, right)
        assert res.pairs[0, 0] and res.pairs[1, 1] and not res.pairs[:, 2].any()
    cleft = SpatialIndex.build(da, structure="mqr", backend="pallas",
                               precision="compact")
    _check(cleft, right)


def test_join_grid_aligned_boundaries():
    """Integer-lattice boxes that exactly share edges: boundary pairs
    must survive quantization (outward rounding on the joint grid can
    only widen, and the confirming pass is exact)."""
    xs, ys = np.meshgrid(np.arange(4) * 10.0, np.arange(4) * 10.0)
    ll = np.stack([xs.ravel(), ys.ravel()], axis=1)
    da = f32_exact(np.concatenate([ll, ll + 10.0], axis=1))    # tiling
    db = f32_exact(np.concatenate([ll + 10.0, ll + 20.0], axis=1))
    right = SpatialIndex.build(db, structure="mqr", backend="host")
    for left in _build_all_backends(da):
        _check(left, right)
    cleft = SpatialIndex.build(da, structure="mqr", backend="pallas",
                               precision="compact")
    _check(cleft, right)


# ---------------------------------------------------------------------------
# Live state: mid-buffer, tombstones, post-flush
# ---------------------------------------------------------------------------


def test_join_live_midbuffer_tombstones_and_flush():
    da = _data("a", "uniform_squares", 120)
    db = _data("b", "uniform_squares", 100)
    left = SpatialIndex.build(da, structure="pyramid", backend="pallas",
                              capacity=64)
    right = SpatialIndex.build(db, structure="mqr", backend="pallas",
                               capacity=64)
    ga = left.insert(f32_exact(datasets.uniform_squares(
        30, seed=conftest.derived_seed("test_join", "ins-a", SEED))))
    left.delete(np.arange(10))
    left.delete(ga[:5])
    gb = right.insert(f32_exact(datasets.uniform_squares(
        25, seed=conftest.derived_seed("test_join", "ins-b", SEED))))
    right.delete(gb[:3])

    # mid-buffer: every backend, both sides carrying deltas + tombstones
    expect = oracle_pairs(left, right)
    for backend in BACKENDS:
        res = left.with_backend(backend).join(right)
        assert np.array_equal(res.pairs, expect), f"live×{backend}"
        assert int(res.delta_tests.sum()) > 0  # deltas actually cross-scan
    res = left.with_backend("pallas", precision="compact").join(right)
    assert np.array_equal(res.pairs, expect)

    # tombstoned ids appear in no pair, ever
    res = left.join(right)
    assert not res.pairs[np.arange(10), :].any()
    assert not res.pairs[ga[:5], :].any()
    assert not res.pairs[:, gb[:3]].any()

    # post-flush: same global ids, same pair set (padded to new id space)
    left.flush()
    right.flush()
    post = left.join(right)
    assert np.array_equal(post.pairs, oracle_pairs(left, right))
    na, nb = expect.shape
    assert np.array_equal(post.pairs[:na, :nb], expect)
    assert not post.pairs[na:, :].any() and not post.pairs[:, nb:].any()
    assert int(post.delta_tests.sum()) == 0


# ---------------------------------------------------------------------------
# Serve ladder, API contract, stats
# ---------------------------------------------------------------------------


def test_join_serve_degrades_bit_identically():
    da = _data("a", "uniform_squares", 80)
    db = _data("b", "uniform_squares", 80)
    right = SpatialIndex.build(db, structure="mqr", backend="host")
    healthy = SpatialIndex.build(da, structure="mqr", backend="serve")
    expect = healthy.join(right).pairs
    assert healthy.stats.rung_dispatches.get("pallas", 0) == 1

    hurt = SpatialIndex.build(da, structure="mqr", backend="serve")
    hurt.bind_fault_plan(FaultPlan(fail_launches=1, fail_rungs=("pallas",)))
    res = hurt.join(right)
    assert np.array_equal(res.pairs, expect)
    assert hurt.stats.degraded
    assert hurt.stats.launch_failures == 1
    assert hurt.stats.rung_dispatches.get("lax", 0) == 1

    floor = SpatialIndex.build(da, structure="mqr", backend="serve")
    floor.bind_fault_plan(FaultPlan(fail_launches=2,
                                    fail_rungs=("pallas", "lax")))
    res = floor.join(right)
    assert np.array_equal(res.pairs, expect)
    assert floor.stats.rung_dispatches.get("host", 0) == 1
    assert tuple(JOIN_LADDER) == ("pallas", "lax", "host")


def test_join_unknown_predicate_raises():
    da = _data("a", "uniform_squares", 40)
    idx = SpatialIndex.build(da, structure="mqr", backend="host")
    with pytest.raises(ValueError, match="predicate"):
        idx.join(idx, predicate="within")
    assert PREDICATES == ("intersects",)


def test_join_stats_ledger():
    da = _data("a", "uniform_squares", 90)
    db = _data("b", "uniform_squares", 70)
    left = SpatialIndex.build(da, structure="mqr", backend="pallas")
    right = SpatialIndex.build(db, structure="mqr", backend="host")
    res = left.join(right)
    assert left.stats.joins == 1
    assert left.stats.queries == 1
    assert left.stats.node_accesses == int(res.pair_visits.sum())
    assert left.stats.launches == 1
    assert res.n_pairs == len(res.pair_list())
    assert np.array_equal(
        np.argwhere(res.pairs), res.pair_list()
    )


# ---------------------------------------------------------------------------
# Self-join fast path: symmetric upper-triangle sweep (half the work)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_self_join_symmetric_sweep_bit_parity(backend):
    """``idx.join(idx)`` takes the symmetric fast path: pair set
    bit-identical to the full sweep over an equal twin index, with
    strictly fewer sweep pair-tests (only the upper triangle runs)."""
    da = _data("a", "exponential_squares", 150)
    idx = SpatialIndex.build(da, structure="mqr", backend=backend)
    twin = SpatialIndex.build(da, structure="mqr", backend=backend)
    fast = idx.join(idx)        # right IS left -> symmetric sweep
    full = idx.join(twin)       # equal data, different object -> full sweep
    assert np.array_equal(fast.pairs, full.pairs)
    assert np.array_equal(fast.pairs, oracle_pairs(idx, idx))
    assert fast.sweep_visits.sum() < full.sweep_visits.sum()
    # the delta cross-scan columns are untouched by the fast path
    assert np.array_equal(fast.pair_visits[-2:], full.pair_visits[-2:])


def test_self_join_symmetric_visits_block_size_invariant():
    """The kernel's triu mask is SLOT-granular, so the surviving set —
    and therefore the visit ledger — cannot depend on tile block size,
    and matches the lax/host twins bit-for-bit."""
    da = _data("a", "uniform_squares", 150)
    ref = SpatialIndex.build(da, structure="mqr", backend="host")
    want = ref.join(ref)
    for backend, opts in (("lax", {}), ("pallas", {}),
                          ("pallas", {"block_w": 32}),
                          ("pallas", {"block_w": 64})):
        idx = SpatialIndex.build(da, structure="mqr", backend=backend,
                                 **opts)
        res = idx.join(idx)
        assert np.array_equal(res.pairs, want.pairs), (backend, opts)
        assert np.array_equal(res.pair_visits, want.pair_visits), (
            backend, opts
        )


def test_self_join_symmetric_compact_and_live():
    """Fast path holds on the compact uint16 grid and across live state
    (delta buffer + tombstones): pairs equal to a full-sweep twin that
    replayed the identical mutations."""
    da = _data("a", "uniform_squares", 140)
    extra = _data("b", "uniform_squares", 12)

    def build():
        idx = SpatialIndex.build(da, structure="mqr", backend="pallas",
                                 precision="compact", capacity=32)
        idx.insert(extra)
        idx.delete(np.arange(6))
        return idx

    idx, twin = build(), build()
    fast = idx.join(idx)
    full = idx.join(twin)
    assert np.array_equal(fast.pairs, full.pairs)
    assert np.array_equal(fast.pairs, oracle_pairs(idx, idx))
    assert fast.sweep_visits.sum() < full.sweep_visits.sum()


# ---------------------------------------------------------------------------
# Property: arbitrary finite geometry on both sides
# ---------------------------------------------------------------------------
# Unlike the module-level ``importorskip`` idiom elsewhere, the guard is a
# plain try/except: the parity matrix above must still run where the dev
# extras are absent — only the property test downgrades to a skip.

try:
    from hypothesis import given, settings, strategies as st

    _HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dependency
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    _coord = st.floats(
        min_value=-1e6, max_value=1e6, allow_nan=False,
        allow_infinity=False, width=32,
    )
    _rect = st.tuples(_coord, _coord, _coord, _coord).map(
        lambda t: (min(t[0], t[2]), min(t[1], t[3]),
                   max(t[0], t[2]), max(t[1], t[3]))
    )

    # Fixed sizes so the jitted pair sweeps compile once across examples.
    _N_A, _N_B = 16, 12

    @settings(max_examples=25, deadline=None)
    @given(
        rects_a=st.lists(_rect, min_size=_N_A, max_size=_N_A),
        rects_b=st.lists(_rect, min_size=_N_B, max_size=_N_B),
        builder=st.sampled_from(["mqr", "rtree"]),
    )
    def test_join_matches_oracle_on_adversarial_geometry(rects_a, rects_b,
                                                         builder):
        """For arbitrary finite geometry (huge magnitudes, degenerate and
        co-located boxes) the join equals brute-force float32 overlap on
        the exact AND compact paths — the sweep may only
        over-approximate, and the confirming pass restores exactness."""
        da = np.asarray(rects_a, np.float64)
        db = np.asarray(rects_b, np.float64)
        left = SpatialIndex.build(da, structure=builder, backend="pallas")
        right = SpatialIndex.build(db, structure=builder, backend="host")
        expect = _overlap_np(
            np.asarray(da, np.float32)[:, None, :],
            np.asarray(db, np.float32)[None, :, :],
        )
        assert np.array_equal(left.join(right).pairs, expect)
        compact = SpatialIndex.build(
            da, structure=builder, backend="pallas", precision="compact"
        )
        assert np.array_equal(compact.join(right).pairs, expect)
else:
    @pytest.mark.skip(reason="pip install -r requirements-dev.txt")
    def test_join_matches_oracle_on_adversarial_geometry():
        pass
