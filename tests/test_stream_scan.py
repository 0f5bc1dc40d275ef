"""HBM-streaming fused sweep == VMEM-resident sweep, bit for bit.

DESIGN.md §12's contract: the double-buffered streaming schedule of
``pyramid_scan(..., stream=True)`` — MBR tiles DMA'd HBM→VMEM two slots
deep while the previous tile computes, survivor masks ping-ponged through
HBM scratch windows — changes WHERE bytes live, never WHAT the sweep
computes.  Hits AND per-level visit counts stay bit-identical to the
VMEM path on every dataset shape × structure × engine rung (fused kernel,
lax twin, numpy twin), including Hilbert-permuted schedules (which
exercise the conservative full-width window fallback) and live delta
levels on the memory-bounded twins.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import conftest
from repro.index import SpatialIndex
from repro.kernels import fallback, ops

_SIZES = {
    "uniform_squares": 300,
    # the paper's zero-overlap case: degenerate point MBRs (§4)
    "uniform_points": 256,
    "exponential_squares": 250,
}
STRUCTURES = ("mqr", "rtree", "pyramid")


def _data(name):
    return conftest.mbr_dataset("test_stream_scan", name, _SIZES[name])


def _queries(name):
    return conftest.dataset_queries("test_stream_scan", name, _SIZES[name])


def _schedule(name, structure):
    idx = SpatialIndex.build(_data(name), structure=structure, backend="pallas")
    return idx.artifacts.schedule


# ---------------------------------------------------------------------------
# The fused kernel: streamed == VMEM on the full matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(_SIZES))
@pytest.mark.parametrize("structure", STRUCTURES)
def test_stream_kernel_bit_identical(name, structure):
    sched = _schedule(name, structure)
    qs = _queries(name)
    hits, visits = ops.pyramid_scan(sched, qs, interpret=True)
    s_hits, s_visits = ops.pyramid_scan(sched, qs, interpret=True, stream=True)
    assert np.array_equal(np.asarray(s_hits), np.asarray(hits))
    assert np.array_equal(np.asarray(s_visits), np.asarray(visits))


@pytest.mark.parametrize("block_w", [64, 256])
def test_stream_kernel_block_w_invariant(block_w):
    """Tile width changes the DMA schedule (number of steps, window
    rounding), never the answers."""
    sched = _schedule("uniform_squares", "mqr")
    qs = _queries("uniform_squares")
    hits, visits = ops.pyramid_scan(sched, qs, interpret=True)
    s_hits, s_visits = ops.pyramid_scan(
        sched, qs, interpret=True, stream=True, block_w=block_w
    )
    assert np.array_equal(np.asarray(s_hits), np.asarray(hits))
    assert np.array_equal(np.asarray(s_visits), np.asarray(visits))


@pytest.mark.parametrize("structure", STRUCTURES)
def test_stream_compact_bit_identical(structure):
    """Streaming composes with the uint16 compact form: same integer
    sweep, tiles just arrive by DMA."""
    sched = _schedule("uniform_squares", structure)
    qs = _queries("uniform_squares")
    qsched = ops.quantize_schedule(sched, interpret=True)
    hits, visits = ops.pyramid_scan_compact(qsched, qs, interpret=True)
    s_hits, s_visits = ops.pyramid_scan_compact(
        qsched, qs, interpret=True, stream=True
    )
    assert np.array_equal(np.asarray(s_hits), np.asarray(hits))
    assert np.array_equal(np.asarray(s_visits), np.asarray(visits))


def test_stream_hilbert_full_width_window():
    """A Hilbert-permuted schedule scatters parents, forcing the streamed
    survivor window to its conservative full-width fallback — answers
    must still be bit-identical."""
    data = _data("uniform_squares")
    qs = _queries("uniform_squares")
    plain = SpatialIndex.build(data, structure="mqr", backend="pallas")
    hil = SpatialIndex.build(
        data, structure="mqr", backend="pallas", order="hilbert",
        backend_opts={"stream": True},
    )
    ref = plain.region(qs)
    res = hil.region(qs)
    assert np.array_equal(res.hits, ref.hits)
    assert np.array_equal(res.visits_per_level, ref.visits_per_level)


# ---------------------------------------------------------------------------
# parent_windows: the host-side window plan the DMA schedule trusts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("structure", STRUCTURES)
@pytest.mark.parametrize("block_w", [64, 128])
def test_parent_windows_cover_all_real_parents(structure, block_w):
    """Every real slot's parent row lies inside its tile's declared
    window — the invariant that makes the windowed survivor gather safe."""
    sched = _schedule("uniform_squares", structure)
    win_off, win_w = ops.parent_windows(
        sched.parent, sched.n_real, block_w=block_w
    )
    levels, width = sched.parent.shape
    n_tiles = win_off.shape[1]
    assert win_off.shape == (levels, n_tiles)
    for l in range(1, levels):
        nr = int(sched.n_real[l])
        for t in range(n_tiles):
            s0, s1 = t * block_w, min((t + 1) * block_w, nr)
            if s0 >= nr:
                continue
            parents = np.asarray(sched.parent[l, s0:s1], np.int64)
            off = int(win_off[l, t])
            assert (parents >= off).all() and (parents < off + win_w).all()


def test_stream_requires_windows_at_kernel_level():
    """The private sweep refuses stream=True without a window plan (the
    public wrappers always compute one)."""
    sched = _schedule("uniform_squares", "mqr")
    qs = _queries("uniform_squares")
    from repro.kernels.ops import level_sweep

    with pytest.raises(ValueError, match="win_off"):
        level_sweep(
            jnp.asarray(qs), jnp.asarray(sched.mbr_cm),
            jnp.asarray(sched.parent), interpret=True, stream=True,
        )


# ---------------------------------------------------------------------------
# Degradation twins: the memory-bounded streamed sweep (lax and numpy)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(_SIZES))
def test_twin_stream_parity_float32(name):
    sched = _schedule(name, "mqr")
    qs = _queries(name)
    args = (
        sched.mbr_cm, sched.parent, sched.obj_mbr, sched.obj_level,
        sched.obj_slot, sched.obj_id,
    )
    kwargs = dict(
        n_objects=sched.n_objects,
        root_unconditional=sched.root_unconditional,
        test_object_mbr=sched.test_object_mbr,
    )
    for fn in (fallback.fused_search_lax, fallback.fused_search_np):
        h0, v0, _ = fn(qs, *args, **kwargs)
        h1, v1, _ = fn(qs, *args, stream=True, **kwargs)
        assert np.array_equal(np.asarray(h1), np.asarray(h0))
        assert np.array_equal(np.asarray(v1), np.asarray(v0))


def test_twin_stream_parity_compact():
    sched = _schedule("uniform_squares", "pyramid")
    qs = _queries("uniform_squares")
    q = ops.quantize_schedule(sched, interpret=True)
    args = (
        q.mbr_q, q.parent_q, q.confirm_mbr, sched.obj_level, sched.obj_slot,
        sched.obj_id, q.origin, q.inv_cell,
    )
    kwargs = dict(
        n_objects=sched.n_objects, cells=q.cells,
        root_unconditional=sched.root_unconditional,
    )
    for fn in (fallback.fused_search_compact_lax, fallback.fused_search_compact_np):
        h0, v0 = fn(qs, *args, **kwargs)
        h1, v1 = fn(qs, *args, stream=True, **kwargs)
        assert np.array_equal(np.asarray(h1), np.asarray(h0))
        assert np.array_equal(np.asarray(v1), np.asarray(v0))


def test_twin_stream_parity_live_delta_levels():
    """Streamed twins honor the live layout: unconditional flat delta
    levels past base_levels, tombstone masking — same answers."""
    sched = _schedule("uniform_squares", "mqr")
    qs = _queries("uniform_squares")
    levels, width = sched.parent.shape
    n = sched.n_objects
    sent = np.array([np.inf, np.inf, -np.inf, -np.inf], np.float32)
    delta = np.broadcast_to(sent[None, :, None], (1, 4, width)).copy()
    delta[0, :, 0] = [0.0, 0.0, 1e9, 1e9]  # one delta row overlapping all
    mbr = np.concatenate([sched.mbr_cm, delta], 0)
    parent = np.concatenate([sched.parent, np.zeros((1, width), np.int32)], 0)
    obj_mbr = np.concatenate([sched.obj_mbr, delta[0][:, :1].T], 0)
    obj_level = np.concatenate([sched.obj_level, [levels]])
    obj_slot = np.concatenate([sched.obj_slot, [0]])
    obj_id = np.concatenate([sched.obj_id, [n]])
    alive = np.ones(n + 1, bool)
    alive[0] = False  # one tombstone
    kwargs = dict(
        n_objects=n + 1, base_levels=levels,
        root_unconditional=sched.root_unconditional,
        test_object_mbr=sched.test_object_mbr,
    )
    for fn in (fallback.fused_search_live_lax, fallback.fused_search_live_np):
        h0, v0 = fn(qs, mbr, parent, obj_mbr, obj_level, obj_slot, obj_id,
                    alive, **kwargs)
        h1, v1 = fn(qs, mbr, parent, obj_mbr, obj_level, obj_slot, obj_id,
                    alive, stream=True, **kwargs)
        assert np.array_equal(np.asarray(h1), np.asarray(h0))
        assert np.array_equal(np.asarray(v1), np.asarray(v0))
        h0 = np.asarray(h0)
        assert h0[:, n].all()      # the delta row hits every query
        assert not h0[:, 0].any()  # the tombstone never does


# ---------------------------------------------------------------------------
# Façade plumb: backend_opts carries the stream flag end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("structure", STRUCTURES)
@pytest.mark.parametrize("precision", ["float32", "compact"])
def test_facade_stream_matrix(structure, precision):
    data = _data("uniform_squares")
    qs = _queries("uniform_squares")
    idx = SpatialIndex.build(data, structure=structure, backend="pallas")
    ref = idx.region(qs)
    streamed = idx.with_backend(
        "pallas", stream=True, precision=precision
    ).region(qs)
    assert np.array_equal(streamed.hits, ref.hits)
    if precision == "float32":
        assert np.array_equal(streamed.visits_per_level, ref.visits_per_level)


def test_stream_compact8_rejected():
    data = _data("uniform_squares")
    with pytest.raises(ValueError, match="compact8"):
        SpatialIndex.build(
            data, backend="pallas",
            backend_opts={"stream": True, "precision": "compact8"},
        )
