"""A pyramid launch that returns hit ids answers as the dense mask does.

The ``pallas`` adapter answers every float32 pyramid launch from
compacted hit ids (DESIGN.md §12).  Rows, visits and the object test's
counts must equal the dense launch's, bit for bit, on isolating and
shallow pyramids, stacked duplicates, empty queries and padded batches.
Where a launch needs more than a capacity, the same program returns the
dense mask instead: a zoomed-out batch overflows the slot capacity by
itself, and each capacity is forced to its batch's need (the ids hold)
and one below it (the dense mask holds).  What a launch needs is counted
here from the sweep's deepest level.
"""
import dataclasses

import numpy as np
import pytest

from conftest import f32_exact
from repro.core import bulk, datasets
from repro.index import SpatialIndex
from repro.kernels import ops
from repro.kernels import pyramid_scan as ps
from test_pyramid_exact import _queries, bit_boxes, brute

N = 3000
BLOCK_W = 128
QB = 16
CHUNK = 16


def _data(name):
    if name == "bit":
        return bit_boxes(N, digits=6, seed=81)
    return f32_exact(datasets.uniform_squares(N, seed=82))


_INDEX = {}


def _index(name):
    """``(data, device-built pyramid index)``: ``shallow`` is uniform
    data two levels short of isolating every object."""
    if name not in _INDEX:
        data = _data(name)
        levels = bulk.default_levels(N) + {"uniform": 4, "bit": 0,
                                           "shallow": -2}[name]
        _INDEX[name] = data, SpatialIndex.build(
            data, structure="pyramid", backend="host", build="device",
            levels=levels)
    return _INDEX[name]


def _batch(data, kind):
    if kind == "empty":
        return np.tile(np.array([[2000.0, 2000.0, 2001.0, 2001.0]],
                                np.float32), (6, 1))
    if kind == "zoomed_out":   # every query sees most of the extent
        return datasets.dense_region_queries(QB, seed=84, side=900.0
                                             ).astype(np.float32)
    qs = _queries(data, seed=83, count=14)
    return qs[:5] if kind == "padded" else qs


def _needs(staged, qs, run):
    """``(blocks, slots, chunks)`` the launch of ``qs`` compacts: its
    non-empty column blocks, active deepest slots and member chunks."""
    act = np.asarray(ops.level_sweep(
        np.asarray(qs, np.float32), *staged.arrays[:2],
        block_w=run["block_w"], root_unconditional=False, interpret=True,
        padded=True)[-1]) != 0
    sched = staged.source
    size = np.bincount(sched.obj_slot, minlength=act.shape[1])
    slots = np.nonzero(act)[1]
    return (np.unique(slots // run["block_w"]).size, slots.size,
            int((-(-size[slots] // CHUNK)).sum()))


# (data, batch, capacity forced, how: "at" sets it to the batch's need,
# "over" one below it)
CASES = {
    "uniform": ("uniform", "full", None, None),
    "stacked": ("bit", "full", None, None),
    "shallow": ("shallow", "full", None, None),
    "empty": ("uniform", "empty", None, None),
    "padded": ("bit", "padded", None, None),
    "zoomed_out": ("uniform", "zoomed_out", None, None),
    "blocks_at": ("uniform", "full", "blocks", "at"),
    "blocks_over": ("uniform", "full", "blocks", "over"),
    "slots_at": ("bit", "full", "slots", "at"),
    "slots_over": ("bit", "full", "slots", "over"),
    "chunks_at": ("bit", "full", "chunks", "at"),
    "chunks_over": ("bit", "full", "chunks", "over"),
}


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "stream"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_ids_answer_as_the_dense_mask(case, stream):
    name, kind, forced, how = CASES[case]
    data, base = _index(name)
    sched = base.artifacts.schedule
    qs = _batch(data, kind)
    n = qs.shape[0]
    want = brute(data, qs)
    staged = ops.stage_schedule(sched)
    run = dict(block_w=BLOCK_W, interpret=True, stream=stream, pad_to=QB)
    hits, visits, confirm = (None if a is None else np.asarray(a)
                             for a in ops.scan_staged(staged, qs, **run))
    assert np.array_equal(hits[:n], want)
    needs = _needs(staged, qs, run)
    caps = ops.ids_caps(staged, QB, BLOCK_W)
    over = forced is None and any(x > c for x, c in zip(
        needs, (caps[0], caps[1], caps[3])))
    assert over == (kind == "zoomed_out")

    if forced is None:
        # the adapter: one launch, answered from ids unless it overflows
        idx = base.with_backend("pallas", interpret=True, stream=stream,
                                query_block=QB, block_w=BLOCK_W)
        got = idx.region(qs)
        assert np.array_equal(got.hits, want)
        assert np.array_equal(got.visits_per_level, visits[:n])
        s = idx.stats
        assert (s.compact_launches, s.compact_overflows) == (1, int(over))
        assert s.padded_queries == QB - n
        cand, ok = (0, 0) if confirm is None else confirm[:n].sum(axis=0)
        assert (s.confirm_candidates, s.confirm_hits) == (cand, ok)
        if name != "uniform":
            assert sched.n_shared > 0 and cand > 0
    else:
        at = {"blocks": 0, "slots": 1, "chunks": 3}[forced]
        caps = list(caps)
        caps[at] = needs[(0, 1, None, 2)[at]] - (how == "over")
        over = how == "over"

    v, c, offsets, overflow, ids, dense = (
        None if a is None else np.asarray(a)
        for a in ops.scan_staged_ids(staged, qs, caps=tuple(caps), **run))
    assert bool(overflow) == over
    assert np.array_equal(v, visits)
    if confirm is None:
        assert c is None and sched.n_shared == 0
    else:
        assert np.array_equal(c, confirm)
    if over:
        assert np.array_equal(dense, hits)
        assert not offsets.any() and (ids == -1).all()
        return
    assert not dense.any()
    rows = np.zeros(hits.shape, bool)
    for q in range(QB):
        lane = ids[offsets[q]:offsets[q + 1]]
        rows[q, lane[lane >= 0]] = True
    assert np.array_equal(rows, hits)
    assert offsets[-1] == offsets[n]             # padding: no lanes
    if kind == "empty":
        assert offsets[-1] == 0


def test_member_table_groups_entries_by_slot():
    data, base = _index("bit")
    sched = base.artifacts.schedule
    staged = ops.stage_schedule(sched)
    slot_start, mem_id, *mbr = (np.asarray(a) for a in staged.members())
    member = np.argsort(sched.obj_slot, kind="stable")
    assert slot_start.shape == (sched.width + 1,)
    assert np.array_equal(np.diff(slot_start),
                          np.bincount(sched.obj_slot, minlength=sched.width))
    assert np.array_equal(mem_id, sched.obj_id[member])
    assert np.array_equal(np.stack(mbr, axis=1), sched.obj_mbr[member])
    assert staged.members() is staged.members()


def test_ids_engage_for_every_pyramid():
    data, base = _index("uniform")
    sched = base.artifacts.schedule
    pyramid = ops.stage_schedule(sched)
    assert pyramid.members() is not None
    # each capacity is its ceiling or what the launch can hold, if less,
    # and the id lanes never outnumber the mask's entries
    small = ops.stage_schedule(SpatialIndex.build(
        data[:200], structure="pyramid", backend="host", build="device"
    ).artifacts.schedule)
    ceilings = (ps.IDS_BLOCKS, ps.IDS_SLOTS, ps.IDS_CHUNK, ps.IDS_CHUNKS)
    for staged in (pyramid, small):
        width, n = staged.source.width, staged.source.n_objects
        hold = (-(-width // BLOCK_W), QB * width, CHUNK, QB * n // CHUNK)
        caps = ops.ids_caps(staged, QB, BLOCK_W)
        assert caps == tuple(map(min, hold, ceilings))
    assert caps == hold and ops.ids_caps(pyramid, QB, BLOCK_W) != hold
    # trees, compact schedules and a pyramid whose shared count disagrees
    # with its slots keep the dense launch
    tree = ops.stage_schedule(SpatialIndex.build(
        data[:500], structure="mqr", backend="host").artifacts.schedule)
    assert tree.members() is None
    off = dataclasses.replace(sched, n_shared=1)
    assert ops.stage_schedule(off).members() is None
    compact = ops.stage_schedule(base.artifacts.quantized, "compact")
    assert compact.members() is None
