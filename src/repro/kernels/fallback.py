"""Degradation-ladder twins of the fused Pallas sweeps (DESIGN.md §9).

When a Pallas lowering or launch fails at serving time, the
:class:`repro.launch.spatial_serve.SpatialServer` retries the query batch
on the next rung of its health ladder:

* **lax rung** — the same level sweep in plain ``jnp`` ops (jit'd XLA, no
  ``pallas_call``), signature-compatible with the fused entry points of
  :mod:`repro.kernels.ops` so the server's vmap/pmap plumbing is reused
  unchanged;
* **host rung** — the same sweep in pure numpy, the last resort when the
  device runtime itself is unavailable.

Every twin reproduces the kernel's recurrence exactly — root slot
unconditional (tree schedules), parent-gated overlap per level, flat
unconditional delta levels from ``uncond_from``, per-object confirming
pass, tombstone mask — so degraded answers are *bit-identical* to the
healthy path's hit sets and per-level visit counts (tests/
test_degradation.py); only latency degrades.

``stream=True`` on any region twin mirrors the HBM-streaming kernel's
access pattern (DESIGN.md §12): only the previous level's survivor mask
stays live and object-entry activity is gathered incrementally per level,
so peak memory is O(Q·W) instead of O(L·Q·W) — the twin that lets the
1e7-object benchmark row run off-kernel with the same bit-identical
answers.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core.flat import confirm_shared
from repro.obs import counters as _obs_counters


def _overlap(a, b):
    """Closed-boundary rectangle intersection, broadcasting; index/compare
    ops only, so one definition serves numpy and traced jnp arrays (and
    the integer grid of the compact path, where <=/& mean the same)."""
    return (
        (a[..., 0] <= b[..., 2])
        & (b[..., 0] <= a[..., 2])
        & (a[..., 1] <= b[..., 3])
        & (b[..., 1] <= a[..., 3])
    )


def _quantize_queries(xp, queries, origin, inv_cell, cells):
    """Outward query quantization of the compact sweep — identical to
    ``pyramid_scan._fused_search_compact`` (floor lo, ceil hi, clip)."""
    t = (queries - origin[None, :]) * inv_cell[None, :]
    qq = xp.concatenate([xp.floor(t[:, :2]), xp.ceil(t[:, 2:])], axis=1)
    return xp.clip(qq, 0.0, float(cells)).astype(xp.int32)


# ---------------------------------------------------------------------------
# Shared sweep cores, parameterized by array namespace (np or jnp)
# ---------------------------------------------------------------------------


def _level_act(xp, ov, prev, parent_l, *, l, nq, w, root_unconditional,
               uncond_from):
    """One level of the sweep recurrence — identical on every rung."""
    if l == 0:
        if root_unconditional and uncond_from > 0:
            if xp is np:
                act = np.zeros((nq, w), bool)
                act[:, 0] = True
            else:
                act = jnp.zeros((nq, w), bool).at[:, 0].set(True)
        else:
            act = ov
    elif l >= uncond_from:
        act = ov  # flat delta level: no parent gate
    else:
        act = ov & xp.take(prev, parent_l, axis=1)
    return act


def _sweep_jnp(queries, mbr_cm, parent, *, root_unconditional, uncond_from):
    """(L, Q, W) active mask — the jnp twin of ``pyramid_scan.level_sweep``."""
    levels, _, w = mbr_cm.shape
    mbr_rm = jnp.transpose(mbr_cm, (0, 2, 1))  # (L, W, 4)
    nq = queries.shape[0]
    uncond_from = levels if uncond_from is None else uncond_from
    acts = []
    prev = None
    for l in range(levels):
        ov = _overlap(mbr_rm[l][None, :, :], queries[:, None, :])  # (Q, W)
        act = _level_act(
            jnp, ov, prev, parent[l], l=l, nq=nq, w=w,
            root_unconditional=root_unconditional, uncond_from=uncond_from,
        )
        acts.append(act)
        prev = act
    return jnp.stack(acts)  # (L, Q, W)


def _sweep_np(queries, mbr_cm, parent, *, root_unconditional, uncond_from):
    levels, _, w = mbr_cm.shape
    mbr_rm = mbr_cm.transpose(0, 2, 1)  # (L, W, 4)
    nq = queries.shape[0]
    uncond_from = levels if uncond_from is None else uncond_from
    acts = np.zeros((levels, nq, w), bool)
    prev = None
    for l in range(levels):
        ov = _overlap(mbr_rm[l][None, :, :], queries[:, None, :])
        acts[l] = _level_act(
            np, ov, prev, parent[l], l=l, nq=nq, w=w,
            root_unconditional=root_unconditional, uncond_from=uncond_from,
        )
        prev = acts[l]
    return acts


def _stream_entry_sweep(xp, qeff, mbr_cm, parent, *, root_unconditional,
                        uncond_from, obj_level, obj_slot):
    """Memory-bounded sweep mirroring the HBM-streaming kernel: only the
    previous level's (Q, W) survivor mask stays live; per-level visit
    counts and object-entry activity are folded out incrementally instead
    of stacking the (L, Q, W) mask.  Returns ``(hit (Q, E), visits
    (Q, L))`` — bit-identical to the stacked path."""
    levels, _, w = mbr_cm.shape
    nq = qeff.shape[0]
    uncond_from = levels if uncond_from is None else uncond_from
    obj_level_h = np.asarray(obj_level)
    obj_slot_h = np.asarray(obj_slot)
    by_level = [np.nonzero(obj_level_h == l)[0] for l in range(levels)]
    n_entries = obj_level_h.shape[0]
    if xp is np:
        hit = np.zeros((nq, n_entries), bool)
    else:
        hit = jnp.zeros((nq, n_entries), bool)
    visits = []
    prev = None
    for l in range(levels):
        rm = mbr_cm[l].T  # (W, 4)
        ov = _overlap(rm[None, :, :], qeff[:, None, :])
        act = _level_act(
            xp, ov, prev, parent[l], l=l, nq=nq, w=w,
            root_unconditional=root_unconditional, uncond_from=uncond_from,
        )
        visits.append(act.sum(axis=1).astype(xp.int32))
        idx = by_level[l]
        if idx.size:
            cols = act[:, obj_slot_h[idx]]
            if xp is np:
                hit[:, idx] = cols
            else:
                hit = hit.at[:, idx].set(cols)
        prev = act
    return hit, xp.stack(visits, axis=1)


def _sweep_hier(xp, qq8, qq16, mbr8, mbr16, parent, *, root_unconditional):
    """(L, Q, W) active mask of the hierarchical (uint8 upper / uint16
    lower) sweep — the rung twin of ``pyramid_scan.level_sweep_hier``."""
    l8 = mbr8.shape[0]
    levels = l8 + mbr16.shape[0]
    nq = qq16.shape[0]
    w = mbr16.shape[2]
    acts = []
    prev = None
    for l in range(levels):
        if l < l8:
            rm = mbr8[l].T.astype(xp.int32)
            ov = _overlap(rm[None, :, :], qq8[:, None, :])
        else:
            rm = mbr16[l - l8].T.astype(xp.int32)
            ov = _overlap(rm[None, :, :], qq16[:, None, :])
        act = _level_act(
            xp, ov, prev, parent[l], l=l, nq=nq, w=w,
            root_unconditional=root_unconditional, uncond_from=levels,
        )
        acts.append(act)
        prev = act
    if xp is np:
        return np.stack(acts)
    return jnp.stack(acts)


def _finish(xp, queries, hit, visits, gate_mbr, obj_id, n_objects,
            alive=None, shared_mbr=None):
    """Shared epilogue: exact confirm gate (or the object test of a
    pyramid's shared entries), global-id scatter, tombstones.  Returns
    ``(hits, visits, confirm)`` as the kernel's ``_hits_epilogue``."""
    confirm = None
    if gate_mbr is not None:
        hit = hit & _overlap(gate_mbr[None, :, :], queries[:, None, :])
    elif shared_mbr is not None:
        hit, confirm = confirm_shared(hit, queries, shared_mbr, xp=xp)
    nq = queries.shape[0]
    if xp is np:
        hits = np.zeros((nq, max(n_objects, 1)), bool)
        np.maximum.at(hits, (slice(None), obj_id), hit)
        visits = visits.astype(np.int32)
    else:
        hits = jnp.zeros((nq, max(n_objects, 1)), jnp.bool_)
        hits = hits.at[:, obj_id].max(hit)
        visits = visits.astype(jnp.int32)
    if alive is not None:
        hits = hits & alive[None, :]
    return hits, visits, confirm


def _twin_search(xp, queries, qeff, mbr_cm, parent, obj_level, obj_slot,
                 obj_id, *, n_objects, root_unconditional, uncond_from,
                 gate_mbr, alive=None, stream=False, shared_mbr=None):
    """One generic region-search rung; every public twin is a thin shell.

    ``qeff`` is what the sweep tests (float32 queries, or their outward
    integer quantization on the compact rungs); ``queries`` stays float32
    for the exact confirming gate."""
    if xp is np and _obs_counters.collecting():
        # the lax twins run jit/vmap-traced, where a host side channel
        # cannot exist — only the eager numpy rung reports launches
        _obs_counters.emit(_obs_counters.host_twin_report(
            queries, mbr_cm, parent, stream=stream))
    if stream:
        hit, visits = _stream_entry_sweep(
            xp, qeff, mbr_cm, parent,
            root_unconditional=root_unconditional, uncond_from=uncond_from,
            obj_level=obj_level, obj_slot=obj_slot,
        )
    else:
        sweep = _sweep_np if xp is np else _sweep_jnp
        act = sweep(
            qeff, mbr_cm, parent,
            root_unconditional=root_unconditional, uncond_from=uncond_from,
        )
        visits = xp.transpose(act.sum(axis=2).astype(xp.int32))
        hit = xp.transpose(act[obj_level, :, obj_slot])
    return _finish(
        xp, queries, hit, visits, gate_mbr, obj_id, n_objects, alive=alive,
        shared_mbr=shared_mbr,
    )


# ---------------------------------------------------------------------------
# lax rung: jnp level sweep, jit/vmap-able, no pallas_call
# ---------------------------------------------------------------------------


def fused_search_lax(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id,
    *, n_objects, block_w=128, root_unconditional=True,
    test_object_mbr=True, n_shared=0, interpret=None, stream=False,
):
    del block_w, interpret  # kernel-only tuning knobs
    return _twin_search(
        jnp, queries, queries, mbr_cm, parent, obj_level, obj_slot, obj_id,
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=None, gate_mbr=obj_mbr if test_object_mbr else None,
        stream=stream, shared_mbr=obj_mbr[:n_shared] if n_shared else None,
    )


def fused_search_live_lax(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id, alive,
    *, n_objects, base_levels, block_w=128, root_unconditional=True,
    test_object_mbr=True, interpret=None, stream=False,
):
    del block_w, interpret
    return _twin_search(
        jnp, queries, queries, mbr_cm, parent, obj_level, obj_slot, obj_id,
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=base_levels,
        gate_mbr=obj_mbr if test_object_mbr else None,
        alive=alive, stream=stream,
    )[:2]


def fused_search_compact_lax(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell,
    *, n_objects, cells, block_w=128, root_unconditional=True,
    interpret=None, stream=False,
):
    del block_w, interpret
    qq = _quantize_queries(jnp, queries, origin, inv_cell, cells)
    return _twin_search(
        jnp, queries, qq, mbr_q.astype(jnp.int32), parent_q.astype(jnp.int32),
        obj_level, obj_slot, obj_id,
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=None, gate_mbr=confirm_mbr, stream=stream,
    )[:2]


def fused_search_compact_live_lax(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell, alive,
    *, n_objects, cells, base_levels, block_w=128, root_unconditional=True,
    interpret=None, stream=False,
):
    del block_w, interpret
    qq = _quantize_queries(jnp, queries, origin, inv_cell, cells)
    return _twin_search(
        jnp, queries, qq, mbr_q.astype(jnp.int32), parent_q.astype(jnp.int32),
        obj_level, obj_slot, obj_id,
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=base_levels, gate_mbr=confirm_mbr, alive=alive,
        stream=stream,
    )[:2]


def fused_search_compact8_lax(
    queries, mbr_q8, mbr_q16, parent_q, confirm_mbr, obj_level, obj_slot,
    obj_id, origin, inv_cell, inv_cell8,
    *, n_objects, cells, cells8, split, block_w=128,
    root_unconditional=True, interpret=None,
):
    """lax rung of :func:`repro.kernels.ops.fused_search_compact8`: the
    hierarchical uint8/uint16 sweep in plain jnp (DESIGN.md §12)."""
    del block_w, interpret
    qq16 = _quantize_queries(jnp, queries, origin, inv_cell, cells)
    if split == 0:
        act = _sweep_jnp(
            qq16, mbr_q16.astype(jnp.int32), parent_q.astype(jnp.int32),
            root_unconditional=root_unconditional, uncond_from=None,
        )
    else:
        qq8 = _quantize_queries(jnp, queries, origin, inv_cell8, cells8)
        act = _sweep_hier(
            jnp, qq8, qq16, mbr_q8, mbr_q16, parent_q.astype(jnp.int32),
            root_unconditional=root_unconditional,
        )
    visits = jnp.transpose(act.sum(axis=2).astype(jnp.int32))
    hit = jnp.transpose(act[obj_level, :, obj_slot])
    return _finish(
        jnp, queries, hit, visits, confirm_mbr, obj_id, n_objects
    )[:2]


# ---------------------------------------------------------------------------
# host rung: the same sweep in pure numpy (no device runtime at all)
# ---------------------------------------------------------------------------


def fused_search_np(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id,
    *, n_objects, block_w=128, root_unconditional=True,
    test_object_mbr=True, n_shared=0, interpret=None, stream=False,
):
    del block_w, interpret
    queries = np.asarray(queries, np.float32)
    obj_mbr = np.asarray(obj_mbr)
    return _twin_search(
        np, queries, queries, np.asarray(mbr_cm), np.asarray(parent),
        np.asarray(obj_level), np.asarray(obj_slot), np.asarray(obj_id),
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=None, gate_mbr=obj_mbr if test_object_mbr else None,
        stream=stream, shared_mbr=obj_mbr[:n_shared] if n_shared else None,
    )


def fused_search_live_np(
    queries, mbr_cm, parent, obj_mbr, obj_level, obj_slot, obj_id, alive,
    *, n_objects, base_levels, block_w=128, root_unconditional=True,
    test_object_mbr=True, interpret=None, stream=False,
):
    del block_w, interpret
    queries = np.asarray(queries, np.float32)
    return _twin_search(
        np, queries, queries, np.asarray(mbr_cm), np.asarray(parent),
        np.asarray(obj_level), np.asarray(obj_slot), np.asarray(obj_id),
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=base_levels,
        gate_mbr=np.asarray(obj_mbr) if test_object_mbr else None,
        alive=np.asarray(alive, bool), stream=stream,
    )[:2]


def fused_search_compact_np(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell,
    *, n_objects, cells, block_w=128, root_unconditional=True,
    interpret=None, stream=False,
):
    del block_w, interpret
    queries = np.asarray(queries, np.float32)
    qq = _quantize_queries(
        np, queries, np.asarray(origin), np.asarray(inv_cell), cells
    )
    return _twin_search(
        np, queries, qq, np.asarray(mbr_q, np.int32),
        np.asarray(parent_q, np.int32),
        np.asarray(obj_level), np.asarray(obj_slot), np.asarray(obj_id),
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=None, gate_mbr=np.asarray(confirm_mbr), stream=stream,
    )[:2]


def fused_search_compact_live_np(
    queries, mbr_q, parent_q, confirm_mbr, obj_level, obj_slot, obj_id,
    origin, inv_cell, alive,
    *, n_objects, cells, base_levels, block_w=128, root_unconditional=True,
    interpret=None, stream=False,
):
    del block_w, interpret
    queries = np.asarray(queries, np.float32)
    qq = _quantize_queries(
        np, queries, np.asarray(origin), np.asarray(inv_cell), cells
    )
    return _twin_search(
        np, queries, qq, np.asarray(mbr_q, np.int32),
        np.asarray(parent_q, np.int32),
        np.asarray(obj_level), np.asarray(obj_slot), np.asarray(obj_id),
        n_objects=n_objects, root_unconditional=root_unconditional,
        uncond_from=base_levels, gate_mbr=np.asarray(confirm_mbr),
        alive=np.asarray(alive, bool), stream=stream,
    )[:2]


def fused_search_compact8_np(
    queries, mbr_q8, mbr_q16, parent_q, confirm_mbr, obj_level, obj_slot,
    obj_id, origin, inv_cell, inv_cell8,
    *, n_objects, cells, cells8, split, block_w=128,
    root_unconditional=True, interpret=None,
):
    """host rung of the hierarchical uint8/uint16 sweep (DESIGN.md §12)."""
    del block_w, interpret
    queries = np.asarray(queries, np.float32)
    qq16 = _quantize_queries(
        np, queries, np.asarray(origin), np.asarray(inv_cell), cells
    )
    if split == 0:
        act = _sweep_np(
            qq16, np.asarray(mbr_q16, np.int32), np.asarray(parent_q, np.int32),
            root_unconditional=root_unconditional, uncond_from=None,
        )
    else:
        qq8 = _quantize_queries(
            np, queries, np.asarray(origin), np.asarray(inv_cell8), cells8
        )
        act = _sweep_hier(
            np, qq8, qq16, np.asarray(mbr_q8), np.asarray(mbr_q16),
            np.asarray(parent_q, np.int32),
            root_unconditional=root_unconditional,
        )
    visits = act.sum(axis=2).T.astype(np.int32)
    hit = act[np.asarray(obj_level), :, np.asarray(obj_slot)].T
    return _finish(
        np, queries, hit, visits, np.asarray(confirm_mbr),
        np.asarray(obj_id), n_objects,
    )[:2]


# ---------------------------------------------------------------------------
# tree-vs-tree join twins (DESIGN.md §10): same rungs for SpatialIndex.join
# ---------------------------------------------------------------------------


def _pair_sweep_jnp(a_cm, a_parent, b_cm, b_parent, symmetric=False):
    """(K, Wa, Wb) pair-active mask — jnp twin of ``join_scan.pair_sweep``.

    Same recurrence: a node pair survives level ``k`` iff its parent pair
    survived ``k-1`` and the two level-``k`` MBRs overlap (level 0 tests
    the root-pair overlap directly — conservative for every schedule
    flavour).  Tiles cast to float32 so uint16 joint-grid tiles take the
    identical path.  ``symmetric`` is the self-join fast path: only slot
    pairs with ``ga <= gb`` are kept per level (the same slot-granularity
    triu the kernel applies — bit-compatible regardless of block size),
    and the parent gather reads the mirrored previous level."""
    k_levels = a_cm.shape[0]
    a = jnp.asarray(a_cm).astype(jnp.float32)
    b = jnp.asarray(b_cm).astype(jnp.float32)
    wa, wb = a.shape[2], b.shape[2]
    triu = None
    if symmetric:
        triu = (
            jnp.arange(wa)[:, None] <= jnp.arange(wb)[None, :]
        )
    acts = []
    prev = None
    for k in range(k_levels):
        al, bl = a[k], b[k]  # (4, Wa) / (4, Wb)
        ov = (
            (al[0][:, None] <= bl[2][None, :])
            & (bl[0][None, :] <= al[2][:, None])
            & (al[1][:, None] <= bl[3][None, :])
            & (bl[1][None, :] <= al[3][:, None])
        )
        if k == 0:
            act = ov
        else:
            gather = prev | prev.T if symmetric else prev
            act = ov & jnp.take(
                jnp.take(gather, a_parent[k], axis=0), b_parent[k], axis=1
            )
        if symmetric:
            act = act & triu
        acts.append(act)
        prev = act
    return jnp.stack(acts)


def _pair_sweep_np(a_cm, a_parent, b_cm, b_parent, symmetric=False):
    k_levels, _, wa = a_cm.shape
    wb = b_cm.shape[2]
    a = np.asarray(a_cm, np.float32)
    b = np.asarray(b_cm, np.float32)
    triu = (
        np.arange(wa)[:, None] <= np.arange(wb)[None, :]
        if symmetric else None
    )
    acts = np.zeros((k_levels, wa, wb), bool)
    for k in range(k_levels):
        al, bl = a[k], b[k]
        ov = (
            (al[0][:, None] <= bl[2][None, :])
            & (bl[0][None, :] <= al[2][:, None])
            & (al[1][:, None] <= bl[3][None, :])
            & (bl[1][None, :] <= al[3][:, None])
        )
        if k == 0:
            acts[k] = ov
        else:
            prev = acts[k - 1]
            if symmetric:
                prev = prev | prev.T
            acts[k] = ov & prev[a_parent[k]][:, b_parent[k]]
        if symmetric:
            acts[k] &= triu
    return acts


def fused_join_lax(
    a_cm, a_parent, a_anc, a_level, a_gid,
    b_cm, b_parent, b_anc, b_level, b_gid,
    table_a, table_b, alive_a, alive_b, delta_a, delta_b,
    *, block_a=128, block_b=128, interpret=None, symmetric=False,
):
    """lax rung of :func:`repro.kernels.ops.fused_join`: plain-XLA pair
    sweep + the shared candidate/confirm epilogue — pair sets AND pair-
    visit ledger bit-identical to the fused kernel."""
    del block_a, block_b, interpret  # kernel-only tuning knobs
    from .join_scan import join_epilogue

    act = _pair_sweep_jnp(a_cm, a_parent, b_cm, b_parent, symmetric)
    return join_epilogue(
        act,
        jnp.asarray(a_anc), jnp.asarray(a_level), jnp.asarray(a_gid),
        jnp.asarray(b_anc), jnp.asarray(b_level), jnp.asarray(b_gid),
        jnp.asarray(table_a), jnp.asarray(table_b),
        jnp.asarray(alive_a), jnp.asarray(alive_b),
        jnp.asarray(delta_a), jnp.asarray(delta_b),
        symmetric=symmetric,
    )


def fused_join_np(
    a_cm, a_parent, a_anc, a_level, a_gid,
    b_cm, b_parent, b_anc, b_level, b_gid,
    table_a, table_b, alive_a, alive_b, delta_a, delta_b,
    *, block_a=128, block_b=128, interpret=None, symmetric=False,
):
    """host rung: the same join in pure numpy (no device runtime)."""
    del block_a, block_b, interpret
    from .join_scan import join_epilogue

    act = _pair_sweep_np(
        np.asarray(a_cm), np.asarray(a_parent),
        np.asarray(b_cm), np.asarray(b_parent), symmetric,
    )
    return join_epilogue(
        act,
        np.asarray(a_anc), np.asarray(a_level), np.asarray(a_gid),
        np.asarray(b_anc), np.asarray(b_level), np.asarray(b_gid),
        np.asarray(table_a, np.float32), np.asarray(table_b, np.float32),
        np.asarray(alive_a, bool), np.asarray(alive_b, bool),
        np.asarray(delta_a, bool), np.asarray(delta_b, bool),
        symmetric=symmetric,
    )


# degradation-ladder rung -> join twin; the pallas rung is
# ``repro.kernels.ops.fused_join`` itself.
JOIN_FALLBACKS = {"lax": fused_join_lax, "host": fused_join_np}


# variant key -> (lax rung fn, host rung fn); the server picks by the
# same (precision, live) pair it used to choose the fused kernel.
FALLBACKS = {
    ("float32", False): (fused_search_lax, fused_search_np),
    ("float32", True): (fused_search_live_lax, fused_search_live_np),
    ("compact", False): (fused_search_compact_lax, fused_search_compact_np),
    ("compact", True): (
        fused_search_compact_live_lax, fused_search_compact_live_np,
    ),
    ("compact8", False): (
        fused_search_compact8_lax, fused_search_compact8_np,
    ),
}
