"""Pallas TPU kernel: levelized tree-vs-tree spatial join (one launch).

``pyramid_scan`` sweeps ONE schedule against a resident query batch; this
kernel sweeps TWO :class:`repro.core.flat.LevelSchedule`s against each
other (DESIGN.md §10).  Both sides advance level-synchronized through one
``pallas_call``:

* grid = (K, A-tiles, B-tiles) with ``K = min(levels_a, levels_b)`` —
  levels iterate in the outer grid dimension, so level ``k`` sees level
  ``k-1``'s surviving PAIRS;
* the per-level pair survivor masks live in two VMEM scratch buffers
  (``prev``/``cur``, each (Wa, Wb)) that persist across grid steps;
* both sides' MBR tiles stream coordinate-major (4, block) — one A-tile ×
  B-tile fetch = one tile-pair test, the join analogue of the paper's
  disk access;
* the pair recurrence

      P[k, a, b] = P[k-1, parent_a(a), parent_b(b)] & overlaps(A[k,a], B[k,b])

  prunes exactly like the single-index sweep: a node pair survives only
  if its parent pair did.  The double parent gather is expressed as two
  one-hot matmuls (``onehotA^T @ prev @ onehotB``) so it runs on the MXU;
* level 0 tests the root-pair MBR overlap for EVERY schedule flavour —
  root MBRs contain all their objects, so this is conservative for
  ``root_unconditional`` trees too, and padded sentinel slots can never
  activate.

The sweep is only required to be CONSERVATIVE: the epilogue looks up each
entry pair at the deepest level where both sides still have proper
ancestors (``k = min(entry_level_a, entry_level_b)``, via precomputed
ancestor-slot chains from :func:`repro.core.flat.ancestor_chains`) and
then runs an exact float32 object-MBR confirming pass over the candidate
set.  Any true object pair keeps all its synchronized ancestor pairs
overlapping (both ancestors contain the shared intersection point), so no
true pair is ever pruned, and the confirmed pair-set is bit-identical to
the brute-force O(n·m) nested-loop oracle by construction — for float32
AND uint16 tiles (tests/test_join.py).  Tile precision only moves the
pair-visit counts.

VMEM ceiling: the pair masks cost ``2 · Wa · Wb · 4`` bytes of scratch,
so both level widths together must fit (~3k × 3k within the 100 MiB
``VMEM_LIMIT_BYTES``, with the one-hot gathers); past that the mask
itself needs block-pair tiling (ROADMAP Queue 2 item 3).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flat import NEVER_MBR, Q_NEVER_MBR, _overlaps

from .pyramid_scan import COMPILER_PARAMS


def _pair_overlap_tile(a_tile, b_tile):
    """(4, BA) × (4, BB) coordinate-major tiles -> (BA, BB) int32 0/1
    closed-boundary pair overlap.  Tiles are cast to float32 after the
    VMEM load (uint16 grid cells are exact in float32), so one comparison
    path serves the float32 and compact precisions and HBM only streams
    the narrow form."""
    a = a_tile.astype(jnp.float32)
    b = b_tile.astype(jnp.float32)
    alx, aly, ahx, ahy = a[0][:, None], a[1][:, None], a[2][:, None], a[3][:, None]
    blx, bly, bhx, bhy = b[0][None, :], b[1][None, :], b[2][None, :], b[3][None, :]
    ov = (alx <= bhx) & (blx <= ahx) & (aly <= bhy) & (bly <= ahy)
    return ov.astype(jnp.int32)


def _pair_sweep_kernel(
    a_ref,       # (1, 4, BA) tile of side A, level k
    pa_ref,      # (1, 1, BA) parent slots of side A, level k
    b_ref,       # (1, 4, BB) tile of side B, level k
    pb_ref,      # (1, 1, BB) parent slots of side B, level k
    act_ref,     # out (1, BA, BB) int8
    prev_ref,    # scratch (Wa, Wb) f32 — level k-1 surviving pairs
    cur_ref,     # scratch (Wa, Wb) f32 — level k surviving pairs
    *,
    block_a: int,
    block_b: int,
    width_a: int,
    width_b: int,
    onehot_gather: bool,
    symmetric: bool,
):
    k = pl.program_id(0)
    ta = pl.program_id(1)
    tb = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(ta * block_a, block_a), block_a)
    cols = pl.ds(pl.multiple_of(tb * block_b, block_b), block_b)

    @pl.when((k > 0) & (ta == 0) & (tb == 0))
    def _roll():  # level finished: its pair survivors become the parent mask
        if symmetric:
            # Only the upper triangle was swept, but a child pair's
            # parent slots may land BELOW the diagonal — mirror the
            # survivors so the gather sees the full symmetric mask.
            c = cur_ref[...]
            prev_ref[...] = jnp.maximum(c, c.T)
        else:
            prev_ref[...] = cur_ref[...]

    def _tile_body():
        ov = _pair_overlap_tile(a_ref[0], b_ref[0])  # (BA, BB)

        pa_row = pa_ref[0].astype(jnp.int32)  # (1, BA)
        pb_row = pb_ref[0].astype(jnp.int32)  # (1, BB)
        if onehot_gather:
            # TPU path: prev[pa, pb] as onehotA^T @ prev @ onehotB — two
            # MXU matmuls instead of a two-axis lane gather.
            ia = jax.lax.broadcasted_iota(jnp.int32, (width_a, block_a), 0)
            oa = (ia == pa_row).astype(jnp.float32)  # (Wa, BA)
            ib = jax.lax.broadcasted_iota(jnp.int32, (width_b, block_b), 0)
            ob = (ib == pb_row).astype(jnp.float32)  # (Wb, BB)
            pp = jnp.dot(
                oa.T,
                jnp.dot(prev_ref[...], ob,
                        preferred_element_type=jnp.float32),
                preferred_element_type=jnp.float32,
            )
        else:
            # Interpreter path: O(BA·Wb + BA·BB) two-stage take.
            pp = jnp.take(
                jnp.take(prev_ref[...], pa_row[0], axis=0), pb_row[0], axis=1
            )
        parent_active = (pp > 0.5).astype(jnp.int32)

        # int32 select: Mosaic cannot select between boolean vectors.
        act = jnp.where(k == 0, ov, parent_active & ov)
        if symmetric:
            # Self-join: the pair mask is symmetric at every level, so
            # only slot pairs with ga <= gb are swept.  The mask is at
            # SLOT granularity (not tile granularity) so the surviving
            # set is independent of block size — the lax/np twins apply
            # the identical triu and stay bit-compatible.
            ga = ta * block_a + jax.lax.broadcasted_iota(
                jnp.int32, (block_a, block_b), 0
            )
            gb = tb * block_b + jax.lax.broadcasted_iota(
                jnp.int32, (block_a, block_b), 1
            )
            act = act & (ga <= gb).astype(jnp.int32)
        cur_ref[rows, cols] = act.astype(jnp.float32)
        act_ref[0] = act.astype(jnp.int8)

    if symmetric:
        # Tiles strictly below the diagonal hold no ga <= gb slot pair:
        # skip the overlap compute and parent gather entirely (this is
        # the ~half-work saving), but still zero their act/cur region so
        # the mirrored roll and the epilogue never read garbage.
        @pl.when(tb < ta)
        def _skip_lower():
            cur_ref[rows, cols] = jnp.zeros((block_a, block_b), jnp.float32)
            act_ref[0] = jnp.zeros((block_a, block_b), jnp.int8)

        @pl.when(tb >= ta)
        def _upper():
            _tile_body()
    else:
        _tile_body()


def _pad_side(mbr_cm, parent, block):
    """Pad one side's level tiles to a block multiple with never-overlap
    sentinels (float32 or uint16 grid form) and zero parents."""
    levels, _, w = mbr_cm.shape
    pad = (-w) % block
    if pad:
        never = (
            NEVER_MBR
            if jnp.issubdtype(mbr_cm.dtype, jnp.floating)
            else Q_NEVER_MBR.astype(mbr_cm.dtype)
        )
        mbr_cm = jnp.concatenate(
            [mbr_cm,
             jnp.broadcast_to(jnp.asarray(never)[None, :, None],
                              (levels, 4, pad))],
            axis=2,
        )
        parent = jnp.concatenate(
            [parent, jnp.zeros((levels, pad), parent.dtype)], axis=1
        )
    return mbr_cm, parent, w + pad


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_a", "block_b", "interpret", "onehot_gather", "symmetric"
    ),
)
def pair_sweep(
    a_cm,      # (K, 4, Wa) level tiles of side A (f32 or uint16)
    a_parent,  # (K, Wa) int parent slots of side A
    b_cm,      # (K, 4, Wb) level tiles of side B
    b_parent,  # (K, Wb) int parent slots of side B
    *,
    block_a: int = 128,
    block_b: int = 128,
    interpret: bool = False,
    onehot_gather: bool | None = None,
    symmetric: bool = False,
):
    """Run the fused pair sweep; returns the (K, Wa, Wb) pair-active mask.

    ``symmetric=True`` is the self-join fast path: both sides MUST be the
    same schedule, the sweep tests only slot pairs with ``ga <= gb``
    (strict-lower tiles are skipped — half the tile-pair work), and the
    returned mask holds only the upper triangle per level.  Mirror with
    ``act | act.transpose(0, 2, 1)`` to recover the full mask (the
    epilogue does this when told the join is symmetric).
    """
    k_levels, _, wa = a_cm.shape
    kb, _, wb = b_cm.shape
    assert k_levels == kb, "both sides must be trimmed to the same K levels"
    if symmetric:
        assert wa == wb and block_a == block_b, (
            "symmetric sweep requires identical widths and blocks"
        )
    a_cm, a_parent, wa_p = _pad_side(a_cm, a_parent, block_a)
    b_cm, b_parent, wb_p = _pad_side(b_cm, b_parent, block_b)
    if onehot_gather is None:
        onehot_gather = not interpret
    kernel = functools.partial(
        _pair_sweep_kernel,
        block_a=block_a,
        block_b=block_b,
        width_a=wa_p,
        width_b=wb_p,
        onehot_gather=onehot_gather,
        symmetric=symmetric,
    )
    act = pl.pallas_call(
        kernel,
        grid=(k_levels, wa_p // block_a, wb_p // block_b),
        in_specs=[
            pl.BlockSpec((1, 4, block_a), lambda k, ta, tb: (k, 0, ta)),
            pl.BlockSpec((1, 1, block_a), lambda k, ta, tb: (k, 0, ta)),
            pl.BlockSpec((1, 4, block_b), lambda k, ta, tb: (k, 0, tb)),
            pl.BlockSpec((1, 1, block_b), lambda k, ta, tb: (k, 0, tb)),
        ],
        out_specs=pl.BlockSpec((1, block_a, block_b),
                               lambda k, ta, tb: (k, ta, tb)),
        out_shape=jax.ShapeDtypeStruct((k_levels, wa_p, wb_p), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((wa_p, wb_p), jnp.float32),
            pltpu.VMEM((wa_p, wb_p), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(a_cm, a_parent.reshape(k_levels, 1, wa_p),
      b_cm, b_parent.reshape(k_levels, 1, wb_p))
    return act[:, :wa, :wb] != 0


def join_epilogue(
    act,                       # (K, Wa, Wb) pair-active mask
    a_anc, a_level, a_gid,     # (Ea, K) chains, (Ea,) levels, (Ea,) global ids
    b_anc, b_level, b_gid,
    table_a, table_b,          # (Na, 4) / (Nb, 4) f32 global-id MBR tables
    alive_a, alive_b,          # (Na,) / (Nb,) bool tombstone masks
    delta_a, delta_b,          # (Na,) / (Nb,) bool delta-buffer candidate rows
    *,
    symmetric: bool = False,   # act holds only the upper triangle per level
):
    """Candidate lookup + exact confirming pass, shared by every engine.

    Entry pair (ea, eb) is a candidate iff the pair mask is active at
    ``k = min(level_a, level_b)`` — the deepest synchronized level where
    both entries still have proper ancestors (their ancestor slots come
    from the precomputed chains).  Delta-buffer rows bypass the structure
    sweep entirely: every pair touching one is a candidate (the flat
    cross-scan of DESIGN.md §10 — the buffer is O(capacity) rows, so
    structural pruning buys nothing the exact pass doesn't).  The exact
    float32 overlap ∧ tombstone masks then make the result bit-identical
    to the brute-force oracle.  Runs under jit (jnp inputs) and as plain
    numpy (host rung) unchanged — index/compare ops only.
    """
    ea = a_level.shape[0]
    eb = b_level.shape[0]
    xp = np if isinstance(act, np.ndarray) else jnp
    sweep_act = act  # unmirrored: the ledger counts pairs actually TESTED
    if symmetric:
        # Upper-triangle sweep: entry pairs gather at arbitrary (sa, sb)
        # order, so mirror the mask for the candidate lookup.
        act = act | act.transpose(0, 2, 1)
    k_ab = xp.minimum(a_level[:, None], b_level[None, :])        # (Ea, Eb)
    sa = a_anc[xp.arange(ea)[:, None], k_ab]
    sb = b_anc[xp.arange(eb)[None, :], k_ab]
    cand = act[k_ab, sa, sb]                                     # (Ea, Eb)
    n_a = table_a.shape[0]
    n_b = table_b.shape[0]
    if xp is jnp:
        pairs = jnp.zeros((n_a, n_b), jnp.bool_)
        pairs = pairs.at[a_gid[:, None], b_gid[None, :]].max(cand)
    else:
        pairs = xp.zeros((n_a, n_b), bool)
        xp.maximum.at(pairs, (a_gid[:, None], b_gid[None, :]), cand)
    pairs = pairs | delta_a[:, None] | delta_b[None, :]
    exact = _overlaps(table_a[:, None, :], table_b[None, :, :])
    pairs = pairs & exact & alive_a[:, None] & alive_b[None, :]
    # Pair-test ledger: per-level tile-pair survivors from the sweep, then
    # one column per side for the delta cross-scan's exact tests.
    visits = xp.concatenate([
        sweep_act.sum(axis=(1, 2), dtype=xp.int32),
        xp.stack([
            delta_a.sum(dtype=xp.int32) * alive_b.sum(dtype=xp.int32),
            delta_b.sum(dtype=xp.int32) * alive_a.sum(dtype=xp.int32),
        ]),
    ])
    return pairs, visits


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "interpret", "symmetric")
)
def _fused_join(
    a_cm, a_parent, a_anc, a_level, a_gid,
    b_cm, b_parent, b_anc, b_level, b_gid,
    table_a, table_b, alive_a, alive_b, delta_a, delta_b,
    *,
    block_a: int,
    block_b: int,
    interpret: bool,
    symmetric: bool = False,
):
    """One jit program: pair sweep kernel + candidate/confirm epilogue.

    Returns ``(pairs (Na, Nb) bool, visits (K + 2,) int32)`` — the pair
    set in global-id space and the per-level pair-test ledger.  The same
    entry serves float32 and compact tiles: the caller just streams the
    uint16 joint-grid form for ``precision="compact"`` (the confirming
    pass is always exact float32, DESIGN.md §10).
    """
    act = pair_sweep(
        a_cm, a_parent, b_cm, b_parent,
        block_a=block_a, block_b=block_b, interpret=interpret,
        symmetric=symmetric,
    )
    return join_epilogue(
        act,
        a_anc, a_level, a_gid,
        b_anc, b_level, b_gid,
        table_a, table_b, alive_a, alive_b, delta_a, delta_b,
        symmetric=symmetric,
    )
