"""Autotuned tiling for the fused sweep (DESIGN.md §12).

The tuner only ever changes WHICH configuration runs — every candidate is
bit-identical on answers — so the tests pin the selection machinery:
candidate grids always contain the fixed default (tuned can't lose to
fixed), shape keys bucket correctly, timing picks the fastest fake
runner and survives raising candidates, explicit overrides pin the fixed
config without spending tuning time, and winners land in
``BuildArtifacts.tuned`` where ``with_backend`` twins reuse them.
"""
import time

import numpy as np
import pytest

import conftest
from repro.index import SpatialIndex
from repro.kernels.autotune import (
    AUTO_MIN_WIDTH,
    TileConfig,
    candidates,
    shape_key,
    tune,
)

_N = 260


def _data(n=_N):
    return conftest.mbr_dataset("test_autotune", "uniform_squares", n)


def _queries(n=_N):
    return conftest.dataset_queries("test_autotune", "uniform_squares", n)


# ---------------------------------------------------------------------------
# candidate grid
# ---------------------------------------------------------------------------


def test_candidates_always_include_fixed_default():
    for kwargs in (
        dict(precision="float32"),
        dict(precision="compact"),
        dict(precision="compact8"),
        dict(precision="float32", stream=True),
        dict(precision="float32", live=True),
    ):
        cands = candidates(2048, 64, **kwargs)
        assert TileConfig() in cands


def test_candidates_per_level_plan_only_for_plain_float32():
    plain = candidates(2048, 64, precision="float32")
    assert any(not c.levels_in_grid for c in plain)
    for kwargs in (
        dict(precision="compact"),
        dict(precision="compact8"),
        dict(precision="float32", stream=True),
        dict(precision="float32", live=True),
    ):
        assert all(
            c.levels_in_grid for c in candidates(2048, 64, **kwargs)
        )


def test_candidates_block_ws_bounded_by_width():
    # a 200-wide grid never proposes 512-wide tiles (pure padding), and
    # no grid proposes a tile narrower than the chip's 128 lanes
    assert {c.block_w for c in candidates(200, 8)} == {128, 256}
    assert {c.block_w for c in candidates(4096, 8)} == {128, 256, 512}


def test_candidates_query_block_only_for_large_batches():
    assert all(c.query_block is None for c in candidates(2048, 8))
    assert any(c.query_block == 32 for c in candidates(2048, 100))


# ---------------------------------------------------------------------------
# shape keys
# ---------------------------------------------------------------------------


def test_shape_key_buckets_width_and_queries():
    a = shape_key(1000, 5, 60, "float32", False)
    b = shape_key(1024, 5, 64, "float32", False)
    assert a == b
    assert shape_key(1025, 5, 64, "float32", False) != a


def test_shape_key_exact_on_kernel_identity():
    base = shape_key(1024, 5, 64, "float32", False)
    assert shape_key(1024, 6, 64, "float32", False) != base
    assert shape_key(1024, 5, 64, "compact", False) != base
    assert shape_key(1024, 5, 64, "float32", True) != base


# ---------------------------------------------------------------------------
# the timing loop
# ---------------------------------------------------------------------------


def test_tune_picks_fastest_and_skips_raising():
    slow = TileConfig(64)
    fast = TileConfig(128)
    broken = TileConfig(256)

    def make_run(cfg):
        if cfg is broken:
            raise RuntimeError("unsupported tile")
        delay = 0.02 if cfg is slow else 0.0
        return lambda: time.sleep(delay)

    best, timings, refused = tune(make_run, [slow, broken, fast], iters=2)
    assert best == fast
    assert broken not in timings
    assert timings[slow] > timings[fast]
    assert refused == {broken: "RuntimeError: unsupported tile"}


def test_tune_all_raising_falls_back_to_default():
    def make_run(cfg):
        raise RuntimeError("no runtime")

    best, timings, refused = tune(make_run, [TileConfig(64), TileConfig(256)])
    assert best == TileConfig()
    assert timings == {}
    assert set(refused) == {TileConfig(64), TileConfig(256)}


# ---------------------------------------------------------------------------
# backend wiring: pinning, tuning, and the shared winner cache
# ---------------------------------------------------------------------------


def test_explicit_block_w_pins_fixed_config():
    idx = SpatialIndex.build(
        _data(), backend="pallas",
        backend_opts={"block_w": 256, "autotune": "on"},
    )
    host = SpatialIndex.build(_data(), backend="host")
    qs = _queries()
    res = idx.region(qs)
    assert np.array_equal(res.hits, host.region(qs).hits)
    assert idx.artifacts.tuned == {}  # explicit override: no timing spent


def test_autotune_off_pins_fixed_config():
    idx = SpatialIndex.build(
        _data(), backend="pallas", backend_opts={"autotune": "off"}
    )
    idx.region(_queries())
    assert idx.artifacts.tuned == {}


def test_autotune_auto_skips_narrow_grids():
    idx = SpatialIndex.build(_data(), backend="pallas")  # width << 1024
    assert idx.artifacts.schedule.width < AUTO_MIN_WIDTH
    idx.region(_queries())
    assert idx.artifacts.tuned == {}


def test_autotune_on_tunes_and_caches_in_artifacts():
    data, qs = _data(), _queries()
    host = SpatialIndex.build(data, backend="host")
    idx = SpatialIndex.build(
        data, backend="pallas", backend_opts={"autotune": "on"}
    )
    res = idx.region(qs)
    assert np.array_equal(res.hits, host.region(qs).hits)
    assert len(idx.artifacts.tuned) == 1
    (key, cfg), = idx.artifacts.tuned.items()
    assert key == shape_key(
        idx.artifacts.schedule.width, idx.artifacts.schedule.levels,
        qs.shape[0], "float32", False,
    )
    assert isinstance(cfg, TileConfig)
    assert idx.artifacts.tune_refusals == {}
    # same shape again: the cached winner is reused, not re-timed
    idx.region(qs)
    assert len(idx.artifacts.tuned) == 1


def test_with_backend_twin_shares_tuned_cache():
    data, qs = _data(), _queries()
    idx = SpatialIndex.build(
        data, backend="pallas", backend_opts={"autotune": "on"}
    )
    ref = idx.region(qs)
    twin = idx.with_backend("pallas", autotune="on")
    res = twin.region(qs)
    assert np.array_equal(res.hits, ref.hits)
    assert len(idx.artifacts.tuned) == 1  # twin reused the measurement


def test_autotune_validation():
    with pytest.raises(ValueError, match="autotune"):
        SpatialIndex.build(
            _data(), backend="pallas", backend_opts={"autotune": "sometimes"}
        )


# ---------------------------------------------------------------------------
# backend_opts strictness (satellite a)
# ---------------------------------------------------------------------------


def test_backend_opts_unknown_key_is_typeerror():
    with pytest.raises(TypeError):
        SpatialIndex.build(
            _data(), backend="pallas", backend_opts={"block_width": 256}
        )


def test_backend_opts_duplicate_of_direct_opt_is_typeerror():
    with pytest.raises(TypeError, match="duplicates"):
        SpatialIndex.build(
            _data(), backend="pallas", precision="compact",
            backend_opts={"precision": "float32"},
        )


def test_backend_opts_rejects_build_options():
    with pytest.raises(TypeError, match="build option"):
        SpatialIndex.build(
            _data(), backend="pallas", backend_opts={"levels": 3}
        )
    with pytest.raises(TypeError, match="build option"):
        SpatialIndex.build(
            _data(), backend="pallas", backend_opts={"order": "hilbert"}
        )


def test_backend_opts_none_and_empty_are_noops():
    qs = _queries()
    a = SpatialIndex.build(_data(), backend="pallas")
    b = SpatialIndex.build(_data(), backend="pallas", backend_opts={})
    assert np.array_equal(a.region(qs).hits, b.region(qs).hits)
