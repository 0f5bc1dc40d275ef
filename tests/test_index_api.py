"""`SpatialIndex` façade: cross-backend parity, k-NN exactness, API hygiene.

The acceptance contract of DESIGN.md §6: every (structure × backend) pair
the registry advertises returns bit-identical hits AND per-level access
counts to the host pointer search, `knn` matches brute-force nearest
neighbours exactly on ≥3 dataset shapes, and no module outside `kernels/`
imports a `_`-prefixed kernel symbol.
"""
import functools
import pathlib
import re

import numpy as np
import pytest

import conftest
from repro.core import mbr as M
from repro.index import SpatialIndex, advertised_pairs, backend_names, get_backend
from repro.index.knn import _mindist_np

# shared builders live in tests/conftest.py; sizes are this module's own
DATASETS = {
    "uniform_squares": 250,
    # the paper's zero-overlap case: degenerate point MBRs (§4)
    "uniform_points": 220,
    "exponential_squares": 200,
}
STRUCTURES = ("mqr", "rtree", "pyramid")
BACKENDS = ("host", "lax", "pallas", "serve")


def _data(name: str) -> np.ndarray:
    return conftest.mbr_dataset("test_index_api", name, DATASETS[name])


@functools.lru_cache(maxsize=None)
def _host_index(structure: str, ds: str) -> SpatialIndex:
    return SpatialIndex.build(_data(ds), structure=structure, backend="host")


def _queries(ds: str) -> np.ndarray:
    return conftest.dataset_queries("test_index_api", ds, DATASETS[ds])


@functools.lru_cache(maxsize=None)
def _host_region(structure: str, ds: str):
    return _host_index(structure, ds).region(_queries(ds))


# ---------------------------------------------------------------------------
# The parity matrix: structures × backends × dataset shapes
# ---------------------------------------------------------------------------


def test_registry_advertises_full_matrix():
    pairs = advertised_pairs()
    for structure in STRUCTURES:
        for backend in BACKENDS:
            assert (structure, backend) in pairs
    assert set(backend_names()) == set(BACKENDS)
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("gpu-of-theseus")


@pytest.mark.parametrize("ds", sorted(DATASETS))
@pytest.mark.parametrize("structure", STRUCTURES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_region_parity_matrix(ds, structure, backend):
    """Identical hit sets and per-level access counts on every advertised
    (structure × backend) pair, for 3 dataset shapes."""
    if (structure, backend) not in advertised_pairs():
        pytest.skip(f"{backend} does not advertise {structure}")
    ref = _host_region(structure, ds)
    idx = _host_index(structure, ds).with_backend(backend)
    res = idx.region(_queries(ds))
    assert np.array_equal(res.hits, ref.hits)
    assert np.array_equal(res.visits_per_level, ref.visits_per_level), (
        f"per-level access counts diverge on {structure}×{backend}"
    )
    # the AccessStats ledger reports the same accounting everywhere
    assert idx.stats.queries == _queries(ds).shape[0]
    assert idx.stats.node_accesses == int(ref.visits_per_level.sum())


@pytest.mark.parametrize("structure", ("mqr", "rtree"))
def test_host_backend_is_the_pointer_search(structure):
    """The host backend's numbers ARE the pointer implementation's."""
    ds = "uniform_squares"
    idx = _host_index(structure, ds)
    res = idx.region(_queries(ds))
    tree = idx.artifacts.pointer_tree
    for i, q in enumerate(_queries(ds)):
        found, v = tree.region_search(np.asarray(q, np.float64))
        assert set(res.ids(i)) == set(found)
        assert int(res.visits[i]) == v


def test_point_and_count_fast_paths():
    ds = "uniform_squares"
    data = _data(ds)
    idx = _host_index("mqr", ds)
    centers = np.stack(
        [(data[:5, 0] + data[:5, 2]) / 2, (data[:5, 1] + data[:5, 3]) / 2], 1
    )
    res = idx.point(centers)
    for i, p in enumerate(centers):
        expect = set(np.nonzero(M.contains_point(data, p))[0])
        assert set(res.ids(i)) == expect
    assert np.array_equal(idx.count(_queries(ds)), _host_region("mqr", ds).counts)
    # point parity across a device backend too (degenerate rectangles)
    dev = idx.with_backend("pallas").point(centers)
    assert np.array_equal(dev.hits, res.hits)
    assert np.array_equal(dev.visits_per_level, res.visits_per_level)


# ---------------------------------------------------------------------------
# k-NN: first-class, exact on every path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ds", sorted(DATASETS))
@pytest.mark.parametrize("structure", STRUCTURES)
def test_knn_matches_brute_force(ds, structure):
    data = _data(ds)
    pts = np.random.default_rng(11).uniform(50.0, 950.0, (7, 2))
    k = 6
    brute_d = _mindist_np(pts, np.asarray(data, np.float64))
    brute_ids = np.argsort(brute_d, axis=1, kind="stable")[:, :k]
    # distances strictly separate at the k boundary -> ids are unambiguous
    srt = np.sort(brute_d, axis=1)
    assert (srt[:, k] > srt[:, k - 1]).all(), "degenerate test fixture"

    host = _host_index(structure, ds)
    for backend in ("host", "lax", "pallas"):
        res = host.with_backend(backend).knn(pts, k)
        assert np.array_equal(res.ids, brute_ids), f"{structure}×{backend}"
        assert np.allclose(
            res.dists, np.take_along_axis(brute_d, brute_ids, 1), atol=1e-4
        )
        assert res.visits.shape == (7,)


def test_knn_accounting_and_bounds():
    ds = "uniform_squares"
    idx = _host_index("mqr", ds).with_backend("pallas")
    pts = np.random.default_rng(3).uniform(100, 900, (4, 2))
    res = idx.knn(pts, 3)
    assert idx.stats.knn_queries == 4
    assert idx.stats.knn_rounds >= 2  # at least one probe + confirm round
    assert idx.stats.node_accesses == int(res.visits.sum())
    with pytest.raises(ValueError, match="outside"):
        idx.knn(pts, 0)
    with pytest.raises(ValueError, match="outside"):
        idx.knn(pts, idx.n_objects + 1)


@pytest.mark.parametrize("structure", STRUCTURES)
def test_knn_tie_breaking_consistent_across_engines(structure):
    """Equal distances resolve by lowest object id on EVERY engine —
    co-centred squares give distance-0 ties at the shared centroid."""
    n, k = 40, 5
    s = np.arange(1, n + 1, dtype=np.float64)[:, None]
    data = np.concatenate([500 - s, 500 - s, 500 + s, 500 + s], axis=1)
    pts = np.array([[500.0, 500.0], [495.0, 505.0], [200.0, 200.0]])
    idx = SpatialIndex.build(data, structure=structure, backend="host")
    ref = idx.knn(pts, k)
    # point 0 is inside every square -> ids 0..k-1; point 1 is inside all
    # squares with half-side >= 5 -> ids 4..8; point 2 has distinct dists
    assert np.array_equal(ref.ids[0], np.arange(k))
    assert np.array_equal(ref.ids[1], np.arange(4, 4 + k))
    assert np.array_equal(ref.ids[2], np.arange(n - 1, n - 1 - k, -1))
    for backend in ("lax", "pallas"):
        res = idx.with_backend(backend).knn(pts, k)
        assert np.array_equal(res.ids, ref.ids), f"{structure}×{backend}"
        assert np.allclose(res.dists, ref.dists, atol=1e-4)


# ---------------------------------------------------------------------------
# API hygiene
# ---------------------------------------------------------------------------


def test_unknown_backend_option_raises():
    """Options a backend does not support must fail loudly, not be
    silently swallowed (typos, or a documented option of another backend)."""
    with pytest.raises(TypeError):
        SpatialIndex.build(
            _data("uniform_squares"), structure="mqr", backend="pallas",
            cache_size=8,  # a serve-only option
        )
    with pytest.raises(TypeError):
        _host_index("mqr", "uniform_squares").with_backend("lax", block_w=64)
    # build options are structure-strict too
    with pytest.raises(TypeError, match="does not accept"):
        SpatialIndex.build(
            _data("uniform_squares"), structure="mqr", backend="host",
            levels=4,  # a pyramid-only option
        )
    with pytest.raises(TypeError, match="does not accept"):
        SpatialIndex.build(
            _data("uniform_squares"), structure="pyramid", backend="host",
            max_entries=8,  # an rtree-only option
        )


def test_custom_backend_registration_never_masks_builtins():
    """Regression: registering a user backend before the first built-in
    lookup must not stop the built-ins from loading."""
    import sys

    from repro.index import registry

    import repro.index as index_pkg

    saved_registry = dict(registry._REGISTRY)
    saved_flag = registry._BUILTINS_LOADED
    # simulate a fresh process: built-ins neither imported nor registered
    # (`from . import backends` short-circuits to an existing package attr)
    saved_mod = sys.modules.pop("repro.index.backends", None)
    saved_attr = index_pkg.__dict__.pop("backends", None)
    registry._REGISTRY.clear()
    registry._BUILTINS_LOADED = False
    try:

        @registry.register_backend(
            "dummy", structures=("mqr",), artifact="schedule"
        )
        class Dummy:
            def __init__(self, artifacts):
                pass

        assert registry.get_backend("host").name == "host"
        assert "dummy" in registry.backend_names()
    finally:
        registry._REGISTRY.clear()
        registry._REGISTRY.update(saved_registry)
        registry._BUILTINS_LOADED = saved_flag
        if saved_mod is not None:
            sys.modules["repro.index.backends"] = saved_mod
        if saved_attr is not None:
            index_pkg.backends = saved_attr


def test_structure_backend_validation():
    with pytest.raises(ValueError, match="unknown structure"):
        SpatialIndex.build(_data("uniform_squares"), structure="kd")
    idx = _host_index("pyramid", "uniform_squares")
    with pytest.raises(ValueError, match="no pointer tree"):
        _ = idx.artifacts.flat


def test_top_level_reexport():
    import repro

    assert repro.SpatialIndex is SpatialIndex
    assert "SpatialIndex" in dir(repro)


def test_no_private_kernel_imports_outside_kernels():
    """No module outside kernels/ may touch a `_`-prefixed kernel symbol —
    the public surface is `repro.kernels.ops` (fused_search,
    interpret_default, pyramid_scan, ...)."""
    root = pathlib.Path(__file__).resolve().parents[1]
    kernel_mods = (
        "kernels", "ops", "mbr_scan", "pyramid_scan",
    )
    import_pat = re.compile(
        r"from\s+(?:repro\.)?kernels(?:\.\w+)?\s+import\s+[^\n]*\b_\w+"
    )
    attr_pat = re.compile(r"\b(?:%s)\._\w+" % "|".join(kernel_mods))
    offenders = []
    for sub in ("src", "tests", "benchmarks", "examples"):
        for f in sorted((root / sub).rglob("*.py")):
            if "kernels" in f.parts:
                continue  # inside the kernel package, private use is fine
            text = f.read_text()
            for pat in (import_pat, attr_pat):
                for m in pat.finditer(text):
                    offenders.append(f"{f.relative_to(root)}: {m.group(0)}")
    assert not offenders, "\n".join(offenders)


def test_kernel_entry_points_have_one_public_home():
    """`repro.kernels.ops` is the ONE public home of the kernel entry
    points (device_schedule, quantize_schedule, pyramid_scan*,
    level_sweep, build_levels_*, hilbert_*, parent_windows, ...).
    Outside kernels/, importing a kernel SUBMODULE other than the public
    trio (`ops`, `fallback`, `autotune`) is forbidden — re-export shims
    must not grow back (DESIGN.md §12)."""
    root = pathlib.Path(__file__).resolve().parents[1]
    allowed = {"ops", "fallback", "autotune"}
    from_pat = re.compile(r"from\s+repro\.kernels\.(\w+)\s+import")
    import_pat = re.compile(r"^\s*import\s+repro\.kernels\.(\w+)", re.M)
    offenders = []
    for sub in ("src", "tests", "benchmarks", "examples"):
        for f in sorted((root / sub).rglob("*.py")):
            if "kernels" in f.parts:
                continue  # inside the kernel package, cross-imports are fine
            text = f.read_text()
            for pat in (from_pat, import_pat):
                for m in pat.finditer(text):
                    if m.group(1) not in allowed:
                        offenders.append(
                            f"{f.relative_to(root)}: {m.group(0)}"
                        )
    assert not offenders, "\n".join(offenders)
