"""Backend registry for the :class:`SpatialIndex` façade.

Backends self-register with a declaration of (a) which structures they
serve and (b) which build artifact they lower — the pointer tree, the
``FlatTree``, or the ``LevelSchedule``.  The façade consults
:func:`get_backend` at build time and :func:`advertised_pairs` is the
single source of truth the parity matrix test sweeps
(tests/test_index_api.py).

Backends that accept ``precision="compact"`` (pallas, serve) additionally
pull the QUANTIZED lowering — the conservative uint16 tile form of the
schedule (DESIGN.md §7) — via ``BuildArtifacts.quantized``; like every
lowering it is computed once and cached, so float32 and compact engines
over the same build share one quantization.

The backend name also selects the join engine: ``SpatialIndex.join``
routes on the LEFT index's spec (``index/join.py`` — host/lax/pallas
pair-sweep twins, serve walking the degradation ladder), so registering
a backend here serves region/point/knn AND tree-vs-tree joins
(DESIGN.md §10) with one name.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Tuple

ARTIFACTS = ("pointer", "flat", "schedule")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    structures: frozenset
    artifact: str           # which lowering of the build the backend consumes
    factory: Callable       # (BuildArtifacts, **opts) -> adapter with .region()
    doc: str = ""


_REGISTRY: Dict[str, BackendSpec] = {}
_BUILTINS_LOADED = False


def register_backend(name: str, *, structures: Iterable[str], artifact: str,
                     doc: str = ""):
    """Class/function decorator: declare a query backend.

    The factory is called as ``factory(artifacts, **backend_opts)`` and
    must return an adapter exposing ``region(queries) -> (hits (Q, n_obj)
    bool, visits (Q, L) int32, launches int)``.
    """
    if artifact not in ARTIFACTS:
        raise ValueError(f"artifact {artifact!r} not in {ARTIFACTS}")

    def deco(factory):
        _REGISTRY[name] = BackendSpec(
            name=name,
            structures=frozenset(structures),
            artifact=artifact,
            factory=factory,
            doc=doc,
        )
        return factory

    return deco


def get_backend(name: str) -> BackendSpec:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {backend_names()}"
        ) from None


def backend_names() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def advertised_pairs() -> List[Tuple[str, str]]:
    """Every (structure, backend) combination the registry serves."""
    _ensure_loaded()
    return sorted(
        (structure, spec.name)
        for spec in _REGISTRY.values()
        for structure in spec.structures
    )


def _ensure_loaded() -> None:
    # The built-in backends live in repro.index.backends and register on
    # import; imported lazily so registry.py stays import-cycle-free.  A
    # dedicated flag (not `if not _REGISTRY`) so user-registered backends
    # never mask the built-ins; set only after the import succeeds so a
    # transient import failure re-raises the real error on retry instead
    # of an empty-registry "unknown backend".
    global _BUILTINS_LOADED
    if not _BUILTINS_LOADED:
        from . import backends  # noqa: F401

        _BUILTINS_LOADED = True
