"""The package's layers point one way (DESIGN.md §4).

Every ``repro`` import of a module, lazy imports inside functions
included, lands in the module's own layer or a lower one.  An import of a
module that does not exist fails too, and so does a module that no layer
holds: place a new module before it imports anything.
"""

import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

# Lowest first.  A module belongs to the layer of its longest listed
# prefix; the root package ``repro`` holds only itself.
LAYERS = (
    ("leaves", ("repro.obs", "repro.ft", "repro.launch.compile_cache")),
    ("core", ("repro.core",)),
    ("kernels", ("repro.kernels",)),
    ("engine", ("repro.launch.spatial_serve",)),
    ("update", ("repro.update",)),
    ("index", ("repro.index", "repro.checkpoint.spatial")),
    ("durability", ("repro.checkpoint",)),
    ("serve", ("repro.serve",)),
    ("entry points", ("repro.launch.moving", "repro.launch.loadgen", "repro")),
)


def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(SRC).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


MODULES = {_module_name(p): p for p in sorted((SRC / "repro").rglob("*.py"))}


def _rank(module: str):
    ranks = [
        (len(prefix), rank)
        for rank, (_, prefixes) in enumerate(LAYERS)
        for prefix in prefixes
        if module == prefix or (prefix != "repro" and module.startswith(prefix + "."))
    ]
    return max(ranks)[1] if ranks else None


def _imports(module: str):
    """Every ``repro`` module that ``module`` imports, at any depth."""
    path = MODULES[module]
    package = module if path.name == "__init__.py" else module.rpartition(".")[0]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            targets = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                base = package.rsplit(".", node.level - 1)[0]
                if node.module:
                    base += "." + node.module
            # ``from pkg import name`` imports a submodule where one exists
            targets = [
                f"{base}.{alias.name}" if f"{base}.{alias.name}" in MODULES else base
                for alias in node.names
            ]
        else:
            continue
        yield from (t for t in targets if t == "repro" or t.startswith("repro."))


@pytest.mark.parametrize("module", sorted(MODULES))
def test_imports_point_down(module):
    rank = _rank(module)
    assert rank is not None, f"{module} is in no layer: place it in LAYERS"
    bad = set()
    for target in _imports(module):
        if target not in MODULES:
            bad.add(f"{target} (no such module)")
            continue
        target_rank = _rank(target)
        if target_rank is None:
            bad.add(f"{target} (in no layer)")
        elif target_rank > rank:
            bad.add(f"{target} ({LAYERS[target_rank][0]})")
    layer = LAYERS[rank][0]
    assert not bad, f"{module} ({layer}) imports above its layer: {', '.join(sorted(bad))}"
