"""Every file path that README.md and DESIGN.md cite exists.

A cited path is a backticked token with a directory part that ends in a
file suffix or in ``/``; a ``:123`` or ``::name`` suffix is dropped.  It
must exist under the repo root or under ``src/repro/``.  PERF.md is left
out: its findings record past changes, and may cite files since deleted.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
DOCS = ("README.md", "DESIGN.md")
PATH = re.compile(r"(?:[\w.-]+/)+(?:[\w.-]+\.(?:py|json|md|yml|toml|pb))?")


def _cited_paths():
    paths = set()
    for doc in DOCS:
        for span in re.findall(r"`([^`\n]+)`", (ROOT / doc).read_text()):
            for token in span.split():
                token = token.split(":")[0]
                if PATH.fullmatch(token):
                    paths.add(token)
    return sorted(paths)


@pytest.mark.parametrize("path", _cited_paths())
def test_cited_path_exists(path):
    assert (ROOT / path).exists() or (ROOT / "src" / "repro" / path).exists(), (
        f"{path} is cited in {' or '.join(DOCS)} but does not exist"
    )
