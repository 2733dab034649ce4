"""Every path a document names exists.

In each document, every repo path in backticks (``scripts/…``, ``results/…``,
``kubeml_tpu/…`` or its short form such as ``serving/batcher.py``,
``tests/…``, ``benchmark/…``, ``deploy/…``, ``examples/…``, ``docs/…``,
``native/…``) must be in the tree, a ``tests/x.py::test_y`` must name a test
that file defines, a ``engine/kavg.stage_round`` must name something
``engine/kavg.py`` defines, and every ``python -m kubeml_tpu.…`` must name a
module. A path with a placeholder (``<x>``, ``…``) is skipped; one with ``*``
must match something.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "kubeml_tpu"

DOCUMENTS = [
    "README.md", "docs/api.md", "docs/design.md", "docs/user-guide.md",
    "deploy/README.md", "examples/README.md", "PARITY.md",
]

_ROOTS = ("scripts", "results", "kubeml_tpu", "tests", "benchmark", "deploy",
          "examples", "docs", "native")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
# a path: segments of word characters, dots, dashes and stars, at least one
# slash; ``::name`` (a test) or ``:12`` / ``:12-40`` (lines) may follow
_PATH = re.compile(r"^([\w.*-]+(?:/[\w.*-]+)+/?)(?:::([\w\[\]-]+)|:[\d,-]+)?$")
_MODULE = re.compile(r"python3?\s+-m\s+(kubeml_tpu(?:\.\w+)+)")


def _places(path: str):
    """Where in the tree a documented path may be: under the repo's root,
    under the package (the short form), both for ``native/``; empty when the
    token is not a repo path at all (``application/json``, ``dp/tp``)."""
    first = path.split("/", 1)[0]
    places = [REPO / path] if first in _ROOTS else []
    if (PACKAGE / first / "__init__.py").exists() and re.search(r"\.\w+$|/$", path):
        places.append(PACKAGE / path)
    return places


def _found(where: Path, test) -> bool:
    if "*" in where.name:
        return bool(list(where.parent.glob(where.name)))
    if where.exists():
        return not test or f"def {test.split('[')[0]}(" in where.read_text()
    # ``engine/kavg.stage_round``: a name that ``engine/kavg.py`` defines
    module = where.with_name(where.stem + ".py")
    name = re.escape(where.suffix[1:])
    return module.exists() and bool(name) and re.search(
        rf"^\s*(?:(?:def|class)\s+{name}\b|{name}\s*[:=])", module.read_text(), re.M) is not None


def _missing(text: str):
    out = []
    for token in _BACKTICKED.findall(text):
        for word in token.split():
            m = _PATH.match(word.strip(".,;:()"))
            if not m or "<" in token or "…" in token or "..." in word:
                continue
            places = _places(m.group(1))
            if places and not any(_found(w, m.group(2)) for w in places):
                out.append(word)
    for module in _MODULE.findall(text):
        rel = Path(*module.split(".")[1:])
        if not ((PACKAGE / rel).with_suffix(".py").exists()
                or (PACKAGE / rel / "__init__.py").exists()):
            out.append(f"python -m {module}")
    return sorted(set(out))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_path_exists(document):
    missing = _missing((REPO / document).read_text())
    assert not missing, (
        f"{document} names what is not in the tree: {missing}")
