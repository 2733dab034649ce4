"""The packages of ``kubeml_tpu`` import downward only.

``ORDER`` is the one declared order, lowest first: a package may import from
the packages before it and from nothing else of the program. The top-level
modules (``cli``, ``cluster``, ``supervisor``) sit above every package. Read
from the sources with ``ast``, so nothing here imports jax. ``KNOWN_DEBTS``
names the imports that point up today (ROADMAP.md D13 says where each piece
of code should move); the last case fails when an entry no longer occurs, so
the list only shrinks.
"""

import ast
from functools import lru_cache
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent / "kubeml_tpu"

ORDER = [
    "api", "utils", "ops", "interop", "storage", "native", "parallel",
    "models", "data", "runtime", "functions", "serving", "scheduler",
    "engine", "ps", "controller",
]

# (module, imported package)
KNOWN_DEBTS = [
    ("api/config.py", "parallel"),
    ("utils/profiler.py", "ps"),
    ("utils/resilience.py", "ps"),
    ("storage/service.py", "data"),
    ("parallel/moe.py", "models"),
    ("models/layers.py", "serving"),
    ("serving/stats.py", "ps"),
    ("serving/kvsnap.py", "engine"),
]


def _imported(node, module_parts):
    """First name under ``kubeml_tpu`` of each thing an import node names."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            parts = alias.name.split(".")
            if parts[0] == "kubeml_tpu" and len(parts) > 1:
                yield parts[1]
        return
    if node.level == 0:
        parts = (node.module or "").split(".")
        if parts[0] != "kubeml_tpu":
            return
        parts = parts[1:]
    else:
        # module_parts names the module inside kubeml_tpu; one level is its
        # own package, each further level one package up
        parts = module_parts[:len(module_parts) - node.level]
        parts = parts + (node.module.split(".") if node.module else [])
    if parts:
        yield parts[0]
    else:  # ``from .. import utils`` / ``from kubeml_tpu import utils``
        for alias in node.names:
            yield alias.name


@lru_cache(maxsize=None)
def _imports(package):
    """{(module, imported top-level name)} over every module of ``package``,
    the package's imports of itself left out."""
    found = set()
    for path in sorted((ROOT / package).rglob("*.py")):
        rel = path.relative_to(ROOT)
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                for name in _imported(node, list(rel.with_suffix("").parts)):
                    if name != package:
                        found.add((rel.as_posix(), name))
    return found


@pytest.mark.parametrize("package", ORDER)
def test_package_imports_only_from_below(package):
    below = set(ORDER[:ORDER.index(package)])
    up = sorted(edge for edge in _imports(package)
                if edge[1] not in below and edge not in KNOWN_DEBTS)
    assert not up, (
        f"{package} may import from {sorted(below)} only; it also imports "
        f"{up}. Move the code down, or the import into the higher package.")


def test_declarations_match_the_tree():
    on_disk = sorted(p.name for p in ROOT.iterdir()
                     if (p / "__init__.py").exists())
    assert sorted(ORDER) == on_disk
    gone = [debt for debt in KNOWN_DEBTS
            if debt not in _imports(debt[0].split("/")[0])]
    assert not gone, (
        f"no longer occurs, so strike it from KNOWN_DEBTS and from "
        f"ROADMAP.md D13: {gone}")


# --- the serving engines are told, they do not look ------------------------

_BATCHER = (ROOT / "serving" / "batcher.py").read_text()
# what a model's caches are is ``models/cache_spec.py``'s to read, once
_CACHE_FIELDS = ("mla", "attn_kinds", "num_kv_heads", "head_dim", "ssm",
                 "hc_mult", "kv_quant", "paged_attn", "num_heads", "embed_dim")


def test_the_engines_read_no_process_config():
    """The parameter server maps ``Config`` to constructor arguments
    (``ps/parameter_server.py _new_decoder``); an engine that read the
    process config again would decide every option twice."""
    assert "get_config" not in _BATCHER


def test_serving_sniffs_no_cache_field():
    import re

    sniff = re.compile(r"(?:get|has)attr\(\s*(?:self\.)?(?:draft_)?module,\s*"
                       r"[\"'](?:%s)[\"']" % "|".join(_CACHE_FIELDS))
    found = {path.name: sniff.findall(path.read_text())
             for path in sorted((ROOT / "serving").glob("*.py"))}
    assert not any(found.values()), (
        f"ask models/cache_spec.py instead: {found}")
