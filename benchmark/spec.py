"""What a run is asked to do, read from data: ``BENCHMARK.json`` at the root of
the checkout, and the files under ``benchmark/`` that its entries name.

Nothing here knows a cell, a configuration, a mix or a metric by name: a
later PR adds one by adding files and entries (see README.md)."""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# where the entries are read from; the tests point these at a tiny cell
BENCH_FILE = ROOT / "BENCHMARK.json"
DATA = HERE


class SpecError(SystemExit):
    """A run that cannot be described: exits non-zero, prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{path} is missing")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json as it is run
    traffic: dict         # traffic/<traffic>.json
    end_to_end: tuple     # names of the end-to-end metrics this cell reports
    per_layer: tuple      # names of the per-layer metrics read in this cell


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str) -> Cell:
    bench = _load_json(BENCH_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(has: {', '.join(sorted(cells))})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(BENCH_FILE.parent / configs[w["config"]]["file"])
    traffic = _load_json(DATA / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m["name"] for m in bench["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m["name"] for m in bench["per_layer"]
                        if _reports(m, workload)))


def units() -> dict:
    bench = _load_json(BENCH_FILE)
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks. A kind that is not in the table is an
    error, never a default."""
    table = _load_json(DATA / "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json (has: {', '.join(table)})")
    return table[device_kind]


def plugin(package: str, name: str):
    """``benchmark/<package>/<name>.py``, found by the name an entry gives
    (names may hold ``-`` and ``.``; module files use ``_``)."""
    mod = name.replace("-", "_").replace(".", "_")
    try:
        return importlib.import_module(f"benchmark.{package}.{mod}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{package}.{mod}":
            raise
        raise SpecError(f"benchmark/{package}/{mod}.py is missing "
                        f"(named by {name!r})")
