"""``latent_walk_live_share`` (PR 49): the reader on hand-made counters, on a
program without them, and its place in ``BENCHMARK.json``."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import layers, reduce, spec  # noqa: E402

NAME = "latent_walk_live_share"
CELLS = ["glm-4.7-flash.rag", "xing4.0-29b-a4b.extract",
         "longcat-flash-omni.agent"]


def _reading(cell, counters):
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=[],
                          counters=counters)
    return layers.Reading(cell=SimpleNamespace(config=spec.load_cell(cell)
                                               .config),
                          win=win, trace=reduce.Trace(), peaks={})


def read(cell, counters):
    return spec.plugin("layer_metrics", NAME).read(_reading(cell, counters))


@pytest.mark.parametrize("cell", CELLS)
def test_the_share_is_the_live_trips_over_the_trips_run(cell):
    c0 = {"latent_walk_trips_live": 40.0, "latent_walk_trips_run": 50.0,
          "latent_walk_pages": 900.0}
    c1 = {"latent_walk_trips_live": 40.0 + 2940.0,
          "latent_walk_trips_run": 50.0 + 3000.0,
          "latent_walk_pages": 900.0 + 90000.0}
    assert read(cell, (c0, c1)) == pytest.approx(98.0)
    # no step in the window: nothing to divide by
    assert read(cell, (c1, c1)) is None


@pytest.mark.parametrize("cell", CELLS + ["falcon-h1-34b.turns"])
def test_a_program_without_the_counters_reads_none(cell):
    """The parent commit's telemetry in a latent cell (no such counters), a
    K/V cell's (its own walk's), one side only, and nothing at all."""
    kv = ({"walk_chunks_live": 0.0, "walk_chunks_grid": 0.0},
          {"walk_chunks_live": 128.0, "walk_chunks_grid": 512.0})
    half = ({}, {"latent_walk_trips_live": 5.0, "latent_walk_trips_run": 6.0})
    for counters in (kv, half, ({}, {})):
        assert read(cell, counters) is None


def test_the_metric_is_appended_for_the_three_latent_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "output_tokens_per_s", "workloads": CELLS}]
    reports = {w["name"] for w in bench["workloads"]}
    assert set(CELLS) <= reports
    assert (ROOT / "benchmark/layer_metrics" / f"{NAME}.py").exists()
