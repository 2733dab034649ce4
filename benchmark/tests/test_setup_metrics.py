"""The readers of a replica's start (PR 39), against two ``telemetry()``
dicts made by hand (``data/setup_counters.json``): each reads the value at
the window's opening, and a program without the keys leaves the metric out.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import layers, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

BY_HAND = {
    "setup_restore_s": 3.75,         # restore 2.5 + hold 1.25
    "setup_build_s": 3.5,            # decoder 0.5 + slab 3.0
    "setup_trace_s": 12.0,
    "setup_lower_s": 4.5,
    "setup_backend_s": 9.0,
    "setup_cache_hit_share": 75.0,   # 3 hits of 3 + 1 looked up
}


def _reading(change=lambda c0, c1: None) -> layers.Reading:
    d = json.loads((DATA / "setup_counters.json").read_text())
    c0, c1 = dict(d["open"]), dict(d["close"])
    change(c0, c1)
    return layers.Reading(cell=None, win=SimpleNamespace(counters=(c0, c1)),
                          trace=None, peaks={})


def _read(name, r):
    return spec.plugin("layer_metrics", name).read(r)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_reading_worked_by_hand(name):
    assert _read(name, _reading()) == pytest.approx(BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_the_value_is_the_one_at_the_windows_opening(name):
    """A compile inside the window grows the counters at its close
    (``window_compiles`` counts it); set-up is what came before."""
    def compiled_in_window(c0, c1):
        for k in c1:
            c1[k] += 7.0
    assert _read(name, _reading(compiled_in_window)) == pytest.approx(
        BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_program_without_the_keys_leaves_the_metric_out(name):
    """The parent commit's telemetry has none of them: None, no raise."""
    def parent(c0, c1):
        for c in (c0, c1):
            for k in [k for k in c if k.startswith(("startup_", "compile_"))]:
                del c[k]
    assert _read(name, _reading(parent)) is None

    # one of a metric's keys missing is no reading either
    def half(c0, c1):
        for k in ("startup_hold_seconds", "startup_slab_seconds",
                  "compile_trace_seconds", "compile_lower_seconds",
                  "compile_backend_seconds", "compile_cache_misses"):
            del c0[k]
    assert _read(name, _reading(half)) is None


def test_no_share_of_no_lookups():
    """A process without a persistent compile cache counts neither hits nor
    writes: 0 of 0 is no reading."""
    def no_cache(c0, c1):
        c0["compile_cache_hits"] = c0["compile_cache_misses"] = 0.0
    assert _read("setup_cache_hit_share", _reading(no_cache)) is None

    def cold(c0, c1):
        c0["compile_cache_hits"], c0["compile_cache_misses"] = 0.0, 3.0
    assert _read("setup_cache_hit_share", _reading(cold)) == 0.0


def test_the_six_are_in_the_benchmark_under_setup_s_in_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in BY_HAND:
        assert by[name]["moves"] == "setup_s"
        assert by[name]["source"] == "program_counter"
        assert by[name]["workloads"] == cells
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == list(BY_HAND)
