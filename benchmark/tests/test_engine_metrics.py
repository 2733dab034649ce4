"""The readers of the engine's dispatch-level timeline (PR 24), on a
hand-made window of spans (``data/engine_spans.json``) whose readings are
worked out below, and on a program that has no such spans.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import layers, reduce, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PLANE = "/device:TPU:0"


def _reading(keep=lambda span: True) -> layers.Reading:
    d = json.loads((DATA / "engine_spans.json").read_text())
    win = SimpleNamespace(
        t_open=d["t_open"], seconds=d["seconds"],
        trace_wall=tuple(d["trace_wall"]),
        spans=[s for s in d["spans"] if keep(s)])
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): [tuple(r) for r in d["ops"]]},
        wall_zero=d["wall_zero"])
    return layers.Reading(cell=None, win=win, trace=trace, peaks={})


# the window is wall 1000-1010; one request, one admit program and their
# spans start before it and are left out of every reading
BY_HAND = {
    # submit less the start of the server span that is its parent: 4, 6 and
    # 10 ms; the fourth request's server span is not recorded and is skipped
    "entry_hop_p50_ms": 6.0,
    # wait_s of the three admit programs inside the window: 170, 260, 200
    "admit_wait_p50_ms": 200.0,
    # their svc_s: 240, 230, 234
    "admit_svc_p50_ms": 234.0,
    # (0.23 + 0.27 + 0.5 + 0.25) s stalled over 40 + 60 + 100 + 50 tokens
    "stall_ms_per_token": 5.0,
    # 3 x 6 ms admit + 3 x 4 ms and 5 x 2 ms dispatch + 8 x 1 ms process
    # = 48 ms over 1 + 1 + 1 + 1 + 4 decode steps
    "engine_host_ms_per_step": 6.0,
    # trace time 0-4 s; operations cover 0-1, 1.5-2.5 and 3-3.9, so 1.1 s
    # are idle: 1-1.5, 2.5-3, 3.9-4. The engine waited for work over
    # 1.1-1.4 and 2.4-2.9, which excuses 0.3 + 0.4 s: 0.4 s of 4 s remain
    "idle_with_work_share": 10.0,
}
CAPACITY = {"engine_host_ms_per_step.capacity": "engine_host_ms_per_step",
            "idle_with_work_share.capacity": "idle_with_work_share"}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_reading_worked_by_hand(name):
    value = spec.plugin("layer_metrics", name).read(_reading())
    assert value == pytest.approx(BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(CAPACITY))
def test_the_capacity_cells_read_the_same_number(name):
    assert spec.plugin("layer_metrics", name).read is \
        spec.plugin("layer_metrics", CAPACITY[name]).read


def test_idle_with_work_and_idle_inside_waits_add_up_to_the_idle_share():
    r = _reading()
    window = r.win.trace_wall[1] - r.trace.wall_zero
    idle_share = 100.0 * (1.0 - reduce.busy_seconds(r.trace) / window)
    in_waits = 100.0 * (0.3 + 0.4) / window
    got = spec.plugin("layer_metrics", "idle_with_work_share").read(r)
    assert got + in_waits == pytest.approx(idle_share)
    # a window in which the engine never waited for work: all of it counts
    busy = _reading(lambda s: s["name"] != "engine.wait_work")
    assert spec.plugin("layer_metrics", "idle_with_work_share").read(
        busy) == pytest.approx(idle_share)
    # and without the wall-clock tie there is no reading
    r.trace.wall_zero = None
    assert spec.plugin("layer_metrics", "idle_with_work_share").read(r) is None


@pytest.mark.parametrize("name", sorted(BY_HAND) + sorted(CAPACITY))
def test_a_program_without_engine_spans_leaves_the_metric_out(name):
    """The parent commit has the request-level spans only: the readers of
    what PR 24 adds find nothing, return None and do not raise."""
    old = _reading(lambda s: not s["name"].startswith("engine."))
    value = spec.plugin("layer_metrics", name).read(old)
    if name in ("entry_hop_p50_ms", "stall_ms_per_token"):
        assert value == pytest.approx(BY_HAND[name])   # spans it always had
    else:
        assert value is None
    none = _reading(lambda s: False)
    assert spec.plugin("layer_metrics", name).read(none) is None


def test_every_metric_of_the_benchmark_has_its_reader_and_its_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(spec.plugin("layer_metrics", m["name"]).read)
    by = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    # the cells judged by a latency and those judged by their capacity, as
    # the benchmark's own end-to-end entries list them
    latency = e2e["ttft_p50_ms"]["workloads"]
    capacity = e2e["output_tokens_per_s"]["workloads"]
    assert latency == e2e["tpot_mean_ms"]["workloads"]
    assert latency and capacity and not set(latency) & set(capacity)
    assert set(latency) | set(capacity) == {
        w["name"] for w in bench["workloads"]}
    for name in BY_HAND:
        assert by[name]["workloads"] == latency
        assert by[name]["moves"] in ("ttft_p50_ms", "tpot_mean_ms")
    for name in CAPACITY:
        assert by[name]["workloads"] == capacity
        assert by[name]["moves"] == "output_tokens_per_s"
    # every cell reports a metric of a whole step's share of the peak that
    # moves the end-to-end metric its kernels' rooflines move
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            mfu = [x for x in bench["per_layer"] if "mfu" in x["name"]
                   and x["moves"] == m["moves"]
                   and set(m["workloads"]) <= set(x["workloads"])]
            assert mfu, m["name"]
