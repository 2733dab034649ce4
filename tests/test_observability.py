"""End-to-end distributed tracing across the control plane.

The acceptance surface of the tracing subsystem: one train request crossing
CLI -> controller -> scheduler -> PS -> worker leaves a single-trace span
tree, fetchable as one merged Chrome trace via ``GET /tasks/{id}/trace`` /
``kubeml trace``, and the PS ``/metrics`` exposition carries the new latency
histograms. (Tracer unit tests live in test_tracing_failures.py.)
"""

import json

import pytest

from kubeml_tpu.api.types import TrainOptions, TrainRequest
from kubeml_tpu.ps.traces import TraceStore
from kubeml_tpu.utils import tracing

from conftest import make_blobs, wait_job_done
from test_controlplane import FN_SOURCE


# --- TraceStore ---


def test_trace_store_bounds_and_eviction():
    ts = TraceStore(max_tasks=2, max_spans_per_task=3)
    assert ts.add("a", [{"span_id": str(i)} for i in range(5)]) == 3
    assert len(ts.get("a")) == 3
    assert ts.dropped("a") == 2
    ts.add("b", [{"span_id": "b0"}])
    ts.add("c", [{"span_id": "c0"}])  # evicts oldest task "a"
    assert ts.get("a") == []
    assert len(ts.get("b")) == 1 and len(ts.get("c")) == 1
    ts.add("a", ["not-a-dict"])  # malformed spans are dropped, not stored
    assert ts.get("a") == []
    ts.clear("b")
    assert ts.get("b") == []


def test_ps_trace_merge_dedupes_span_ids(tmp_config):
    """get_trace merges POSTed spans with the local tracer's and dedupes by
    span_id (in the all-in-one cluster every service shares one tracer)."""
    from kubeml_tpu.ps.parameter_server import ParameterServer

    tracer = tracing.get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        ps = ParameterServer(config=tmp_config)
        with tracer.span("job.epoch", job="tj", epoch=0):
            pass
        local = tracer.task_dicts("tj")
        # the runner delivers the same span again plus one of its own
        ps.post_trace("tj", local + [{
            "name": "runner.extra", "start": 1.0, "duration": 0.1,
            "thread": 1, "attrs": {"job": "tj"},
            "trace_id": local[0]["trace_id"], "span_id": "feedbeeffeedbeef",
            "parent_id": local[0]["span_id"], "service": "worker", "pid": 1,
        }])
        trace = ps.get_trace("tj")
        assert trace["task_id"] == "tj"
        names = sorted(s["name"] for s in trace["spans"])
        assert names == ["job.epoch", "runner.extra"]
        assert trace["trace_ids"] == [local[0]["trace_id"]]
    finally:
        tracer.disable()
        tracer.clear()


# --- full pipeline over HTTP ---


@pytest.fixture
def traced_cluster(tmp_config):
    from kubeml_tpu.cluster import LocalCluster

    tracer = tracing.get_tracer()
    tracer.clear()
    tracer.enable()
    tracer.service = "kubeml"
    try:
        with LocalCluster(config=tmp_config) as c:
            yield c
    finally:
        tracer.disable()
        tracer.clear()


def _train_traced(cluster):
    from kubeml_tpu.controller.client import KubemlClient

    client = KubemlClient(cluster.controller_url)
    x, y = make_blobs(256, shape=(8, 8, 1))
    client.datasets().create("blobs", x, y, x[:64], y[:64])
    client.functions().create("tiny", FN_SOURCE)
    req = TrainRequest(
        model_type="tiny", batch_size=16, epochs=2, dataset="blobs", lr=0.05,
        function_name="tiny",
        options=TrainOptions(default_parallelism=2, k=2,
                             static_parallelism=True),
    )
    # the CLI's root span: everything downstream becomes its child
    with tracing.get_tracer().span("cli.train", service="cli"):
        job_id = client.networks().train(req)
    wait_job_done(client, job_id, timeout=180)
    return client, job_id


def test_train_request_yields_one_stitched_trace(traced_cluster):
    """Acceptance: a completed train task's trace holds spans from at least
    three distinct processes (controller, PS, worker) sharing one trace_id,
    with parent/child links intact; /metrics grows >= 3 _bucket series."""
    client, job_id = _train_traced(traced_cluster)
    trace = client.tasks().trace(job_id)
    spans = trace["spans"]
    services = {s["service"] for s in spans}
    assert {"controller", "scheduler", "ps", "worker"} <= services
    assert len(trace["trace_ids"]) == 1
    assert all(s["trace_id"] == trace["trace_ids"][0] for s in spans)
    # link integrity: exactly one root (the CLI span), no dangling parents
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if not s["parent_id"]]
    assert [r["name"] for r in roots] == ["cli.train"]
    assert all(s["parent_id"] in ids for s in spans if s["parent_id"])
    # the worker's epoch spans hang under the PS-side job umbrella
    by_id = {s["span_id"]: s for s in spans}
    epochs = [s for s in spans if s["name"] == "job.epoch"]
    assert len(epochs) == 2
    assert all(by_id[s["parent_id"]]["name"] == "ps.job.run" for s in epochs)
    # merged chrome export: one process row per service, ids in args
    chrome = tracing.merge_chrome_trace(spans)
    rows = {e["args"]["name"] for e in chrome["traceEvents"] if e["ph"] == "M"}
    assert {"cli", "controller", "scheduler", "ps", "worker"} <= rows
    # /metrics: the new histogram series exist for the finished job
    import requests

    text = requests.get(f"{traced_cluster.ps_api.url}/metrics", timeout=5).text
    for metric in ("kubeml_job_epoch_seconds", "kubeml_job_round_seconds",
                   "kubeml_job_merge_seconds"):
        assert f"# TYPE {metric} histogram" in text
        assert f'{metric}_bucket{{jobid="{job_id}",le="+Inf"}}' in text
    assert f'kubeml_job_epoch_seconds_count{{jobid="{job_id}"}} 2' in text


def test_cli_trace_command_writes_chrome_file(traced_cluster, tmp_path,
                                              capsys):
    from kubeml_tpu.cli import main

    client, job_id = _train_traced(traced_cluster)
    out = tmp_path / "trace.json"
    rc = main(["--url", traced_cluster.controller_url, "trace", job_id,
               "-o", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    events = data["traceEvents"]
    rows = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"controller", "ps", "worker"} <= rows
    xs = [e for e in events if e["ph"] == "X"]
    trace_ids = {e["args"]["trace_id"] for e in xs if "trace_id" in e["args"]}
    assert len(trace_ids) == 1
    assert "spans from" in capsys.readouterr().out


def test_trace_unknown_task_is_404(traced_cluster):
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.controller.client import KubemlClient

    client = KubemlClient(traced_cluster.controller_url)
    with pytest.raises(KubeMLError) as err:
        client.tasks().trace("nope1234")
    assert err.value.status_code == 404
