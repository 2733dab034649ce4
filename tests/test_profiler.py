"""Performance-attribution layer: data-plane byte accounting, profiling
sessions, the flight recorder, exposition hardening, and the span-tree
attribution report behind ``kubeml profile``."""

import json
import re
import time

import pytest

from kubeml_tpu.ps.metrics import (MAX_HISTOGRAM_JOBS, MetricsRegistry,
                                   escape_help, escape_label_value)
from kubeml_tpu.utils import profiler, tracing


@pytest.fixture(autouse=True)
def _clean_accounting():
    profiler.reset_accounting()
    profiler.get_recorder().clear()
    yield
    profiler.reset_accounting()
    profiler.get_recorder().clear()


# --- byte accounting ---


def test_account_totals_and_bandwidth_histogram():
    profiler.account("stage_round", 1024)               # async: bytes only
    profiler.account("stage_round", 1024)
    profiler.account("weights.publish", 10_000, 0.5)    # blocking: bandwidth
    lines = profiler.render_metrics()
    text = "\n".join(lines)
    assert 'kubeml_dataplane_bytes_total{phase="stage_round"} 2048' in text
    assert 'kubeml_dataplane_events_total{phase="stage_round"} 2' in text
    # the async phase observed NO bandwidth; the blocking one did (20 kB/s)
    assert 'kubeml_staging_bandwidth_bytes_per_sec_bucket{phase="stage_round"' not in text
    assert ('kubeml_staging_bandwidth_bytes_per_sec_count{phase='
            '"weights.publish"} 1') in text


def test_account_phase_cardinality_is_bounded():
    for i in range(profiler.MAX_PHASES + 10):
        profiler.account(f"phase-{i}", 1)
    snap = profiler.counters_snapshot()["dataplane"]
    assert len(snap) <= profiler.MAX_PHASES
    assert "phase-0" not in snap  # oldest evicted


def test_record_io_spans_carry_byte_attributes():
    tracer = tracing.get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        profiler.record_io("weights.publish", 4096, 0.25, version=3)
        (span,) = tracer.spans("weights.publish")
        assert span.attrs["bytes"] == 4096
        assert span.attrs["bandwidth_bps"] == pytest.approx(16384.0)
        assert span.attrs["version"] == 3
        assert span.duration == pytest.approx(0.25)
    finally:
        tracer.disable()
        tracer.clear()


def test_http_byte_counters_per_route(tmp_config):
    from kubeml_tpu.utils import resilience, traced_http
    from kubeml_tpu.utils.httpd import Router, Service

    assert traced_http.route_label("http://h:1/update/job-17") == "/update"
    assert traced_http.route_label("http://h:1/") == "/"

    router = Router("bytes-test")
    router.route("POST", "/echo", lambda req: {"got": len(req.body or b"")})
    svc = Service(router, "127.0.0.1", 0).start()
    try:
        before_tx = resilience.counter_value(
            "kubeml_http_sent_bytes_total", "/echo")
        before_rx = resilience.counter_value(
            "kubeml_http_received_bytes_total", "/echo")
        resp = traced_http.post(f"{svc.url}/echo", json={"pad": "x" * 100},
                                timeout=traced_http.timeouts(5))
        assert resp.status_code == 200
        sent = resilience.counter_value(
            "kubeml_http_sent_bytes_total", "/echo") - before_tx
        received = resilience.counter_value(
            "kubeml_http_received_bytes_total", "/echo") - before_rx
        assert sent >= 100
        assert received >= len(resp.content) > 0
    finally:
        svc.stop()


# --- profiling sessions ---


def test_profile_session_report_classifies_phases():
    s = profiler.ProfileSession("bench")
    with s:
        with s.phase("stage", nbytes=1_000_000):
            time.sleep(0.01)
    s.note_phase("compute", 2.0, flops=4e12)
    rep = s.report()
    rows = {r["phase"]: r for r in rep["phases"]}
    assert rows["stage"]["bound"] == "transfer-bound"
    assert rows["stage"]["bandwidth_bps"] > 0
    assert rows["compute"]["bound"] == "compute-bound"
    assert rows["compute"]["flops_per_sec"] == pytest.approx(2e12)
    assert sum(r["share"] for r in rep["phases"]) == pytest.approx(1.0)


def test_profile_session_dump_appends_jsonl(tmp_path):
    s = profiler.ProfileSession("d")
    s.note_phase("a", 1.0, nbytes=10)
    out = tmp_path / "prof.jsonl"
    s.dump(out)
    s.dump(out)
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(rows) == 2 and rows[0]["session"] == "d"


def test_classify_roofline_terms():
    assert profiler.classify(0, 0) == "host"
    assert profiler.classify(1e9, 0) == "transfer-bound"
    assert profiler.classify(0, 1e9) == "compute-bound"
    # both terms nonzero on a device with no entry in the peaks table (this
    # CPU): no machine to draw a roofline for
    assert profiler.classify(1e9, 1e9) == "unknown"


# --- flight recorder ---


def test_flight_recorder_ring_is_bounded_and_dumps(tmp_path):
    rec = profiler.FlightRecorder(capacity=4)
    for i in range(10):
        rec.note({"kind": "dataplane", "phase": f"p{i}", "bytes": i})
    tail = rec.tail(10)
    assert len(tail) == 4
    assert tail[-1]["phase"] == "p9" and tail[0]["phase"] == "p6"
    path = rec.dump("test", out_dir=tmp_path)
    record = json.loads(path.read_text())
    assert record["reason"] == "test"
    assert [e["phase"] for e in record["events"]] == ["p6", "p7", "p8", "p9"]
    assert "counters" in record and "http_counters" in record


def test_flight_recorder_dump_disabled_without_dir(monkeypatch):
    monkeypatch.delenv("KUBEML_FLIGHT_DIR", raising=False)
    assert profiler.FlightRecorder(capacity=2).dump("nope") is None


def test_flight_recorder_receives_tracer_spans():
    tracer = tracing.get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        rec = profiler.get_recorder()
        rec.clear()
        with tracer.span("job.round", job="j-fr", bytes=123):
            pass
        spans = [e for e in rec.tail() if e.get("kind") == "span"]
        assert spans and spans[-1]["name"] == "job.round"
        assert spans[-1]["bytes"] == 123
        assert spans[-1]["trace_id"]
    finally:
        tracer.disable()
        tracer.clear()


def test_errorhook_payload_carries_flight_tail(tmp_path, monkeypatch):
    import http.server
    import threading

    got = []

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            got.append(json.loads(
                self.rfile.read(int(self.headers["Content-Length"]))))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        from kubeml_tpu.utils.errorhook import report_error

        profiler.account("weights.publish", 999, 0.1)
        monkeypatch.setenv("KUBEML_ERROR_WEBHOOK",
                           f"http://127.0.0.1:{srv.server_address[1]}/hook")
        monkeypatch.setenv("KUBEML_FLIGHT_DIR", str(tmp_path / "flight"))
        with tracing.use_context(tracing.TraceContext("ab" * 16, "cd" * 8)):
            report_error("job-failure", "boom", wait=True)
        assert got, "webhook never fired"
        payload = got[0]
        # the tail rides the report, correlated by the bound trace id
        assert payload["trace_id"] == "ab" * 16
        phases = [e.get("phase") for e in payload["flight_recorder"]]
        assert "weights.publish" in phases
        # and the full ring dumped to KUBEML_FLIGHT_DIR for the postmortem
        dump = json.loads((tmp_path / "flight").glob("flight-*.json")
                          .__next__().read_text())
        assert dump["reason"] == "errorhook:job-failure"
        assert payload["flight_dump"].endswith(".json")
    finally:
        srv.shutdown()


# --- span-tree attribution (`kubeml profile`) ---


def _span(name, start, dur, **attrs):
    return {"name": name, "start": start, "duration": dur, "thread": 1,
            "attrs": attrs, "trace_id": "t" * 32, "span_id": name[:16],
            "service": "worker", "pid": 1}


def test_attribution_report_aggregates_bytes_and_flops():
    spans = [
        _span("job.round", 1.0, 0.5, bytes=1000, flops=5e9),
        _span("job.round", 2.0, 0.5, bytes=1000, flops=5e9),
        _span("weights.publish", 3.0, 0.1, bytes=500),
        _span("scheduler POST /job", 3.5, 0.01),
    ]
    rep = profiler.attribution_report(
        spans, counters={"worker": {"dataplane": {}}})
    rows = {r["phase"]: r for r in rep["phases"]}
    assert rows["job.round"]["bytes"] == 2000
    assert rows["job.round"]["flops"] == 1e10
    assert rows["job.round"]["count"] == 2
    assert rows["weights.publish"]["bound"] == "transfer-bound"
    assert rows["scheduler POST /job"]["bound"] == "host"
    assert rep["total_bytes"] == 2500
    assert rep["counters"]["worker"] == {"dataplane": {}}


def test_perfetto_export_emits_counter_tracks():
    spans = [_span("job.round", 1.0, 0.5, bytes=1000),
             _span("job.round", 2.0, 0.5, bytes=3000)]
    trace = profiler.perfetto_with_counters(spans)
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    byte_track = [e for e in counters
                  if e["name"] == "dataplane_bytes_total"]
    assert [e["args"]["bytes"] for e in byte_track] == [1000.0, 4000.0]
    # bandwidth tracks are per service, so concurrent transfers in other
    # processes can't zero each other's rate
    bw_track = [e for e in counters
                if e["name"] == "transfer_bandwidth_MBps/worker"]
    assert bw_track and bw_track[0]["args"]["MBps"] == pytest.approx(0.002)
    # the counter rows live on their own process track
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M"}
    assert "dataplane" in names
    # spanless input degrades to the plain merged trace
    assert profiler.perfetto_with_counters([])["traceEvents"] == []


def test_perfetto_cumulative_track_is_monotonic_under_overlap():
    """A long transfer overlapping a short one must not make the cumulative
    byte counter decrease over time (samples order by transfer END)."""
    spans = [_span("a", 0.0, 10.0, bytes=1_000_000),
             _span("b", 1.0, 1.0, bytes=2_000_000)]
    trace = profiler.perfetto_with_counters(spans)
    byte_track = sorted(
        (e for e in trace["traceEvents"]
         if e["ph"] == "C" and e["name"] == "dataplane_bytes_total"),
        key=lambda e: e["ts"])
    values = [e["args"]["bytes"] for e in byte_track]
    assert values == sorted(values), values
    assert values[-1] == 3_000_000.0


def test_trace_store_keeps_and_evicts_counters():
    from kubeml_tpu.ps.traces import TraceStore

    ts = TraceStore(max_tasks=2)
    ts.add("a", [{"span_id": "a"}])
    ts.add_counters("a", "worker", {"dataplane": {"x": {"bytes": 1.0}}})
    ts.add_counters("a", "ps", {"dataplane": {}})
    assert sorted(ts.get_counters("a")) == ["ps", "worker"]
    ts.add("b", [{"span_id": "b"}])
    ts.add("c", [{"span_id": "c"}])  # evicts task "a" and its counters
    assert ts.get_counters("a") == {}
    ts.add_counters("d", "w", "not-a-dict")  # malformed: ignored
    assert ts.get_counters("d") == {}


# --- exposition hardening ---

_SERIES_RX = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                       # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'   # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\})?'
    r' (?P<value>.+)$')


def _assert_parses(text):
    for line in text.splitlines():
        if not line or line.startswith("#"):
            if line.startswith("# HELP") or line.startswith("# TYPE"):
                assert "\n" not in line
            continue
        m = _SERIES_RX.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        float(m.group("value"))  # the sample value must be a number


def test_escaping_helpers():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert escape_help("x\\y\nz") == "x\\\\y\\nz"


def test_metrics_exposition_parses_with_adversarial_labels():
    from kubeml_tpu.api.types import MetricUpdate

    reg = MetricsRegistry()
    evil = 'job"7\\id\nx'
    reg.update(MetricUpdate(job_id=evil, validation_loss=1.0, accuracy=0.5,
                            train_loss=2.0, parallelism=2,
                            epoch_duration=3.0, merge_seconds=0.5,
                            round_seconds=[0.1, 0.2]))
    reg.set_serving_source(lambda: {'m"odel\n': {
        "tokens_emitted": 5.0, "queue_depth": 1.0,
        "fetches": 2.0, "fetchers_total": 4.0,
        "hist": {"request": {"buckets": [[0.1, 1]], "sum": 0.05,
                             "count": 1}},
    }})
    profiler.account('weird"phase\\', 10, 0.1)
    text = reg.render()
    _assert_parses(text)
    # the raw jobid round-trips through the escaping (unescape and compare)
    line = next(l for l in text.splitlines()
                if l.startswith("kubeml_job_train_loss"))
    raw = re.search(r'jobid="((?:[^"\\]|\\.)*)"', line).group(1)
    unescaped = raw.replace("\\n", "\n").replace('\\"', '"').replace(
        "\\\\", "\\")
    assert unescaped == evil


def test_histogram_jobid_label_cap_evicts_oldest():
    """MAX_HISTOGRAM_JOBS bounds the per-metric jobid cardinality —
    the oldest job's series evicts, newest survive (previously untested)."""
    reg = MetricsRegistry()
    n = MAX_HISTOGRAM_JOBS + 3
    for i in range(n):
        reg.observe("kubeml_job_round_seconds", f"job-{i:03d}", 0.1)
    jobs = sorted(j for (m, j) in reg._hists
                  if m == "kubeml_job_round_seconds")
    assert len(jobs) == MAX_HISTOGRAM_JOBS
    assert jobs[0] == f"job-{n - MAX_HISTOGRAM_JOBS:03d}"  # oldest 3 gone
    assert f"job-{n - 1:03d}" in jobs
    text = reg.render()
    assert 'jobid="job-000"' not in text
    _assert_parses(text)


def test_serving_fetcher_pool_stats():
    from kubeml_tpu.serving.stats import DecoderStats

    st = DecoderStats(slots=4)
    st.fetchers_total = 6
    st.fetch_started()
    st.fetch_started()
    snap = st.snapshot()
    assert snap["fetchers_inflight"] == 2.0
    assert snap["fetcher_utilization"] == pytest.approx(2 / 6)
    st.fetch_finished(0.25)
    snap = st.snapshot()
    assert snap["fetchers_inflight"] == 1.0
    assert snap["fetches"] == 1.0
    assert snap["fetch_busy_seconds"] == pytest.approx(0.25)
