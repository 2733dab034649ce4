"""The bench regression gate (scripts/bench_compare.py) over a trajectory of
driver-wrapper records (``{"n", "rc", "parsed": <bench.py row>}``) — the
fast tier-1 wiring the gate is meant for. The rows are fixtures with made-up
values, not measurements."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
GATE = REPO / "scripts" / "bench_compare.py"


def _run(*files, threshold=None):
    cmd = [sys.executable, str(GATE)]
    if threshold is not None:
        cmd += ["--threshold", str(threshold)]
    cmd += [str(f) for f in files]
    return subprocess.run(cmd, capture_output=True, text=True, cwd=str(REPO))


def _wrapper(tmp_path, n, value, end_to_end, mfu):
    """One driver-wrapper record the way the driver stores a bench.py row."""
    f = tmp_path / f"BENCH_r{n:02d}.json"
    f.write_text(json.dumps({"n": n, "rc": 0, "parsed": {
        "metric": "resnet18-cifar10-kavg-train-throughput", "value": value,
        "unit": "samples/sec", "mfu": mfu, "end_to_end": end_to_end}}))
    return f


@pytest.fixture
def trajectory(tmp_path):
    return [_wrapper(tmp_path, 3, 900.0, 300.0, 0.30),
            _wrapper(tmp_path, 4, 1000.0, 400.0, 0.40),
            _wrapper(tmp_path, 5, 1200.0, 600.0, 0.48)]


def test_improving_wrapper_pair_passes(trajectory):
    p = _run(*trajectory[-2:])
    assert p.returncode == 0, p.stderr
    report = json.loads(p.stdout)
    assert report["pass"] is True
    assert {c["metric"] for c in report["checks"]} == {
        "device_samples_per_sec", "end_to_end_samples_per_sec", "mfu"}


def test_full_trajectory_compares_last_pair(trajectory):
    files = trajectory
    p = _run(*files)
    assert p.returncode == 0, p.stderr
    report = json.loads(p.stdout)
    assert report["baseline_file"].endswith(files[-2].name)
    assert report["candidate_file"].endswith(files[-1].name)
    assert len(report["trajectory"]) == len(files)


def test_synthetic_regression_fails_the_gate(tmp_path, trajectory):
    last = trajectory[-1]
    base = json.loads(last.read_text())
    cand = {"parsed": dict(base["parsed"])}
    cand["parsed"]["value"] = base["parsed"]["value"] * 0.85  # -15% device
    f = tmp_path / "cand.json"
    f.write_text(json.dumps(cand))
    p = _run(last, f)
    assert p.returncode == 1
    report = json.loads(p.stdout)
    assert report["pass"] is False
    assert report["regressions"][0]["metric"] == "device_samples_per_sec"
    # inside the threshold the same delta passes
    assert _run(last, f, threshold=0.20).returncode == 0


def test_error_row_candidate_fails(tmp_path, trajectory):
    f = tmp_path / "err.json"
    f.write_text(json.dumps({"metric": "x", "value": 0.0,
                             "unit": "samples/sec", "vs_baseline": 0.0,
                             "error": "accelerator backend unreachable"}))
    p = _run(trajectory[-1], f)
    assert p.returncode == 1
    assert "error row" in p.stderr


def test_missing_mfu_is_skipped_not_failed(tmp_path):
    rows = []
    for v in (100.0, 99.0):
        f = tmp_path / f"b{v}.json"
        f.write_text(json.dumps({"metric": "m", "value": v,
                                 "end_to_end": v, "mfu": None}))
        rows.append(f)
    p = _run(*rows)
    assert p.returncode == 0, p.stderr
    report = json.loads(p.stdout)
    assert any(s["metric"] == "mfu" for s in report["skipped"])


def test_nothing_comparable_is_a_distinct_failure(tmp_path):
    f = tmp_path / "empty.json"
    f.write_text(json.dumps({"metric": "m"}))
    p = _run(f, f)
    assert p.returncode == 2


def test_direction_metadata_lower_is_better_latency(tmp_path):
    """Per-metric direction (ISSUE 14 satellite): a latency RISE past the
    threshold regresses, a latency DROP passes — the opposite of the
    throughput semantics the gate used to assume for everything."""
    base = {"metric": "serving", "value": 100.0, "latency_p95_ms": 200.0}
    worse = {**base, "latency_p95_ms": 300.0}   # +50% latency
    better = {**base, "latency_p95_ms": 100.0}  # -50% latency
    files = {}
    for name, row in (("base", base), ("worse", worse), ("better", better)):
        f = tmp_path / f"{name}.json"
        f.write_text(json.dumps(row))
        files[name] = f
    p = _run(files["base"], files["worse"])
    assert p.returncode == 1
    report = json.loads(p.stdout)
    assert report["regressions"][0]["metric"] == "serving_latency_p95_ms"
    assert "lower-is-better" in report["regressions"][0]["detail"]
    assert _run(files["base"], files["better"]).returncode == 0


def test_spec_decode_rows_gate_tokens_per_step_and_acceptance(tmp_path):
    """A drafter regression (fewer tokens/step, worse acceptance) fails
    the gate through the same direction-aware code path as the serving
    fraction."""
    base = {"metric": "spec-decode-serving", "value": 1000.0,
            "spec_tokens_per_step": 2.6, "spec_accept_ratio": 0.9}
    bad = {**base, "spec_tokens_per_step": 1.1, "spec_accept_ratio": 0.2}
    good = {**base, "spec_tokens_per_step": 2.8, "spec_accept_ratio": 0.95}
    files = {}
    for name, row in (("base", base), ("bad", bad), ("good", good)):
        f = tmp_path / f"{name}.json"
        f.write_text(json.dumps(row))
        files[name] = f
    p = _run(files["base"], files["bad"])
    assert p.returncode == 1
    report = json.loads(p.stdout)
    regressed = {r["metric"] for r in report["regressions"]}
    assert {"spec_tokens_per_step", "spec_accept_ratio"} <= regressed
    assert _run(files["base"], files["good"]).returncode == 0


def test_hol_stall_rows_gate_lower_is_better(tmp_path):
    """Chunked-prefill rows (ISSUE 19): head-of-line stall seconds per
    completed request is lower-is-better — a candidate whose chunking
    regresses (MORE stall per request) fails the gate; the measured
    improvement the demo records passes it."""
    base = {"metric": "chunked-prefill", "value": 1000.0,
            "hol_stall_seconds_per_request": 0.40}
    worse = {**base, "hol_stall_seconds_per_request": 0.55}   # +38% stall
    better = {**base, "hol_stall_seconds_per_request": 0.10}  # -75% stall
    files = {}
    for name, row in (("base", base), ("worse", worse), ("better", better)):
        f = tmp_path / f"{name}.json"
        f.write_text(json.dumps(row))
        files[name] = f
    p = _run(files["base"], files["worse"])
    assert p.returncode == 1
    report = json.loads(p.stdout)
    assert (report["regressions"][0]["metric"]
            == "serving_hol_stall_per_request")
    assert "lower-is-better" in report["regressions"][0]["detail"]
    assert _run(files["base"], files["better"]).returncode == 0
    # rows without the field (train benches) skip the metric, not fail
    f = tmp_path / "plain.json"
    f.write_text(json.dumps({"metric": "m", "value": 1000.0}))
    p = _run(f, f)
    assert p.returncode == 0
    assert any(s["metric"] == "serving_hol_stall_per_request"
               for s in json.loads(p.stdout)["skipped"])


def test_metric_direction_table():
    from kubeml_tpu.benchmarks.harness import GATE_METRICS, metric_direction

    assert metric_direction("spec_tokens_per_step") == "higher"
    assert metric_direction("spec_accept_ratio") == "higher"
    assert metric_direction("serving_latency_p95_ms") == "lower"
    assert metric_direction("serving_hol_stall_per_request") == "lower"
    assert all(d in ("higher", "lower")
               for _f, d in GATE_METRICS.values())


def test_normalize_bench_row_handles_both_forms(trajectory):
    from kubeml_tpu.benchmarks.harness import normalize_bench_row

    wrapper = json.loads(trajectory[-1].read_text())
    row = normalize_bench_row(wrapper)
    assert row["device_samples_per_sec"] == pytest.approx(1200.0)
    assert row["end_to_end_samples_per_sec"] == pytest.approx(600.0)
    assert row["mfu"] == pytest.approx(0.48)
    raw = normalize_bench_row(wrapper["parsed"])
    assert raw == row
    err = normalize_bench_row({"metric": "m", "value": 0.0, "error": "boom"})
    assert err["error"] == "boom" and err["mfu"] is None
