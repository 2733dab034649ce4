#!/usr/bin/env bash
# Performance-attribution demo (two acts, both append to
# results/profile_demo.jsonl):
#
#  1. A PROFILED flagship bench run (KUBEML_BENCH_PROFILE=1): per-phase
#     byte/FLOP attribution of the bench itself, including the gap row that
#     quantifies the staging share of the device-vs-end-to-end throughput
#     difference.
#  2. A traced train task through the live control plane, folded into a
#     per-phase report by `kubeml profile <task-id>` with a Perfetto
#     counter-track trace next to it.
#
#   scripts/profile_demo.sh [out_dir]     (default: a temp dir for the trace
#                                          artifacts; the jsonl rows land in
#                                          results/ either way)
#
# On a CPU dev box this drives the full code path with the light flagship
# (KUBEML_FLAGSHIP=lenet, tiny rounds); unset the KUBEML_BENCH_* overrides on
# a chip host for the real numbers.
set -euo pipefail
cd "$(dirname "$0")/.."
mkdir -p results

OUT_DIR="${1:-$(mktemp -d)}"
mkdir -p "$OUT_DIR"

# --- act 1: profiled bench -> per-phase attribution + gap row ---
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
KUBEML_FLAGSHIP="${KUBEML_FLAGSHIP:-lenet}" \
KUBEML_BENCH_ROUNDS="${KUBEML_BENCH_ROUNDS:-4}" KUBEML_BENCH_REPS="${KUBEML_BENCH_REPS:-1}" \
KUBEML_BENCH_PROFILE=1 \
python bench.py

# --- act 2: traced train task -> kubeml profile report + Perfetto trace ---
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" KUBEML_TRACE="$OUT_DIR/spans" \
KUBEML_FLIGHT_DIR="$OUT_DIR/flight" \
python - "$OUT_DIR" <<'EOF'
import json, sys, time
from pathlib import Path

out_dir = Path(sys.argv[1])

import numpy as np
from kubeml_tpu.api.config import get_config
from kubeml_tpu.api.types import TrainOptions, TrainRequest
from kubeml_tpu.cli import main as cli_main
from kubeml_tpu.cluster import LocalCluster
from kubeml_tpu.controller.client import KubemlClient
from kubeml_tpu.utils import tracing

FN = '''
import flax.linen as nn
import optax
from kubeml_tpu import KubeModel, KubeDataset

class TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

class BlobDataset(KubeDataset):
    def __init__(self):
        super().__init__("profile-demo-blobs")

class TinyModel(KubeModel):
    def __init__(self):
        super().__init__(BlobDataset())
    def build(self):
        return TinyNet()
    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
'''

cfg = get_config()
cfg.ensure_dirs()
tracer = tracing.get_tracer()   # enabled via KUBEML_TRACE
tracer.service = "kubeml"
t_start = time.time()
with LocalCluster(config=cfg) as cluster:
    client = KubemlClient(cluster.controller_url)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(256,)).astype(np.int64)
    # idempotent re-runs: the data root persists between invocations
    from kubeml_tpu.api.errors import KubeMLError
    for cleanup in (lambda: client.datasets().delete("profile-demo-blobs"),
                    lambda: client.functions().delete("profile-demo-tiny")):
        try:
            cleanup()
        except KubeMLError:
            pass
    client.datasets().create("profile-demo-blobs", x, y, x[:64], y[:64])
    client.functions().create("profile-demo-tiny", FN)
    req = TrainRequest(
        model_type="profile-demo-tiny", batch_size=16, epochs=2,
        dataset="profile-demo-blobs", lr=0.05,
        function_name="profile-demo-tiny",
        options=TrainOptions(default_parallelism=2, k=2,
                             static_parallelism=True))
    with tracer.span("cli.train", service="cli"):
        job_id = client.networks().train(req)
    deadline = time.time() + 300
    while time.time() < deadline:
        if all(t.job_id != job_id for t in client.tasks().list()):
            break
        time.sleep(0.2)
    else:
        raise SystemExit(f"job {job_id} did not finish in time")

    # the real CLI command: report to stdout, Perfetto counter trace to -o
    trace_path = out_dir / f"profile-{job_id}.json"
    import contextlib, io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["--url", cluster.controller_url, "profile", job_id,
                       "-o", str(trace_path)])
    assert rc == 0, "kubeml profile failed"
    report = json.loads(buf.getvalue())
    (out_dir / f"profile-report-{job_id}.json").write_text(buf.getvalue())

    chrome = json.loads(trace_path.read_text())
    counter_events = [e for e in chrome["traceEvents"] if e["ph"] == "C"]
    byte_phases = [p for p in report["phases"] if p["bytes"] > 0]

    import requests
    metrics = requests.get(f"{cluster.ps_api.url}/metrics", timeout=10).text
    (out_dir / "metrics.txt").write_text(metrics)
    dataplane = sorted({l.split("{")[0] for l in metrics.splitlines()
                        if l.startswith("kubeml_dataplane_")
                        or l.startswith("kubeml_staging_bandwidth_")})

    assert byte_phases, "no byte-carrying phase in the attribution report"
    assert counter_events, "no counter track in the Perfetto export"
    assert dataplane, "no data-plane series on /metrics"

row = {
    "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    "kind": "kubeml-profile",
    "job_id": job_id,
    "elapsed_s": round(time.time() - t_start, 2),
    "phases": [p["phase"] for p in report["phases"][:12]],
    "byte_phases": [
        {"phase": p["phase"], "bytes": p["bytes"], "bound": p["bound"]}
        for p in byte_phases[:8]],
    "counter_events": len(counter_events),
    "counter_services": sorted(report.get("counters", {})),
    "dataplane_series": dataplane,
    "perfetto_trace": str(trace_path),
}
with open("results/profile_demo.jsonl", "a") as f:
    f.write(json.dumps(row) + "\n")
print(json.dumps(row, indent=2))
print(f"\nopen {trace_path} in https://ui.perfetto.dev — the 'dataplane' "
      f"process row carries the byte/bandwidth counter tracks")
EOF
