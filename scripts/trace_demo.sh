#!/usr/bin/env bash
# End-to-end distributed-tracing demo: boot the single-process cluster with
# tracing on, run a tiny train task, fetch the merged trace through the
# `kubeml trace` CLI, verify the new latency histograms on /metrics, and
# print a summary row, appended to trace_demo.jsonl in the output directory.
#
#   scripts/trace_demo.sh [out_dir]      (default: a new directory under
#                                         $TMPDIR; summary, trace JSON and
#                                         metrics text land there)
set -euo pipefail
cd "$(dirname "$0")/.."

OUT_DIR="${1:-$(mktemp -d)}"
mkdir -p "$OUT_DIR"

JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" KUBEML_TRACE="$OUT_DIR/spans" \
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
        super().__init__("trace-demo-blobs")

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
    client.datasets().create("trace-demo-blobs", x, y, x[:64], y[:64])
    client.functions().create("trace-demo-tiny", FN)
    req = TrainRequest(
        model_type="trace-demo-tiny", batch_size=16, epochs=2,
        dataset="trace-demo-blobs", lr=0.05, function_name="trace-demo-tiny",
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

    # fetch the merged trace through the real CLI command
    trace_path = out_dir / f"trace-{job_id}.json"
    rc = cli_main(["--url", cluster.controller_url, "trace", job_id,
                   "-o", str(trace_path)])
    assert rc == 0, "kubeml trace failed"
    chrome = json.loads(trace_path.read_text())
    procs = sorted(e["args"]["name"] for e in chrome["traceEvents"]
                   if e["ph"] == "M")
    spans = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    trace_ids = sorted({e["args"].get("trace_id") for e in spans
                        if e["args"].get("trace_id")})

    import requests
    metrics = requests.get(f"{cluster.ps_api.url}/metrics", timeout=10).text
    (out_dir / "metrics.txt").write_text(metrics)
    hist_series = sorted({
        line.split("{")[0] for line in metrics.splitlines()
        if "_bucket{" in line})

    assert len(trace_ids) == 1, f"expected one trace, got {trace_ids}"
    assert {"controller", "ps", "worker"} <= set(procs), procs
    assert len(hist_series) >= 3, hist_series

row = {
    "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    "job_id": job_id,
    "elapsed_s": round(time.time() - t_start, 2),
    "processes": procs,
    "spans": len(spans),
    "trace_id": trace_ids[0],
    "histogram_bucket_series": hist_series,
    "trace_file": str(trace_path),
}
with open(out_dir / "trace_demo.jsonl", "a") as f:
    f.write(json.dumps(row) + "\n")
print(json.dumps(row, indent=2))
print(f"\nopen {trace_path} in chrome://tracing or https://ui.perfetto.dev")
EOF
