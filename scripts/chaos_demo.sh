#!/usr/bin/env bash
# Resilience-layer demo: boot the single-process cluster with 10% injected
# network faults on every internal hop (server delay/500/connection-reset +
# client-side connection errors), run a K-AVG train job to completion THROUGH
# the chaos, then drive a serving burst past a tiny admission limit and show
# the overload path (429 + Retry-After, bounded queue, zero hung requests).
# Retry/breaker/chaos/shed counters are read back off /metrics and a summary
# row is printed and appended to chaos_demo.jsonl in the output directory.
#
#   scripts/chaos_demo.sh [out_dir]      (default: a new directory under
#                                         $TMPDIR; the summary and the
#                                         metrics text land there)
set -euo pipefail
cd "$(dirname "$0")/.."

OUT_DIR="${1:-$(mktemp -d)}"
mkdir -p "$OUT_DIR"
export KUBEML_DATA_ROOT="${KUBEML_DATA_ROOT:-$OUT_DIR/kubeml}"

JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
KUBEML_CHAOS="${KUBEML_CHAOS:-0.1}" \
KUBEML_CHAOS_CLIENT="${KUBEML_CHAOS_CLIENT:-0.05}" \
KUBEML_CHAOS_SEED="${KUBEML_CHAOS_SEED:-1234}" \
KUBEML_CHAOS_DELAY="${KUBEML_CHAOS_DELAY:-0.05}" \
KUBEML_RETRY_ATTEMPTS=5 \
KUBEML_RETRY_BUDGET=10 \
KUBEML_BREAKER_THRESHOLD=100 \
KUBEML_SERVING_SLOTS=2 \
KUBEML_SERVING_QUEUE_LIMIT=4 \
python - "$OUT_DIR" <<'EOF'
import json, sys, threading, time
from pathlib import Path

out_dir = Path(sys.argv[1])

import numpy as np
from kubeml_tpu.api.config import get_config
from kubeml_tpu.api.errors import KubeMLError
from kubeml_tpu.api.types import TrainOptions, TrainRequest
from kubeml_tpu.cluster import LocalCluster
from kubeml_tpu.controller.client import KubemlClient
from kubeml_tpu.utils import resilience

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
        super().__init__("chaos-demo-blobs")

class TinyModel(KubeModel):
    def __init__(self):
        super().__init__(BlobDataset())
    def build(self):
        return TinyNet()
    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
'''

SERVE_FN = '''
import jax.numpy as jnp
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gpt import CausalTransformer

class D(KubeDataset):
    def __init__(self):
        super().__init__("unused")

class Model(KubeModel):
    def __init__(self):
        super().__init__(D())
    def build(self):
        return CausalTransformer(vocab_size=101, max_len=64, embed_dim=64,
                                 depth=2, num_heads=4)
'''

cfg = get_config()
cfg.ensure_dirs()
t_start = time.time()
row = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
       "chaos_server_p": resilience.chaos().server_p,
       "chaos_client_p": resilience.chaos().client_p}

with LocalCluster(config=cfg) as cluster:
    client = KubemlClient(cluster.controller_url)

    # --- phase 1: K-AVG train completes under injected faults ---
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(256,)).astype(np.int64)
    client.datasets().create("chaos-demo-blobs", x, y, x[:64], y[:64])
    client.functions().create("chaos-demo-tiny", FN)
    req = TrainRequest(
        model_type="chaos-demo-tiny", batch_size=16, epochs=2,
        dataset="chaos-demo-blobs", lr=0.05,
        function_name="chaos-demo-tiny",
        options=TrainOptions(default_parallelism=2, k=2,
                             static_parallelism=True))
    job_id = client.networks().train(req)
    deadline = time.time() + 300
    while time.time() < deadline:
        if all(t.job_id != job_id for t in client.tasks().list()):
            break
        time.sleep(0.2)
    else:
        raise SystemExit(f"job {job_id} did not finish under chaos")
    hist = client.histories().get(job_id)
    assert len(hist.train_loss) == 2 and all(
        np.isfinite(l) for l in hist.train_loss), hist.train_loss
    row["train"] = {"job_id": job_id, "epochs": len(hist.train_loss),
                    "final_loss": round(float(hist.train_loss[-1]), 4)}

    # --- phase 2: serving burst past the admission limit ---
    # a servable "finished" causal LM: random-init weights exported as the
    # final checkpoint of a synthetic LM function
    import flax.linen as nn
    import jax
    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore

    module = CausalTransformer(vocab_size=101, max_len=64, embed_dim=64,
                               depth=2, num_heads=4)
    prompt = np.asarray(rng.integers(1, 101, size=(1, 8)), np.int32)
    variables = jax.tree.map(
        np.asarray, nn.meta.unbox(module.init(jax.random.PRNGKey(0), prompt)))
    FunctionRegistry(config=cfg).create("chaos-serve-fn", SERVE_FN)
    CheckpointStore(config=cfg).save(
        "chaosserve", variables, epoch=1, tag=FINAL_TAG,
        meta={"request": {"function_name": "chaos-serve-fn"}})

    # warm the decoder (one request pays the cold compiles)
    client.networks().generate("chaosserve", prompt, max_new_tokens=4)

    outcomes = {"ok": 0, "overloaded_429": 0, "other_error": 0}
    lock = threading.Lock()

    def burst_client(i):
        try:
            client.networks().generate("chaosserve", prompt,
                                       max_new_tokens=24)
            key = "ok"
        except KubeMLError as e:
            key = "overloaded_429" if e.status_code == 429 else "other_error"
        except Exception:
            key = "other_error"
        with lock:
            outcomes[key] += 1

    threads = [threading.Thread(target=burst_client, args=(i,))
               for i in range(24)]
    t_burst = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "hung serving requests!"
    row["burst"] = {"clients": 24, "slots": cfg.serving_slots,
                    "queue_limit": cfg.serving_queue_limit,
                    "elapsed_s": round(time.time() - t_burst, 2), **outcomes}
    assert outcomes["overloaded_429"] > 0, "admission limit never tripped"
    assert outcomes["ok"] > 0, "nothing served through the burst"

    # --- read the resilience counters off /metrics ---
    from kubeml_tpu.utils import traced_http
    metrics = traced_http.get(f"{cluster.ps_api.url}/metrics", timeout=10).text
    (out_dir / "metrics.txt").write_text(metrics)

def total(metric):
    return sum(float(l.rsplit(" ", 1)[1]) for l in metrics.splitlines()
               if l.startswith(metric + "{"))

row["metrics"] = {
    "http_retries_total": total("kubeml_http_retries_total"),
    "chaos_injected_total": total("kubeml_chaos_injected_total"),
    "breaker_open_total": total("kubeml_http_breaker_open_total"),
    "deadline_rejected_total": total("kubeml_http_deadline_rejected_total"),
    "serving_overload_total": total("kubeml_serving_requests_overload_total"),
    "serving_shed_total": total("kubeml_serving_requests_shed_total"),
}
assert row["metrics"]["chaos_injected_total"] > 0
assert row["metrics"]["http_retries_total"] > 0
row["elapsed_s"] = round(time.time() - t_start, 2)

with open(out_dir / "chaos_demo.jsonl", "a") as f:
    f.write(json.dumps(row) + "\n")
print(json.dumps(row, indent=2))
print(f"\nfull /metrics text: {out_dir / 'metrics.txt'}")
EOF