"""Test support: training jobs and serving bursts driven through the real
controller -> scheduler -> PS path in one process.

``scenarios()`` lists small jobs shaped like the reference's experiment grid
(reference: ml/experiments/common/experiment.py:82-182 ``KubemlExperiment``:
run task -> poll ``task list --short`` -> fetch ``history get``), and
``ExperimentDriver`` runs one through the ShardStore, the function registry,
the scheduler, the PS and the history store — the path a user's CLI request
takes. ``run_colocation`` and ``run_slo_overload`` drive a ``LocalCluster``
through a serving burst and return what their tests assert on. While the
benchmark has no training cell these are the only end-to-end checks of the
training control plane (PERF.md section 7). Nothing here measures speed.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from kubeml_tpu.api.config import Config
from kubeml_tpu.api.types import TrainOptions, TrainRequest
from kubeml_tpu.data.digits import load_digits_real

# --- synthetic datasets shaped like the reference's benchmarks ---


def synth_images(n: int, shape: Tuple[int, ...], classes: int, seed: int):
    """Learnable image task: class = brightest of ``classes`` row bands.

    uint8, like real image datasets at rest — the host stages quantized bytes
    (4x fewer than f32 over host->HBM) and the model dequantizes on device
    (KubeModel.preprocess)."""
    r = np.random.default_rng(seed)
    x = r.normal(110.0, 40.0, size=(n, *shape))
    y = r.integers(0, classes, size=(n,)).astype(np.int64)
    band = max(1, shape[0] // classes)
    for i in range(n):
        b = int(y[i]) * band
        x[i, b : b + band] += 60.0
    return np.clip(x, 0, 255).astype(np.uint8), y


def synth_tokens(n: int, seq_len: int, vocab: int, classes: int, seed: int):
    """Learnable text task: class = token-id parity bias of the sequence."""
    r = np.random.default_rng(seed)
    y = r.integers(0, classes, size=(n,)).astype(np.int64)
    x = r.integers(1, vocab, size=(n, seq_len))
    for i in range(n):
        if y[i] == 1:  # bias class-1 sequences toward even token ids
            x[i] = (x[i] // 2) * 2
    x[:, -2:] = 0  # padding tail
    return x.astype(np.int64), y


# --- function sources (what a user deploys with `kubeml fn create`) ---

# --- function sources (what a user deploys with `kubeml fn create`) ---

_IMAGE_FN = """
import jax.numpy as jnp
import numpy as np, optax
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.data import transforms as T
from kubeml_tpu.models.{module} import {model}

class Ds(KubeDataset):
    def __init__(self):
        super().__init__({dataset!r})
    def transform(self, x, y):
        # host augmentation on the quantized bytes; dequant happens on device
        if self.is_training():
            x = T.random_horizontal_flip(x)
        return x, y

class Model(KubeModel):
    def __init__(self):
        super().__init__(Ds())
    def build(self):
        return {model}(num_classes={classes})
    def preprocess(self, x):
        # device-side dequantization: uint8 [0,255] -> bf16 [-1,1]
        return x.astype(jnp.bfloat16) / 127.5 - 1.0
    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
"""

_DIGITS_FN = """
import flax.linen as nn
import jax.numpy as jnp
import optax
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset

class DigitsNet(nn.Module):
    # LeNet-style CNN sized for the 8x8 digits scans (LeNet-5 proper needs
    # >= 14x14 for its 5x5 VALID conv)
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Conv(32, (3, 3), padding="SAME")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(nn.Conv(64, (3, 3), padding="SAME")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(10)(x)

class Ds(KubeDataset):
    def __init__(self):
        super().__init__("digits-real")

class Model(KubeModel):
    def __init__(self):
        super().__init__(Ds())
    def build(self):
        return DigitsNet()
    def preprocess(self, x):
        # digits pixels are 0..16 (4-bit scans); scale on device
        return x.astype(jnp.float32) / 16.0
    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
"""

_TEXT_FN = """
import numpy as np, optax
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.bert import BertTiny

class Ds(KubeDataset):
    def __init__(self):
        super().__init__({dataset!r})

class Model(KubeModel):
    def __init__(self):
        super().__init__(Ds())
    def build(self):
        return BertTiny(num_classes={classes}, vocab_size={vocab}, max_len={seq_len})
    def configure_optimizers(self):
        return optax.adamw(self.lr)
"""

_LM_FN = """
import optax
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gpt import CausalTransformer

class Ds(KubeDataset):
    def __init__(self):
        super().__init__({dataset!r})

class Model(KubeModel):
    def __init__(self):
        super().__init__(Ds())
    def build(self):
        return CausalTransformer(vocab_size={vocab}, max_len={seq_len},
                                 embed_dim={dim}, depth={depth}, num_heads=4,
                                 mesh=self.mesh)
    def configure_optimizers(self):
        return optax.adamw(self.lr)
"""




@dataclass
class Scenario:
    name: str
    function_source: str
    make_data: Callable[[], Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]
    request: TrainRequest


def _req(fn: str, ds: str, **kw) -> TrainRequest:
    opts = kw.pop("options", {})
    return TrainRequest(
        model_type=fn, function_name=fn, dataset=ds,
        batch_size=kw.pop("batch_size", 64), epochs=kw.pop("epochs", 2),
        lr=kw.pop("lr", 0.05), options=TrainOptions(**opts),
    )


def scenarios() -> List[Scenario]:
    def images(shape, classes, n):
        def make():
            xtr, ytr = synth_images(n, shape, classes, seed=1)
            xte, yte = synth_images(max(64, n // 8), shape, classes, seed=2)
            return xtr, ytr, xte, yte

        return make

    def tokens(seq_len, vocab, classes, n):
        def make():
            xtr, ytr = synth_tokens(n, seq_len, vocab, classes, seed=1)
            xte, yte = synth_tokens(max(64, n // 8), seq_len, vocab, classes, seed=2)
            return xtr, ytr, xte, yte

        return make

    def lm_tokens(seq_len, vocab, n):
        def make():
            r = np.random.default_rng(1)
            x = r.integers(1, vocab, size=(n, seq_len)).astype(np.int64)
            x[:, -2:] = 0
            xte = r.integers(1, vocab, size=(max(64, n // 8), seq_len)).astype(np.int64)
            xte[:, -2:] = 0
            return (x, np.zeros(n, np.int64), xte, np.zeros(len(xte), np.int64))

        return make

    lenet = _IMAGE_FN.format(module="lenet", model="LeNet", dataset="mnist-bench", classes=10)
    resnet = _IMAGE_FN.format(module="resnet", model="ResNet18", dataset="cifar10-bench", classes=10)
    vit = _IMAGE_FN.format(module="vit", model="ViTTiny", dataset="cifar100-bench", classes=100)
    bert = _TEXT_FN.format(dataset="sst2-bench", classes=2, vocab=1000, seq_len=64)
    gptlm = _LM_FN.format(dataset="lm-bench", vocab=512, seq_len=32, dim=64, depth=2)

    return [
        # 0: REAL-data convergence target (sklearn handwritten digits);
        # reference counterpart: the MNIST/CIFAR experiment grids
        # (ml/experiments/app/time_to_accuracy.py:40-86)
        Scenario(
            "digits-real", _DIGITS_FN, load_digits_real,
            _req("digits-real", "digits-real", epochs=5, batch_size=32, lr=0.05,
                 options=dict(default_parallelism=2, static_parallelism=True,
                              k=4, precision="f32")),
        ),
        # 1: LeNet/MNIST single function (BASELINE target #1)
        Scenario(
            "lenet-mnist", lenet, images((28, 28, 1), 10, 640),
            _req("lenet-mnist", "mnist-bench", epochs=1, batch_size=32,
                 options=dict(default_parallelism=1, static_parallelism=True,
                              k=4, precision="f32")),
        ),
        # 2: ResNet-18/CIFAR-10 data-parallel (target #2)
        Scenario(
            "resnet18-cifar10", resnet, images((32, 32, 3), 10, 512),
            _req("resnet18-cifar10", "cifar10-bench", epochs=1, batch_size=32,
                 options=dict(default_parallelism=2, static_parallelism=True,
                              k=2, precision="f32")),
        ),
        # 3: ViT-Tiny/CIFAR-100 with train/val transform switch (target #3)
        Scenario(
            "vit-cifar100", vit, images((32, 32, 3), 100, 512),
            _req("vit-cifar100", "cifar100-bench", epochs=1, batch_size=32,
                 options=dict(default_parallelism=2, static_parallelism=True,
                              k=2, precision="f32")),
        ),
        # 4: BERT/SST-2 fine-tune over text shards (target #4)
        Scenario(
            "bert-sst2", bert, tokens(64, 1000, 2, 256),
            _req("bert-sst2", "sst2-bench", epochs=1, batch_size=16, lr=3e-4,
                 options=dict(default_parallelism=2, static_parallelism=True,
                              k=2, precision="f32")),
        ),
        # 6 (TPU-native extension beyond BASELINE's five): GPT LM over the SPMD
        # mesh engine through the same control-plane path. tp spans 2 devices
        # when the host has them; on a single chip the mesh is all-dp(1).
        Scenario(
            "gpt-lm-spmd", gptlm, lm_tokens(32, 512, 256),
            _req("gpt-lm-spmd", "lm-bench", epochs=1, batch_size=16, lr=3e-4,
                 options=dict(engine="spmd", precision="f32",
                              mesh_shape=_spmd_mesh(), validate_every=1)),
        ),
    ]


def _spmd_mesh() -> Dict[str, int]:
    import jax

    return {"tp": 2} if len(jax.devices()) >= 2 else {}


@dataclass
class ScenarioResult:
    name: str
    job_id: str
    epochs: int = 0
    train_loss: List[float] = field(default_factory=list)
    accuracy: List[float] = field(default_factory=list)
    parallelism: List[int] = field(default_factory=list)
    epoch_seconds: List[float] = field(default_factory=list)
    status: str = "ok"
    error: Optional[str] = None


class ExperimentDriver:
    """Drives scenarios through an in-process cluster (the generalization of
    the reference's threaded-PS test pattern) and collects history records."""

    def __init__(self, config: Config, max_parallelism: Optional[int] = None):
        from kubeml_tpu.functions.registry import FunctionRegistry
        from kubeml_tpu.ps.metrics import MetricsRegistry
        from kubeml_tpu.ps.parameter_server import ParameterServer
        from kubeml_tpu.scheduler.scheduler import Scheduler
        from kubeml_tpu.storage.history import HistoryStore
        from kubeml_tpu.storage.store import ShardStore

        self.cfg = config
        self.store = ShardStore(config=config)
        self.registry = FunctionRegistry(config=config)
        self.history_store = HistoryStore(config=config)
        self.ps = ParameterServer(
            registry=self.registry, store=self.store,
            history_store=self.history_store, metrics=MetricsRegistry(),
            config=config,
        )
        self.scheduler = Scheduler(
            self.ps, config=config, max_parallelism=max_parallelism
        ).start()
        self.ps.bind_scheduler(self.scheduler)

    def close(self) -> None:
        self.scheduler.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- one scenario ---

    def prepare(self, sc: Scenario) -> None:
        if not self.store.exists(sc.request.dataset):
            xtr, ytr, xte, yte = sc.make_data()
            self.store.create(sc.request.dataset, xtr, ytr, xte, yte)
        if not self.registry.exists(sc.request.function_name):
            self.registry.create(sc.request.function_name, sc.function_source)

    def wait(self, job_id: str, timeout: float = 1800.0) -> bool:
        """Poll like the reference polls `task list --short` (experiment.py:110-131).

        Completion = the history record exists (the job always persists one at
        exit, success or failure) AND the task has left the PS index. The
        index alone is not enough: a freshly-queued job is not in it yet."""
        from kubeml_tpu.api.errors import JobNotFoundError

        t0 = time.time()
        while time.time() - t0 < timeout:
            self.ps.wait(job_id, timeout=1.0)
            try:
                self.history_store.get(job_id)
            except JobNotFoundError:
                time.sleep(0.1)
                continue
            if all(t.job_id != job_id for t in self.ps.list_tasks()):
                return True
            time.sleep(0.1)
        return False

    @staticmethod
    def _job_error(hist) -> Optional[str]:
        """The error a failed job recorded into its history (engine/job.py)."""
        if isinstance(hist.task, dict) and hist.task.get("error"):
            return str(hist.task["error"])
        return None

    def run(self, sc: Scenario) -> ScenarioResult:
        job_id = ""
        try:
            self.prepare(sc)
            job_id = self.scheduler.submit_train(sc.request)
            if not self.wait(job_id):
                return ScenarioResult(sc.name, job_id, status="timeout",
                                      error="job did not finish in time")
            hist = self.history_store.get(job_id)
            err = self._job_error(hist)
            return ScenarioResult(
                name=sc.name, job_id=job_id, epochs=len(hist.train_loss),
                train_loss=hist.train_loss, accuracy=hist.accuracy,
                parallelism=hist.parallelism,
                epoch_seconds=hist.epoch_duration,
                status="failed" if err else "ok", error=err,
            )
        except Exception as e:
            return ScenarioResult(sc.name, job_id, status="error", error=str(e))

    # --- scenario 5: elastic concurrent jobs ---

    def run_elastic_multijob(self) -> ScenarioResult:
        """Two concurrent LeNet jobs with ELASTIC parallelism: both complete
        and the parallelism traces are recorded (BASELINE target #5). The
        mechanism under test is the scheduler's concurrent scale in/out."""
        sc = {s.name: s for s in scenarios()}["lenet-mnist"]
        self.prepare(sc)
        reqs = []
        for _ in range(2):
            req = TrainRequest.from_dict(sc.request.to_dict())
            req.epochs = max(2, req.epochs)
            req.options.static_parallelism = False  # the point of the scenario
            req.options.goal_accuracy = 1000.0  # never early-stop
            reqs.append(req)
        ids = [self.scheduler.submit_train(r) for r in reqs]
        if not all(self.wait(j) for j in ids):
            return ScenarioResult("elastic-multijob", ",".join(ids),
                                  status="timeout", error="a job did not finish")
        hists = [self.history_store.get(j) for j in ids]
        errors = [e for e in (self._job_error(h) for h in hists) if e]
        if errors:
            return ScenarioResult("elastic-multijob", ",".join(ids),
                                  status="failed", error="; ".join(errors))
        return ScenarioResult(
            name="elastic-multijob", job_id=",".join(ids),
            epochs=sum(len(h.train_loss) for h in hists),
            train_loss=[l for h in hists for l in h.train_loss],
            accuracy=[x for h in hists for x in h.accuracy],
            parallelism=[p for h in hists for p in h.parallelism],
            epoch_seconds=[d for h in hists for d in h.epoch_duration],
        )


# --- colocation: serving burst preempts training, training resumes ---

_COLOC_TRAIN_FN = """
import flax.linen as nn
import jax.numpy as jnp
import optax
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset

class BandNet(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(64)(x))
        return nn.Dense(10)(x)

class Ds(KubeDataset):
    def __init__(self):
        super().__init__("coloc-bands")

class Model(KubeModel):
    def __init__(self):
        super().__init__(Ds())
    def build(self):
        return BandNet()
    def preprocess(self, x):
        return x.astype(jnp.float32) / 127.5 - 1.0
    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
"""

_COLOC_SERVE_FN = """
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
"""


def run_colocation(config: Optional[Config] = None, epochs: int = 24) -> dict:
    """The multi-tenant scenario: a latency-critical serving burst colocated
    with a preemptible training run on one cluster. The preemption
    controller watches the serving overload signals, checkpoint-and-yields
    the training job mid-run, serving goes on on the reclaimed capacity, and
    once the burst clears the job is requeued with resume=True and reaches
    final-loss parity (within tolerance) with an uninterrupted run of the
    same request. Returns what the test asserts on.

    Requires KUBEML_PREEMPT_MONITOR (the caller sets the env/threshold knobs
    before the Config is built, burst-sized)."""
    import threading

    import flax.linen as nn
    import jax

    from kubeml_tpu.api.config import get_config
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.api.types import GenerateRequest
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore

    cfg = config or get_config()
    cfg.ensure_dirs()
    rng = np.random.default_rng(0)
    row: Dict = {}

    def wait_out_of_index(cluster, job_id, timeout):
        """Until the job leaves the PS index — ONLY valid once the job has
        been observed in it (a just-queued job is not in it yet)."""
        t0 = time.time()
        while time.time() - t0 < timeout:
            if all(t.job_id != job_id for t in cluster.ps.list_tasks()):
                return True
            time.sleep(0.1)
        return False

    def wait_done(cluster, job_id, timeout):
        """Done = history persisted AND out of the PS index AND not queued
        (the ExperimentDriver.wait rule: the index alone races a
        freshly-queued job)."""
        t0 = time.time()
        while time.time() - t0 < timeout:
            try:
                cluster.history_store.get(job_id)
            except Exception:
                time.sleep(0.1)
                continue
            if (all(t.job_id != job_id for t in cluster.ps.list_tasks())
                    and all(j["job_id"] != job_id
                            for j in cluster.scheduler.jobs_snapshot())):
                return True
            time.sleep(0.1)
        return False

    def train_request(job_id=""):
        return TrainRequest(
            job_id=job_id, model_type="coloc-train", function_name="coloc-train",
            dataset="coloc-bands", batch_size=16, epochs=epochs, lr=0.05,
            options=TrainOptions(default_parallelism=2, static_parallelism=True,
                                 k=2, precision="f32", validate_every=0,
                                 checkpoint_every=1, checkpoint_keep=2,
                                 priority=0, tenant="research"))

    with LocalCluster(config=cfg) as cluster:
        assert cluster.preemption is not None, (
            "run_colocation needs KUBEML_PREEMPT_MONITOR=1 in the env the "
            "Config was built from")
        # data + functions
        xtr, ytr = synth_images(256, (8, 8, 1), 10, seed=1)
        xte, yte = synth_images(64, (8, 8, 1), 10, seed=2)
        if not cluster.store.exists("coloc-bands"):
            cluster.store.create("coloc-bands", xtr, ytr, xte, yte)
        for name, src in (("coloc-train", _COLOC_TRAIN_FN),
                          ("coloc-serve", _COLOC_SERVE_FN)):
            if not cluster.registry.exists(name):
                FunctionRegistry(config=cfg).create(name, src)
        # a servable "finished" causal LM (random init exported as final)
        module = CausalTransformer(vocab_size=101, max_len=64, embed_dim=64,
                                   depth=2, num_heads=4)
        prompt = np.asarray(rng.integers(1, 101, size=(1, 8)), np.int32)
        variables = jax.tree.map(np.asarray, nn.meta.unbox(
            module.init(jax.random.PRNGKey(0), prompt)))
        CheckpointStore(config=cfg).save(
            "colocserve", variables, epoch=1, tag=FINAL_TAG,
            meta={"request": {"function_name": "coloc-serve"}})
        # warm the decoder: the burst must queue behind decode steps, not
        # behind the cold XLA compile
        cluster.scheduler.generate(GenerateRequest(
            model_id="colocserve", prompts=prompt.tolist(), max_new_tokens=4))

        # --- phase 0: uninterrupted baseline (no serving load -> the
        # controller never trips) ---
        base_id = cluster.scheduler.submit_train(train_request())
        if not wait_done(cluster, base_id, 600):
            raise RuntimeError("baseline training run did not finish")
        base_hist = cluster.history_store.get(base_id)

        # --- phase 1: colocated run under a serving burst ---
        job_id = cluster.scheduler.submit_train(train_request())
        # let training actually occupy the devices before the burst
        deadline = time.time() + 120
        while time.time() < deadline:
            if any(t.job_id == job_id for t in cluster.ps.list_tasks()):
                break
            time.sleep(0.05)
        time.sleep(0.5)

        stop_burst = threading.Event()
        reclaimed = threading.Event()
        served = {"during": 0, "after": 0}
        served_lock = threading.Lock()

        def burst_worker():
            while not stop_burst.is_set():
                try:
                    cluster.scheduler.generate(GenerateRequest(
                        model_id="colocserve", prompts=prompt.tolist(),
                        max_new_tokens=16))
                except KubeMLError:
                    # 429 under overload IS the signal, not a result; back
                    # off a beat so rejected clients don't spin the CPU
                    time.sleep(0.05)
                    continue
                except Exception:
                    time.sleep(0.05)
                    continue
                with served_lock:
                    served["after" if reclaimed.is_set() else "during"] += 1

        burst = [threading.Thread(target=burst_worker, daemon=True)
                 for _ in range(12)]
        for b in burst:
            b.start()
        # wait for the controller to reclaim (job leaves the index preempted)
        ok = wait_out_of_index(cluster, job_id, 300)
        if not ok:
            stop_burst.set()
            raise RuntimeError("the preemption controller never reclaimed "
                               "the training job")
        reclaimed.set()
        # serving keeps bursting on the reclaimed capacity for a recovery
        # window, then the burst ends and calm requeues the job
        time.sleep(6)
        stop_burst.set()
        for b in burst:
            b.join(timeout=60)

        # requeue + resumed completion
        deadline = time.time() + 600
        finished = False
        while time.time() < deadline:
            try:
                hist = cluster.history_store.get(job_id)
            except Exception:
                hist = None
            in_index = any(t.job_id == job_id
                           for t in cluster.ps.list_tasks())
            queued = any(j["job_id"] == job_id
                         for j in cluster.scheduler.jobs_snapshot())
            parked = job_id in cluster.preemption.parked_ids()
            if (hist is not None and len(hist.train_loss) >= epochs
                    and not in_index and not queued and not parked):
                finished = True
                break
            time.sleep(0.2)
        if not finished:
            raise RuntimeError("preempted job did not resume to completion")
        hist = cluster.history_store.get(job_id)

        # the live /metrics scrape when the HTTP surface is up (the
        # acceptance surface); the registry render is the same body
        if cluster.ps_api is not None:
            from kubeml_tpu.utils import traced_http

            metrics_text = traced_http.get(f"{cluster.ps_api.url}/metrics",
                                           timeout=10).text
        else:
            metrics_text = cluster.ps.metrics.render()
        row["serving"] = {
            "requests_during_contention": served["during"],
            "requests_after_reclaim": served["after"],
        }
        base_losses = base_hist.train_loss
        # tolerance: the baseline's own late-training wobble, floored — the
        # resumed run replays the interrupted epoch from mid-epoch weights,
        # so bit-equality is not the claim; convergence parity is
        tol = max(0.05, 3 * float(np.mean(np.abs(
            np.diff(base_losses[-5:])))) if len(base_losses) >= 5 else 0.05)
        delta = abs(float(hist.train_loss[-1]) - float(base_losses[-1]))
        row["resumed"] = {
            "job_id": job_id, "epochs": len(hist.train_loss),
            "final_loss": round(float(hist.train_loss[-1]), 5),
            "loss_delta_vs_baseline": round(delta, 5),
            "tolerance": round(tol, 5),
            "loss_parity": bool(delta <= tol),
        }
        row["metrics"] = {
            "preemptions_total_visible":
                "kubeml_preemptions_total" in metrics_text,
            "yield_histogram_visible":
                "kubeml_preempt_yield_seconds" in metrics_text,
            "queue_gauge_visible":
                "kubeml_scheduler_queue_depth" in metrics_text,
            "preemptions": sum(
                int(float(l.rsplit(" ", 1)[1]))
                for l in metrics_text.splitlines()
                if l.startswith("kubeml_preemptions_total{")),
        }
    return row


def run_slo_overload(config: Optional[Config] = None) -> dict:
    """The serving SLO observability chain: drive a live standalone
    cluster through an induced overload — a client burst past
    ``KUBEML_SERVING_QUEUE_LIMIT`` — and record the whole chain:

    * per-request lifecycle histograms + serving spans (``kubeml trace``
      works for a serving request id);
    * occupancy/dead-step/goodput counters on /metrics that sum
      consistently with the request-level token counts;
    * ``GET /metrics/history`` returning windowed rates from the embedded
      time-series store;
    * at least one SLO alert transitioning pending -> firing -> resolved,
      the firing delivered through the errorhook webhook (captured by a
      local sink) with the flight-recorder tail attached.

    The caller sets the env knobs — tight SLO windows, a small queue limit,
    KUBEML_TRACE — before the Config is built; returns what the test
    asserts on."""
    import http.server
    import os
    import threading

    import flax.linen as nn
    import jax

    from kubeml_tpu.api.config import get_config
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.api.types import GenerateRequest
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore
    from kubeml_tpu.utils import traced_http

    cfg = config or get_config()
    cfg.ensure_dirs()
    rng = np.random.default_rng(0)
    row: Dict = {}

    # --- local webhook sink: captures the SLO alert payloads ---
    payloads: List[dict] = []

    class _Sink(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            try:
                payloads.append(json.loads(self.rfile.read(n)))
            except Exception:
                pass
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    sink = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Sink)
    sink_thread = threading.Thread(target=sink.serve_forever, daemon=True)
    sink_thread.start()
    prior_webhook = os.environ.get("KUBEML_ERROR_WEBHOOK")
    os.environ["KUBEML_ERROR_WEBHOOK"] = \
        f"http://127.0.0.1:{sink.server_address[1]}/alert"

    def wait_for(pred, timeout, what):
        t0 = time.time()
        while time.time() - t0 < timeout:
            if pred():
                return True
            time.sleep(0.2)
        raise RuntimeError(f"timed out waiting for {what}")

    try:
        with LocalCluster(config=cfg) as cluster:
            from kubeml_tpu.functions.registry import FunctionRegistry

            if not cluster.registry.exists("slo-serve"):
                FunctionRegistry(config=cfg).create("slo-serve",
                                                    _COLOC_SERVE_FN)
            # a servable "finished" causal LM (random init exported final)
            module = CausalTransformer(vocab_size=101, max_len=64,
                                       embed_dim=64, depth=2, num_heads=4)
            prompt = np.asarray(rng.integers(1, 101, size=(1, 8)), np.int32)
            variables = jax.tree.map(np.asarray, nn.meta.unbox(
                module.init(jax.random.PRNGKey(0), prompt)))
            CheckpointStore(config=cfg).save(
                "sloserve", variables, epoch=1, tag=FINAL_TAG,
                meta={"request": {"function_name": "slo-serve",
                                  "model_type": "slo-serve"}})
            # warm the decoder: the cold XLA compile must not read as
            # an overload
            warm = cluster.scheduler.generate(GenerateRequest(
                model_id="sloserve", prompts=prompt.tolist(),
                max_new_tokens=4))
            row["serving_request_id"] = warm.get("request_id", "")

            # --- phase A: calm traffic earns availability budget ---
            calm_tokens = 0
            for _ in range(6):
                r = cluster.scheduler.generate(GenerateRequest(
                    model_id="sloserve", prompts=prompt.tolist(),
                    max_new_tokens=8))
                calm_tokens += sum(r["lengths"])
            slo0 = cluster.ps.slo_status()
            assert all(o["state"] == "inactive"
                       for o in slo0["objectives"]), "calm phase not calm"

            # --- phase B: burst past the queue limit -> 429s -> burn ---
            stop_burst = threading.Event()
            burst_tokens = [0]
            overloads_seen = [0]
            tok_lock = threading.Lock()

            def burst_worker():
                while not stop_burst.is_set():
                    try:
                        r = cluster.scheduler.generate(GenerateRequest(
                            model_id="sloserve", prompts=prompt.tolist(),
                            max_new_tokens=24))
                        with tok_lock:
                            burst_tokens[0] += sum(r["lengths"])
                    except KubeMLError:
                        with tok_lock:
                            overloads_seen[0] += 1
                        time.sleep(0.02)
                    except Exception:
                        time.sleep(0.02)

            burst = [threading.Thread(target=burst_worker, daemon=True)
                     for _ in range(10)]
            for b in burst:
                b.start()

            def firing():
                return any(o["state"] == "firing"
                           for o in cluster.ps.slo_status()["objectives"])

            wait_for(firing, 120, "an SLO alert to fire under the burst")

            # --- phase C: recovery -> the alert must resolve ---
            stop_burst.set()
            for b in burst:
                b.join(timeout=30)

            def resolved():
                status = cluster.ps.slo_status()
                # calm traffic keeps earning budget while we wait
                try:
                    cluster.scheduler.generate(GenerateRequest(
                        model_id="sloserve", prompts=prompt.tolist(),
                        max_new_tokens=4))
                except KubeMLError:
                    pass
                return (all(o["state"] == "inactive"
                            for o in status["objectives"])
                        and any(e["to"] == "resolved"
                                for e in status["events"]))

            wait_for(resolved, 180, "the SLO alert to resolve after calm")
            status = cluster.ps.slo_status()
            transitions = [(e["slo"], e["from"], e["to"])
                           for e in status["events"]]
            row["transitions"] = [
                {"slo": s, "from": f, "to": t} for s, f, t in transitions]
            fired = {s for s, _f, t in transitions if t == "firing"}
            resolved_slos = {s for s, _f, t in transitions
                             if t == "resolved"}
            pend = {s for s, _f, t in transitions if t == "pending"}
            assert fired & resolved_slos & pend, (
                f"no objective went pending->firing->resolved: {transitions}")

            # webhook evidence: the firing alert arrived with a flight tail
            wait_for(lambda: any(
                p.get("context", "").startswith("slo:") for p in payloads),
                30, "the errorhook webhook delivery")
            alert = next(p for p in payloads
                         if p.get("context", "").startswith("slo:"))
            row["alert_webhook"] = {"context": alert.get("context")}

            # --- the acceptance surfaces, scraped live over HTTP ---
            base = cluster.ps_api.url
            metrics = traced_http.get(f"{base}/metrics", timeout=10).text

            def counter(name):
                return sum(
                    float(l.rsplit(" ", 1)[1]) for l in metrics.splitlines()
                    if l.startswith(name + "{"))

            occ = {k: counter(f"kubeml_serving_occupancy_{k}_steps_total")
                   for k in ("live", "dead", "idle")}
            slot_steps = counter("kubeml_serving_occupancy_slot_steps_total")
            goodput = counter("kubeml_serving_goodput_tokens_total")
            wasted = counter("kubeml_serving_wasted_tokens_total")
            emitted = counter("kubeml_serving_tokens_total")
            assert sum(occ.values()) == slot_steps, (
                f"occupancy partition broken: {occ} != {slot_steps}")
            assert goodput + wasted == emitted, (
                f"token conservation broken: {goodput}+{wasted} != {emitted}")
            client_tokens = calm_tokens + burst_tokens[0]
            assert goodput >= client_tokens > 0, (
                f"goodput {goodput} < client-received {client_tokens}")
            row["occupancy"] = {**occ, "slot_steps": slot_steps,
                                "goodput_tokens": goodput,
                                "wasted_tokens": wasted,
                                "emitted_tokens": emitted,
                                "client_tokens": client_tokens,
                                "overloads_429": overloads_seen[0]}
            for h in ("queue_wait", "prefill", "decode_active", "slot_idle"):
                assert f"kubeml_serving_{h}_seconds_bucket" in metrics, (
                    f"phase histogram {h} missing from /metrics")

            hist = traced_http.get(
                f"{base}/metrics/history?stats=1&match=kubeml_serving",
                timeout=10).json()
            over_key = next(
                (k for k in hist["series"]
                 if k.startswith("kubeml_serving_requests_overload_total")),
                None)
            assert over_key is not None, "/metrics/history has no 429 series"
            assert "rate" in hist["series"][over_key], "no windowed rate"
            row["history"] = {
                "samples": len(hist["series"][over_key].get("samples", []))}

            # serving spans: the traced request's span tree is fetchable by
            # its request id, exactly like a train task's
            if row["serving_request_id"]:
                trace = cluster.ps.get_trace(row["serving_request_id"])
                names = {s.get("name") for s in trace["spans"]}
                assert "serving.request" in names, (
                    f"no serving.request span for "
                    f"{row['serving_request_id']}: {sorted(names)}")
                row["trace"] = {"spans": len(trace["spans"]),
                                "phases": sorted(
                                    n for n in names
                                    if str(n).startswith("serving."))}
            row["status"] = "ok"
    finally:
        sink.shutdown()
        # restore, don't just delete: a caller's real alerting endpoint
        # must survive this scenario (later scenarios keep reporting to it)
        if prior_webhook is None:
            os.environ.pop("KUBEML_ERROR_WEBHOOK", None)
        else:
            os.environ["KUBEML_ERROR_WEBHOOK"] = prior_webhook
    return row


# latency-anatomy serve model: deliberately heavier than _COLOC_SERVE_FN so
# a CPU decode step clears the first histogram bucket edge (1ms) and a
# long-prompt prefill costs ~100 decode steps — without that separation the
