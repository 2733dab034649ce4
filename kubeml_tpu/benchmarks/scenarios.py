"""The BASELINE.md benchmark scenario suite — the port of the reference's
experiment harness (reference: ml/experiments/common/experiment.py:82-182
``KubemlExperiment``: run task -> poll ``task list --short`` -> fetch
``history get`` -> persist records).

Five scenarios mirror BASELINE.md's rebuild targets:

1. ``lenet-mnist``      — single worker, goal-accuracy semantics
2. ``resnet18-cifar10`` — data-parallel K-AVG, K=8 (the headline config)
3. ``vit-cifar100``     — transforms pipeline end-to-end
4. ``bert-sst2``        — text shards (token ids) fine-tune shape
5. ``elastic-multijob`` — concurrent ResNet + LeNet on one cluster; records
   both parallelism traces (scheduler scale in/out)

Each scenario drives the REAL stack: datasets through the ShardStore, function
source through the registry, the job through scheduler -> PS -> TrainJob, and
results from the history store — the same path a user's CLI request takes.
``quick=True`` shrinks data/epochs for CI; full mode is the bench
configuration. Run: ``python -m kubeml_tpu.benchmarks.scenarios --quick``.
"""

from __future__ import annotations

import argparse
import json
import re
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..api.config import Config
from ..api.types import TrainOptions, TrainRequest

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


def load_digits_real():
    """The REAL handwritten-digits dataset shipped with scikit-learn (1,797
    8x8 scans of the UCI optical-digits corpus) — the in-environment real-data
    convergence target (no network egress here; MNIST/CIFAR arrive via
    ``scripts/seed_datasets.py mnist|cifar10`` when their files are present).
    Deterministic 80/20 split (every 5th sample is test). This is THE single
    definition — ``scripts/seed_datasets.py digits`` seeds exactly this split,
    so seeded clusters and scenario-created datasets always match."""
    from sklearn.datasets import load_digits

    d = load_digits()
    x = d.images.astype(np.uint8)[..., None]  # [1797, 8, 8, 1], 0..16
    y = d.target.astype(np.int64)
    test = np.arange(len(x)) % 5 == 0
    return x[~test], y[~test], x[test], y[test]


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
    make_data: Callable[[bool], Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]
    request: TrainRequest
    quick_request: TrainRequest


def _req(fn: str, ds: str, **kw) -> TrainRequest:
    opts = kw.pop("options", {})
    return TrainRequest(
        model_type=fn, function_name=fn, dataset=ds,
        batch_size=kw.pop("batch_size", 64), epochs=kw.pop("epochs", 2),
        lr=kw.pop("lr", 0.05), options=TrainOptions(**opts),
    )


def scenarios() -> List[Scenario]:
    def images(shape, classes, n_train, n_test, n_quick):
        def make(quick: bool):
            n = n_quick if quick else n_train
            xtr, ytr = synth_images(n, shape, classes, seed=1)
            xte, yte = synth_images(max(64, n // 8) if quick else n_test, shape, classes, seed=2)
            return xtr, ytr, xte, yte

        return make

    def tokens(seq_len, vocab, classes, n_train, n_quick):
        def make(quick: bool):
            n = n_quick if quick else n_train
            xtr, ytr = synth_tokens(n, seq_len, vocab, classes, seed=1)
            xte, yte = synth_tokens(max(64, n // 8), seq_len, vocab, classes, seed=2)
            return xtr, ytr, xte, yte

        return make

    def lm_tokens(seq_len, vocab, n_train, n_quick):
        def make(quick: bool):
            r = np.random.default_rng(1)
            n = n_quick if quick else n_train
            x = r.integers(1, vocab, size=(n, seq_len)).astype(np.int64)
            x[:, -2:] = 0
            xte = r.integers(1, vocab, size=(max(64, n // 8), seq_len)).astype(np.int64)
            xte[:, -2:] = 0
            return (x, np.zeros(n, np.int64), xte, np.zeros(len(xte), np.int64))

        return make

    def real_digits(quick: bool):
        return load_digits_real()  # quick == full: the corpus is small

    lenet = _IMAGE_FN.format(module="lenet", model="LeNet", dataset="mnist-bench", classes=10)
    resnet = _IMAGE_FN.format(module="resnet", model="ResNet18", dataset="cifar10-bench", classes=10)
    vit = _IMAGE_FN.format(module="vit", model="ViTTiny", dataset="cifar100-bench", classes=100)
    bert = _TEXT_FN.format(dataset="sst2-bench", classes=2, vocab=1000, seq_len=64)
    gptlm = _LM_FN.format(dataset="lm-bench", vocab=512, seq_len=32, dim=64, depth=2)

    return [
        # 0: REAL-data convergence target (sklearn handwritten digits) — the
        # K-AVG convergence science (TTA, K sweeps, accuracy vs global batch)
        # on real data; reference counterpart: the MNIST/CIFAR experiment
        # grids (ml/experiments/app/time_to_accuracy.py:40-86)
        Scenario(
            "digits-real", _DIGITS_FN, real_digits,
            request=_req("digits-real", "digits-real", epochs=30, batch_size=32,
                         lr=0.05,
                         options=dict(default_parallelism=2, static_parallelism=True,
                                      k=8, goal_accuracy=95.0, precision="f32")),
            quick_request=_req("digits-real", "digits-real", epochs=5, batch_size=32,
                               lr=0.05,
                               options=dict(default_parallelism=2,
                                            static_parallelism=True,
                                            k=4, precision="f32")),
        ),
        # 1: LeNet/MNIST single function (BASELINE target #1)
        Scenario(
            "lenet-mnist", lenet, images((28, 28, 1), 10, 60000, 10000, 640),
            request=_req("lenet-mnist", "mnist-bench", epochs=5, batch_size=64,
                         options=dict(default_parallelism=1, static_parallelism=True,
                                      k=8, goal_accuracy=99.0, precision="f32")),
            quick_request=_req("lenet-mnist", "mnist-bench", epochs=1, batch_size=32,
                               options=dict(default_parallelism=1, static_parallelism=True,
                                            k=4, precision="f32")),
        ),
        # 2: ResNet-18/CIFAR-10 data-parallel K=8 (headline, target #2)
        Scenario(
            "resnet18-cifar10", resnet, images((32, 32, 3), 10, 50000, 10000, 512),
            request=_req("resnet18-cifar10", "cifar10-bench", epochs=5, batch_size=128,
                         options=dict(default_parallelism=8, static_parallelism=True,
                                      k=8, precision="bf16")),
            quick_request=_req("resnet18-cifar10", "cifar10-bench", epochs=1, batch_size=32,
                               options=dict(default_parallelism=2, static_parallelism=True,
                                            k=2, precision="f32")),
        ),
        # 3: ViT-Tiny/CIFAR-100 with train/val transform switch (target #3)
        Scenario(
            "vit-cifar100", vit, images((32, 32, 3), 100, 50000, 10000, 512),
            request=_req("vit-cifar100", "cifar100-bench", epochs=5, batch_size=128,
                         options=dict(default_parallelism=4, static_parallelism=True,
                                      k=8, precision="bf16")),
            quick_request=_req("vit-cifar100", "cifar100-bench", epochs=1, batch_size=32,
                               options=dict(default_parallelism=2, static_parallelism=True,
                                            k=2, precision="f32")),
        ),
        # 4: BERT/SST-2 fine-tune over text shards (target #4)
        Scenario(
            "bert-sst2", bert, tokens(64, 1000, 2, 20000, 256),
            request=_req("bert-sst2", "sst2-bench", epochs=3, batch_size=64, lr=3e-4,
                         options=dict(default_parallelism=4, static_parallelism=True,
                                      k=8, precision="bf16")),
            quick_request=_req("bert-sst2", "sst2-bench", epochs=1, batch_size=16, lr=3e-4,
                               options=dict(default_parallelism=2, static_parallelism=True,
                                            k=2, precision="f32")),
        ),
        # 6 (TPU-native extension beyond BASELINE's five): GPT LM over the SPMD
        # mesh engine through the same control-plane path. tp spans 2 devices
        # when the host has them; on a single chip the mesh is all-dp(1).
        Scenario(
            "gpt-lm-spmd", gptlm, lm_tokens(32, 512, 20000, 256),
            request=_req("gpt-lm-spmd", "lm-bench", epochs=3, batch_size=64, lr=3e-4,
                         options=dict(engine="spmd", precision="bf16",
                                      mesh_shape=_spmd_mesh(), validate_every=1)),
            quick_request=_req("gpt-lm-spmd", "lm-bench", epochs=1, batch_size=16, lr=3e-4,
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
    epochs: int
    train_loss: List[float]
    accuracy: List[float]
    parallelism: List[int]
    epoch_seconds: List[float]
    wall_seconds: float
    samples_per_sec: float
    status: str = "ok"
    error: Optional[str] = None

    def to_dict(self) -> dict:
        return dict(self.__dict__)


class ExperimentDriver:
    """Drives scenarios through an in-process cluster (the generalization of
    the reference's threaded-PS test pattern) and collects history records."""

    def __init__(self, config: Config, max_parallelism: Optional[int] = None):
        from ..functions.registry import FunctionRegistry
        from ..ps.metrics import MetricsRegistry
        from ..ps.parameter_server import ParameterServer
        from ..scheduler.scheduler import Scheduler
        from ..storage.history import HistoryStore
        from ..storage.store import ShardStore

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

    def prepare(self, sc: Scenario, quick: bool) -> None:
        if not self.store.exists(sc.request.dataset):
            xtr, ytr, xte, yte = sc.make_data(quick)
            self.store.create(sc.request.dataset, xtr, ytr, xte, yte)
        if not self.registry.exists(sc.request.function_name):
            self.registry.create(sc.request.function_name, sc.function_source)

    def submit(self, sc: Scenario, quick: bool) -> str:
        req = sc.quick_request if quick else sc.request
        return self.scheduler.submit_train(req)

    def wait(self, job_id: str, timeout: float = 1800.0) -> bool:
        """Poll like the reference polls `task list --short` (experiment.py:110-131).

        Completion = the history record exists (the job always persists one at
        exit, success or failure) AND the task has left the PS index. The
        index alone is not enough: a freshly-queued job is not in it yet."""
        from ..api.errors import JobNotFoundError

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

    def collect(self, sc: Scenario, job_id: str, wall: float) -> ScenarioResult:
        hist = self.history_store.get(job_id)
        err = self._job_error(hist)
        n_train = self.store.get(sc.request.dataset).num_samples("train")
        total = n_train * len(hist.train_loss)
        return ScenarioResult(
            name=sc.name, job_id=job_id, epochs=len(hist.train_loss),
            train_loss=hist.train_loss, accuracy=hist.accuracy,
            parallelism=hist.parallelism, epoch_seconds=hist.epoch_duration,
            wall_seconds=wall,
            samples_per_sec=total / max(sum(hist.epoch_duration), 1e-9),
            status="failed" if err else "ok", error=err,
        )

    def run(self, sc: Scenario, quick: bool = True) -> ScenarioResult:
        t0 = time.time()
        job_id = ""
        try:
            self.prepare(sc, quick)
            job_id = self.submit(sc, quick)
            if not self.wait(job_id):
                return ScenarioResult(sc.name, job_id, 0, [], [], [], [],
                                      time.time() - t0, 0.0, "timeout",
                                      "job did not finish in time")
            return self.collect(sc, job_id, time.time() - t0)
        except Exception as e:
            return ScenarioResult(sc.name, job_id, 0, [], [], [], [],
                                  time.time() - t0, 0.0, "error", str(e))

    # --- scenario 5: elastic concurrent jobs ---

    def run_elastic_multijob(self, quick: bool = True) -> ScenarioResult:
        """Concurrent jobs with ELASTIC parallelism: both complete and the
        parallelism traces are recorded (BASELINE target #5). Full mode runs
        ResNet + LeNet (the BASELINE pair); quick mode runs LeNet + LeNet —
        the mechanism under test is the scheduler's concurrent scale in/out,
        and ResNet recompiles at each new parallelism are minutes on a CI CPU."""
        scs = {s.name: s for s in scenarios()}
        a = scs["lenet-mnist" if quick else "resnet18-cifar10"]
        b = scs["lenet-mnist"]
        for s in (a, b):
            self.prepare(s, quick)
        t0 = time.time()
        reqs = []
        for s in (a, b):
            req = TrainRequest.from_dict((s.quick_request if quick else s.request).to_dict())
            req.epochs = max(2, req.epochs)
            req.options.static_parallelism = False  # the point of the scenario
            req.options.goal_accuracy = 1000.0  # never early-stop
            reqs.append(req)
        ids = [self.scheduler.submit_train(r) for r in reqs]
        ok = all(self.wait(j) for j in ids)
        wall = time.time() - t0
        if not ok:
            return ScenarioResult("elastic-multijob", ",".join(ids), 0, [], [], [],
                                  [], wall, 0.0, "timeout", "a job did not finish")
        hists = [self.history_store.get(j) for j in ids]
        errors = [e for e in (self._job_error(h) for h in hists) if e]
        if errors:
            return ScenarioResult("elastic-multijob", ",".join(ids), 0, [], [], [],
                                  [], wall, 0.0, "failed", "; ".join(errors))
        return ScenarioResult(
            name="elastic-multijob", job_id=",".join(ids),
            epochs=sum(len(h.train_loss) for h in hists),
            train_loss=[l for h in hists for l in h.train_loss],
            accuracy=[x for h in hists for x in h.accuracy],
            parallelism=[p for h in hists for p in h.parallelism],
            epoch_seconds=[d for h in hists for d in h.epoch_duration],
            wall_seconds=wall, samples_per_sec=0.0,
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


def run_colocation(config: Optional[Config] = None, quick: bool = True,
                   epochs: Optional[int] = None) -> dict:
    """The multi-tenant flagship scenario: a latency-critical serving burst
    colocated with a preemptible training run on one cluster. The preemption
    controller watches the serving overload signals, checkpoint-and-yields
    the training job mid-run, serving latency recovers on the reclaimed
    capacity, and once the burst clears the job is requeued with resume=True
    and reaches final-loss parity (within tolerance) with an uninterrupted
    run of the same request. Returns the machine-readable row
    ``scripts/preempt_demo.sh`` appends to ``results/preempt_demo.jsonl``.

    Requires KUBEML_PREEMPT_MONITOR (the caller sets the env/threshold knobs
    before the Config is built; the demo script uses burst-sized ones)."""
    import threading

    import flax.linen as nn
    import jax

    from ..api.config import get_config
    from ..api.errors import KubeMLError
    from ..api.types import GenerateRequest
    from ..cluster import LocalCluster
    from ..functions.registry import FunctionRegistry
    from ..models.gpt import CausalTransformer
    from ..storage.checkpoint import FINAL_TAG, CheckpointStore

    cfg = config or get_config()
    cfg.ensure_dirs()
    if epochs is None:
        epochs = 24 if quick else 60
    rng = np.random.default_rng(0)
    row: Dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "scenario": "colocation-preempt", "epochs": epochs,
                 "quick": bool(quick)}

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
        # warm the decoder: the cold XLA compile must not sit inside the
        # burst's latency measurements
        cluster.scheduler.generate(GenerateRequest(
            model_id="colocserve", prompts=prompt.tolist(), max_new_tokens=4))

        # --- phase 0: uninterrupted baseline (no serving load -> the
        # controller never trips) ---
        t0 = time.time()
        base_id = cluster.scheduler.submit_train(train_request())
        if not wait_done(cluster, base_id, 600):
            raise RuntimeError("baseline training run did not finish")
        base_hist = cluster.history_store.get(base_id)
        row["baseline"] = {
            "job_id": base_id, "epochs": len(base_hist.train_loss),
            "final_loss": round(float(base_hist.train_loss[-1]), 5),
            "wall_s": round(time.time() - t0, 2)}

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
        latencies_during: List[float] = []
        latencies_after: List[float] = []
        preempted_at: List[float] = []
        lat_lock = threading.Lock()

        def burst_worker():
            while not stop_burst.is_set():
                t = time.time()
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
                lat = time.time() - t
                with lat_lock:
                    (latencies_after if preempted_at
                     else latencies_during).append(lat)

        burst = [threading.Thread(target=burst_worker, daemon=True)
                 for _ in range(12)]
        t_burst = time.time()
        for b in burst:
            b.start()
        # wait for the controller to reclaim (job leaves the index preempted)
        ok = wait_out_of_index(cluster, job_id, 300)
        if not ok:
            stop_burst.set()
            raise RuntimeError("the preemption controller never reclaimed "
                               "the training job")
        with lat_lock:
            preempted_at.append(time.time())
        row["preempt_latency_s"] = round(preempted_at[0] - t_burst, 2)
        # serving keeps bursting on the reclaimed capacity for a recovery
        # window, then the burst ends and calm requeues the job
        time.sleep(6 if quick else 12)
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

        def p99(vals):
            if not vals:
                return None
            vs = sorted(vals)
            return round(vs[min(len(vs) - 1, int(round(0.99 * (len(vs) - 1))))], 4)

        # the live /metrics scrape when the HTTP surface is up (the
        # acceptance surface); the registry render is the same body
        if cluster.ps_api is not None:
            from ..utils import traced_http

            metrics_text = traced_http.get(f"{cluster.ps_api.url}/metrics",
                                           timeout=10).text
        else:
            metrics_text = cluster.ps.metrics.render()
        row["serving"] = {
            "requests_during_contention": len(latencies_during),
            "requests_after_reclaim": len(latencies_after),
            "p99_during_s": p99(latencies_during),
            "p99_after_s": p99(latencies_after),
            "p99_recovered": bool(
                latencies_during and latencies_after
                and p99(latencies_after) <= p99(latencies_during)),
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


def run_slo_overload(config: Optional[Config] = None,
                     quick: bool = True) -> dict:
    """The serving SLO observability proof (PR 11): drive a live standalone
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

    The caller (``scripts/slo_demo.sh``) sets the env knobs — tight SLO
    windows, a small queue limit, KUBEML_TRACE — before the Config is
    built; returns the machine-readable row appended to
    ``results/slo_demo.jsonl``."""
    import http.server
    import os
    import threading

    import flax.linen as nn
    import jax

    from ..api.config import get_config
    from ..api.errors import KubeMLError
    from ..api.types import GenerateRequest
    from ..cluster import LocalCluster
    from ..models.gpt import CausalTransformer
    from ..storage.checkpoint import FINAL_TAG, CheckpointStore
    from ..utils import traced_http

    cfg = config or get_config()
    cfg.ensure_dirs()
    rng = np.random.default_rng(0)
    row: Dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "scenario": "slo-overload", "quick": bool(quick)}

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
            from ..functions.registry import FunctionRegistry

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
            # warm the decoder: the cold XLA compile must not sit inside
            # the burst's latency measurements
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
            t_burst = time.time()
            for b in burst:
                b.start()

            def firing():
                return any(o["state"] == "firing"
                           for o in cluster.ps.slo_status()["objectives"])

            wait_for(firing, 120, "an SLO alert to fire under the burst")
            row["fire_latency_s"] = round(time.time() - t_burst, 2)

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
            row["alert_webhook"] = {
                "context": alert.get("context"),
                "burn_fast": alert.get("burn_fast"),
                "flight_recorder_events": len(
                    alert.get("flight_recorder", [])),
            }

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
                "series": len(hist["series"]),
                "overload_rate_429s": hist["series"][over_key]["rate"],
                "samples": len(hist["series"][over_key].get("samples", [])),
            }

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
            row["slo_status"] = {
                o["name"]: {"state": o["state"],
                            "burn_fast": o["burn_fast"],
                            "fired": o["fired_count"]}
                for o in status["objectives"]}
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
# clean/colocated split would land in one bucket and the interference the
# demo must measure would be invisible to bucket quantiles.
_LAT_SERVE_FN = """
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
        return CausalTransformer(vocab_size=101, max_len=256,
                                 embed_dim=384, depth=6, num_heads=8)
"""


def _prom_hist(metrics_text: str, name: str,
               labels: Optional[Dict[str, str]] = None):
    """Parse one rendered histogram family's cumulative buckets (summed
    across any labels NOT in ``labels``): returns (sorted [(le, cum)],
    count). ``le`` is float('inf') for +Inf."""
    want = labels or {}
    buckets: Dict[float, float] = {}
    count = 0.0
    for line in metrics_text.splitlines():
        if not line.startswith((name + "_bucket{", name + "_count")):
            continue
        sel, _, val = line.partition("} ")
        if not val:  # _count with no labels
            sel, val = line.rsplit(" ", 1)
        pairs = dict(re.findall(r'([a-zA-Z_]+)="([^"]*)"', sel))
        if any(pairs.get(k) != v for k, v in want.items()):
            continue
        if "_bucket{" in line:
            le = float("inf") if pairs["le"] == "+Inf" else float(pairs["le"])
            buckets[le] = buckets.get(le, 0.0) + float(val)
        else:
            count += float(val)
    return sorted(buckets.items()), count


def _hist_quantile(buckets, count: float, q: float) -> float:
    """Interpolated quantile from cumulative Prometheus buckets (what
    histogram_quantile() computes) — 0.0 when the family is empty."""
    if count <= 0 or not buckets:
        return 0.0
    target = q * count
    prev_le, prev_cum = 0.0, 0.0
    for le, cum in buckets:
        if cum >= target:
            if le == float("inf"):
                return prev_le  # open-ended: lower bound, like Prometheus
            width = cum - prev_cum
            if width <= 0:
                return le
            return prev_le + (le - prev_le) * (target - prev_cum) / width
        prev_le, prev_cum = le, cum
    return prev_le


def run_latency_anatomy(config: Optional[Config] = None,
                        quick: bool = True) -> dict:
    """The serving latency-anatomy proof (PR 18): drive a live standalone
    cluster through a mixed short/long workload and record the three
    attribution signals end to end on a REAL ps /metrics scrape:

    * per-request inter-token latency: the ``inter_token_seconds``
      histogram plus ``itl_p99``/``itl_max``/``hol_stall_seconds`` riding
      every generate payload;
    * head-of-line stall: short-prompt admissions colocated with long
      decodes charge ``hol_stall_seconds_total`` to the stalled rows, and
      the decode-step histogram's ``cause="prefill_colocated"`` p99 sits
      strictly above ``cause="clean"`` (the interference, measured);
    * compile attribution: per-program ``compiles_total`` counters, the
      distinct-programs gauge, and the cold first-call walls quarantined
      in ``cold_start_seconds`` instead of the steady-state histograms.

    The caller (``scripts/latency_anatomy_demo.sh``) sets the env knobs;
    returns the row appended to ``results/latency_anatomy.jsonl``."""
    import threading

    import flax.linen as nn
    import jax

    from ..api.config import get_config
    from ..api.errors import KubeMLError
    from ..api.types import GenerateRequest
    from ..cluster import LocalCluster
    from ..models.gpt import CausalTransformer
    from ..storage.checkpoint import FINAL_TAG, CheckpointStore
    from ..utils import traced_http

    cfg = config or get_config()
    cfg.ensure_dirs()
    rng = np.random.default_rng(18)
    row: Dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "scenario": "latency-anatomy", "quick": bool(quick)}
    long_rounds = 3 if quick else 8
    short_burst = 6 if quick else 16

    with LocalCluster(config=cfg) as cluster:
        from ..functions.registry import FunctionRegistry

        if not cluster.registry.exists("lat-serve"):
            FunctionRegistry(config=cfg).create("lat-serve",
                                                _LAT_SERVE_FN)
        module = CausalTransformer(vocab_size=101, max_len=256,
                                   embed_dim=384, depth=6, num_heads=8)
        # the aggressors carry LONG prompts (expensive prefill admissions);
        # the victims carry short prompts but LONG decodes — prefill-heavy
        # requests stalling decode-heavy ones is the shape HOL attribution
        # exists to expose
        long_prompt = np.asarray(rng.integers(1, 101, size=(1, 224)),
                                 np.int32)
        short_prompt = np.asarray(rng.integers(1, 101, size=(1, 8)),
                                  np.int32)
        variables = jax.tree.map(np.asarray, nn.meta.unbox(
            module.init(jax.random.PRNGKey(0), long_prompt)))
        CheckpointStore(config=cfg).save(
            "latserve", variables, epoch=1, tag=FINAL_TAG,
            meta={"request": {"function_name": "lat-serve",
                              "model_type": "lat-serve"}})

        def gen(prompt, max_new):
            return cluster.scheduler.generate(GenerateRequest(
                model_id="latserve", prompts=prompt.tolist(),
                max_new_tokens=max_new))

        # cold request: its first-call walls must land in cold_start, not
        # in the steady-state first_token/decode_step histograms
        cold = gen(long_prompt, 8)
        row["cold_request_id"] = cold.get("request_id", "")

        # --- mixed workload: long decodes (the HOL victims) interleaved
        # with short-prompt admissions (the HOL source) ---
        results: List[dict] = []
        res_lock = threading.Lock()

        def worker(prompt, max_new, delay=0.0):
            if delay:
                time.sleep(delay)
            try:
                r = gen(prompt, max_new)
                with res_lock:
                    results.append(r)
            except KubeMLError:
                pass

        def aggressor():
            # back-to-back long-prompt admissions from ONE thread: each
            # heavy prefill dispatches while the victim rows are
            # mid-decode, without a client-side thread storm polluting the
            # clean baseline (this host may be a single core)
            for _ in range(short_burst):
                worker(long_prompt, 4)

        for _ in range(long_rounds):
            threads = [threading.Thread(
                target=worker, args=(short_prompt, 48)) for _ in range(2)]
            threads.append(threading.Thread(target=aggressor, args=()))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)

        # a clean tail: SOLO decode-only requests, run sequentially, with
        # no admissions in flight past each request's own — the clean
        # baseline the colocated quotients are judged against must not be
        # polluted by client-side contention
        for _ in range(3):
            worker(short_prompt, 64)

        assert results, "no mixed-workload request completed"
        paid = [r for r in results if r.get("hol_stall_seconds", 0) > 0]
        with_itl = [r for r in results if r.get("itl_p99", 0) > 0]
        row["requests"] = {
            "completed": len(results),
            "with_hol_stall": len(paid),
            "with_itl": len(with_itl),
            "payload_itl_p99_max": max(
                (r.get("itl_p99", 0.0) for r in results), default=0.0),
            "payload_hol_stall_max": max(
                (r.get("hol_stall_seconds", 0.0) for r in results),
                default=0.0),
        }
        assert with_itl, "no request payload carried itl_p99 > 0"

        # --- the acceptance scrape: a REAL ps /metrics over HTTP ---
        base = cluster.ps_api.url
        metrics = traced_http.get(f"{base}/metrics", timeout=10).text

        def counter(name):
            return sum(
                float(l.rsplit(" ", 1)[1]) for l in metrics.splitlines()
                if l.startswith(name + "{") or l.startswith(name + " "))

        hol = counter("kubeml_serving_hol_stall_seconds_total")
        assert hol > 0, "no head-of-line stall charged under the mix"
        row["hol_stall_seconds_total"] = hol

        itl_b, itl_n = _prom_hist(metrics,
                                  "kubeml_serving_inter_token_seconds")
        assert itl_n > 0, "inter_token histogram empty on /metrics"
        row["inter_token"] = {
            "count": itl_n,
            "p50": round(_hist_quantile(itl_b, itl_n, 0.5), 5),
            "p99": round(_hist_quantile(itl_b, itl_n, 0.99), 5),
        }

        compiles: Dict[str, float] = {}
        for line in metrics.splitlines():
            m = re.match(r'kubeml_serving_compiles_total\{[^}]*'
                         r'program="([^"]+)"[^}]*\} ([0-9.e+-]+)', line)
            if m:
                compiles[m.group(1)] = (compiles.get(m.group(1), 0)
                                        + float(m.group(2)))
        assert compiles, "no per-program compile counters on /metrics"
        assert len(compiles) >= 2, (
            f"expected prefill AND step programs compiled: {compiles}")
        row["compiles"] = compiles
        row["compiled_programs"] = counter(
            "kubeml_serving_compiled_programs")
        cold_b, cold_n = _prom_hist(metrics,
                                    "kubeml_serving_cold_start_seconds")
        assert cold_n > 0, "cold first-call walls not quarantined"
        row["cold_start_count"] = cold_n

        # --- the headline: clean decode steps are strictly faster than
        # steps whose dispatch was colocated with admission/prefill ---
        clean_b, clean_n = _prom_hist(
            metrics, "kubeml_serving_decode_step_seconds",
            {"cause": "clean"})
        coloc_b, coloc_n = _prom_hist(
            metrics, "kubeml_serving_decode_step_seconds",
            {"cause": "prefill_colocated"})
        assert clean_n > 0, "no clean decode steps measured"
        assert coloc_n > 0, "no prefill-colocated decode steps measured"
        clean_p99 = _hist_quantile(clean_b, clean_n, 0.99)
        coloc_p99 = _hist_quantile(coloc_b, coloc_n, 0.99)
        row["decode_step_p99"] = {"clean": round(clean_p99, 6),
                                  "prefill_colocated": round(coloc_p99, 6),
                                  "clean_steps": clean_n,
                                  "colocated_steps": coloc_n}
        assert clean_p99 < coloc_p99, (
            f"clean decode-step p99 {clean_p99:.6f}s not below colocated "
            f"{coloc_p99:.6f}s — HOL attribution shows no interference")

        # the sampled rings carry the new series for `kubeml top`
        hist = traced_http.get(
            f"{base}/metrics/history?stats=1&match=kubeml_serving",
            timeout=10).json()
        series = hist.get("series", {})
        row["history"] = {
            "hol_series": any(k.startswith(
                "kubeml_serving_hol_stall_seconds_total")
                for k in series),
            "compile_series": any(k.startswith(
                "kubeml_serving_compiles_total") for k in series),
            "itl_series": any(k.startswith(
                "kubeml_serving_itl_p99_seconds") for k in series),
        }

        # lifecycle spans: the traced request carries the new fields
        if row["cold_request_id"]:
            trace = cluster.ps.get_trace(row["cold_request_id"])
            req = next((s for s in trace["spans"]
                        if s.get("name") == "serving.request"), None)
            if req is not None:
                attrs = req.get("attrs") or req.get("args") or {}
                row["trace_fields"] = sorted(
                    k for k in ("itl_p99", "hol_stall_seconds")
                    if k in attrs)
                assert "itl_p99" in attrs, (
                    f"serving.request span lacks itl_p99: {sorted(attrs)}")
        row["status"] = "ok"
    return row


def run_chunked_prefill(config: Optional[Config] = None, quick: bool = True,
                        chunk_tokens: int = 32) -> dict:
    """The chunked-prefill proof (PR 19): replay ONE deterministic mixed
    short/long workload twice through a live standalone cluster — first
    monolithic (``KUBEML_PREFILL_CHUNK_TOKENS=0``, the PR-18 behavior),
    then chunked — and record, from REAL ps /metrics scrapes:

    * ``hol_stall_seconds`` total and per completed request: interleaving
      page-aligned prefill chunks with decode lets victim rows' work
      finish dispatching between chunks, so later chunks charge fewer
      stalled rows than one monolithic prefill wall charged all of them;
    * clean-vs-colocated decode-step p99: a decode chunk colocated with a
      bounded chunk shares the device with far less prefill work than one
      colocated with a whole 224-token prompt;
    * ITL p99 (payload + histogram) on the same workload;
    * greedy token parity across the two modes, request by request — the
      scheduling change must not move a single sampled token.

    Every aggressor prompt is DISTINCT (no prefix sharing), so each long
    admission is a full cold prefill — the head-of-line shape chunking
    exists to fix. Returns the row ``scripts/chunked_prefill_demo.sh``
    appends to ``results/chunked_prefill.jsonl``."""
    import dataclasses
    import threading

    import flax.linen as nn
    import jax

    from ..api.config import get_config
    from ..api.errors import KubeMLError
    from ..api.types import GenerateRequest
    from ..cluster import LocalCluster
    from ..models.gpt import CausalTransformer
    from ..storage.checkpoint import FINAL_TAG, CheckpointStore
    from ..utils import traced_http

    cfg = config or get_config()
    cfg.ensure_dirs()
    rng = np.random.default_rng(19)
    rounds = 2 if quick else 5
    per_round = 3 if quick else 6
    # victims sized to DRAIN inside the interleave window (max_new 16 ~
    # four decode chunks at the demo's chunk_steps=4, vs ~7 prefill
    # dispatches per 224-token prompt at chunk 32): the accounted HOL win
    # is at-dispatch retirement removing a victim from later chunks'
    # stalled snapshots — a victim outliving the whole prefill is charged
    # for every chunk and sees no accounted win, only the ITL one
    victim_new = 16

    # one workload, generated once and replayed verbatim in both modes
    long_prompts = [np.asarray(rng.integers(1, 101, size=(1, 224)), np.int32)
                    for _ in range(rounds * per_round)]
    short_prompt = np.asarray(rng.integers(1, 101, size=(1, 8)), np.int32)

    module = CausalTransformer(vocab_size=101, max_len=256,
                               embed_dim=384, depth=6, num_heads=8)
    variables = jax.tree.map(np.asarray, nn.meta.unbox(
        module.init(jax.random.PRNGKey(0), long_prompts[0])))

    def one_pass(knob: int) -> Tuple[dict, Dict[str, list]]:
        mode_cfg = dataclasses.replace(cfg, prefill_chunk_tokens=knob)
        tokens: Dict[str, list] = {}
        payloads: List[dict] = []
        res_lock = threading.Lock()
        with LocalCluster(config=mode_cfg) as cluster:
            from ..functions.registry import FunctionRegistry

            if not cluster.registry.exists("lat-serve"):
                FunctionRegistry(config=mode_cfg).create("lat-serve",
                                                         _LAT_SERVE_FN)
            CheckpointStore(config=mode_cfg).save(
                "cpserve", variables, epoch=1, tag=FINAL_TAG,
                meta={"request": {"function_name": "lat-serve",
                                  "model_type": "lat-serve"}})

            def gen(prompt, max_new):
                return cluster.scheduler.generate(GenerateRequest(
                    model_id="cpserve", prompts=prompt.tolist(),
                    max_new_tokens=max_new))

            # warm both program families so first-call compile walls don't
            # drown the steady-state contrast (they quarantine regardless)
            gen(long_prompts[0], 2)
            gen(short_prompt, 2)

            def worker(key, prompt, max_new):
                try:
                    r = gen(prompt, max_new)
                    with res_lock:
                        tokens[key] = list(r["tokens"][0])
                        payloads.append(r)
                except KubeMLError:
                    pass

            def aggressor(round_i):
                # back-to-back DISTINCT cold long prompts from one thread
                for j in range(per_round):
                    i = round_i * per_round + j
                    worker(f"long-{i}", long_prompts[i], 2)

            for r_i in range(rounds):
                victims = [threading.Thread(
                    target=worker, args=(f"victim-{r_i}-{v}", short_prompt,
                                         victim_new)) for v in range(2)]
                for t in victims:
                    t.start()
                # let the victims land in slots before the first long
                # prompt arrives (same stagger replayed in both modes)
                time.sleep(0.05)
                agg = threading.Thread(target=aggressor, args=(r_i,))
                agg.start()
                for t in victims + [agg]:
                    t.join(timeout=300)

            # a short clean tail so cause="clean" decode steps exist
            for i in range(2):
                worker(f"clean-{i}", short_prompt, 32)

            base = cluster.ps_api.url
            metrics = traced_http.get(f"{base}/metrics", timeout=10).text

        def counter(name):
            return sum(
                float(l.rsplit(" ", 1)[1]) for l in metrics.splitlines()
                if l.startswith(name + "{") or l.startswith(name + " "))

        completed = len(payloads)
        assert completed, "no workload request completed"
        hol = counter("kubeml_serving_hol_stall_seconds_total")
        itl_b, itl_n = _prom_hist(metrics,
                                  "kubeml_serving_inter_token_seconds")
        clean_b, clean_n = _prom_hist(
            metrics, "kubeml_serving_decode_step_seconds",
            {"cause": "clean"})
        coloc_b, coloc_n = _prom_hist(
            metrics, "kubeml_serving_decode_step_seconds",
            {"cause": "prefill_colocated"})
        summary = {
            "prefill_chunk_tokens": knob,
            "requests_completed": completed,
            "hol_stall_seconds": round(hol, 6),
            "hol_stall_seconds_per_request": round(hol / completed, 6),
            "prefill_chunks": counter(
                "kubeml_serving_prefill_chunks_total"),
            "prefill_chunk_tokens_total": counter(
                "kubeml_serving_prefill_chunk_tokens_total"),
            "itl_p99": round(_hist_quantile(itl_b, itl_n, 0.99), 6),
            "payload_chunks_max": max(
                (p.get("prefill_chunks", 0) for p in payloads), default=0),
            "decode_step_p99": {
                "clean": round(_hist_quantile(clean_b, clean_n, 0.99), 6),
                "prefill_colocated": round(
                    _hist_quantile(coloc_b, coloc_n, 0.99), 6),
                "clean_steps": clean_n,
                "colocated_steps": coloc_n,
            },
        }
        return summary, tokens

    row: Dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "scenario": "chunked-prefill", "quick": bool(quick),
                 "chunk_tokens": int(chunk_tokens)}
    mono, mono_tokens = one_pass(0)
    chunked, chunked_tokens = one_pass(chunk_tokens)
    row["monolithic"] = mono
    row["chunked"] = chunked

    # greedy token parity, request by request across the replayed workload
    shared = sorted(set(mono_tokens) & set(chunked_tokens))
    assert shared, "no request completed in BOTH modes"
    mismatched = [k for k in shared
                  if mono_tokens[k] != chunked_tokens[k]]
    assert not mismatched, (
        f"chunked prefill moved sampled tokens: {mismatched}")
    row["token_parity_requests"] = len(shared)

    assert mono["prefill_chunks"] == 0, "monolithic pass reported chunks"
    assert chunked["prefill_chunks"] > 0, (
        "chunked pass dispatched no prefill chunks — knob did not reach "
        "the engine")
    assert chunked["payload_chunks_max"] > 1, (
        "no generate payload reported prefill_chunks > 1")
    # the headline: less decode time lost behind prefill, cheaper
    # colocated decode steps (the bench gate re-checks the per-request
    # number with bench_compare's threshold semantics)
    row["hol_stall_seconds_per_request"] = (
        chunked["hol_stall_seconds_per_request"])
    assert (chunked["hol_stall_seconds_per_request"]
            < mono["hol_stall_seconds_per_request"]), (
        f"chunked HOL/request {chunked['hol_stall_seconds_per_request']} "
        f"not below monolithic {mono['hol_stall_seconds_per_request']}")
    assert (chunked["decode_step_p99"]["prefill_colocated"]
            < mono["decode_step_p99"]["prefill_colocated"]), (
        "chunked colocated decode-step p99 not below monolithic")
    row["status"] = "ok"
    return row


# serving-recovery serve model (ISSUE 20): deliberately tiny — the chaos
# storm replays every stream TWICE (baseline + faulted) and the drain hop
# boots two more python processes, so compile time dominates wall clock
_SNAP_SERVE_FN = """
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
        return CausalTransformer(vocab_size=101, max_len=64,
                                 embed_dim=64, depth=2, num_heads=4)
"""

# drain half of the cross-process hop: boots a full cluster, gets streams
# mid-decode, POSTs /serving/drain over the real wire (PSClient), proves
# the 429 gate + the retryable-503-with-partials waiter contract, and
# leaves KMS1 frames in KUBEML_SNAP_DIR for a process that does not exist
# yet. Talks to the parent scenario via one JSON line on stdout.
_DRAIN_PROC = """
import json, sys, time
import numpy as np
from kubeml_tpu.api.config import get_config
from kubeml_tpu.api.errors import EngineFaultError, KubeMLError
from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.cluster import LocalCluster
from kubeml_tpu.ps.transport import PSClient
from kubeml_tpu.serving import kvsnap

cfg = get_config()
out = {"refs": {}, "partials": {}, "files": [], "gate_429": False}
with LocalCluster(config=cfg) as cluster:
    def gen(prompt, n):
        return cluster.scheduler.generate(GenerateRequest(
            model_id="snapserve", prompts=[prompt], max_new_tokens=n))

    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(1, 101, size=l)]
               for l in (9, 13)]
    # uninterrupted references FIRST (same decoder, greedy => replayable)
    for p in prompts:
        out["refs"][str(len(p))] = gen(p, 40)["tokens"][0][:40]
    dec = cluster.ps._decoders["snapserve"][0]
    # throttle decode so the requests are still MID-STREAM when the drain
    # lands: a warm engine this tiny would otherwise run 40 tokens out
    # before the POST crosses the wire (a real model's chunk takes longer
    # than an HTTP hop; this stands in for that)
    _orig = dec._dispatch_chunk_paged
    def _slow(*a, **kw):
        time.sleep(0.3)
        return _orig(*a, **kw)
    dec._dispatch_chunk_paged = _slow
    entries = [dec.submit(GenerateRequest(prompts=[p], max_new_tokens=40))
               for p in prompts]
    deadline = time.time() + 120
    while time.time() < deadline:
        if all(e.rows[0].out for e in entries):
            break
        time.sleep(0.01)
    client = PSClient(cluster.ps_api.url)
    # grace 0: snapshot the mid-stream rows NOW
    drain = client.drain_serving(grace=0.0)
    for path in drain.get("written", []):
        with open(path, "rb") as f:
            hdr = kvsnap.peek_header(f.read())
        out["files"].append({"request_id": hdr["request_id"],
                             "prompt_len": hdr["prompt_len"]})
    for p, e in zip(prompts, entries):
        try:
            dec.wait(e, timeout=60)
        except EngineFaultError as err:
            out["partials"][str(len(p))] = err.partial_tokens[0]
    try:
        gen(prompts[0], 2)
    except KubeMLError as err:
        out["gate_429"] = (err.status_code == 429)
print("DRAIN_RESULT " + json.dumps(out))
"""

# restore half: a FRESH process (new arena, new page pool, nothing shared
# but the checkpoint store and KUBEML_SNAP_DIR) whose PS replays the
# drained requests at boot; /serving/restored reports their completions.
_RESTORE_PROC = """
import json, time
from kubeml_tpu.api.config import get_config
from kubeml_tpu.cluster import LocalCluster
from kubeml_tpu.ps.transport import PSClient

cfg = get_config()
with LocalCluster(config=cfg) as cluster:
    client = PSClient(cluster.ps_api.url)
    recs = []
    deadline = time.time() + 300
    while time.time() < deadline:
        recs = client.serving_restored()
        if recs and all(r["done"] or r["error"] for r in recs):
            break
        time.sleep(0.25)
print("RESTORE_RESULT " + json.dumps({"restored": recs}))
"""


def run_serving_recovery(config: Optional[Config] = None,
                         quick: bool = True) -> dict:
    """The mid-stream serving-recovery proof (ISSUE 20), two halves:

    CHAOS — one live cluster serves >= 8 concurrent mixed-length greedy
    streams through the paged engine with EVERYTHING on at once: a
    prefix-shared prompt pair, int8 KV pages and self-speculative
    decoding. An injected engine fault lands mid-decode; the engine
    snapshots resident rows to KMS1, rebuilds the arena and replays them.
    Every stream must finish bit-identical to its uninterrupted baseline,
    the page pool must audit clean, and the snapshot/audit counters must
    be visible on a REAL ps /metrics scrape.

    DRAIN — one python process boots a cluster, gets requests mid-stream,
    drains over the wire (POST /serving/drain) and exits; a SECOND fresh
    process restores the KMS1 files from KUBEML_SNAP_DIR at boot and
    finishes them bit-identical to the first process's references.

    Returns the row ``scripts/serving_recovery_demo.sh`` appends to
    ``results/serving_recovery.jsonl``."""
    import dataclasses
    import os
    import shutil
    import subprocess
    import sys
    import threading

    import flax.linen as nn
    import jax

    from ..api.config import get_config
    from ..api.types import GenerateRequest
    from ..cluster import LocalCluster
    from ..functions.registry import FunctionRegistry
    from ..models.gpt import CausalTransformer
    from ..storage.checkpoint import FINAL_TAG, CheckpointStore
    from ..utils import traced_http

    cfg = config or get_config()
    cfg.ensure_dirs()
    row: Dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "scenario": "serving-recovery", "quick": bool(quick)}

    module = CausalTransformer(vocab_size=101, max_len=64, embed_dim=64,
                               depth=2, num_heads=4)
    variables = jax.tree.map(np.asarray, nn.meta.unbox(
        module.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))))
    if not FunctionRegistry(config=cfg).exists("snap-serve"):
        FunctionRegistry(config=cfg).create("snap-serve", _SNAP_SERVE_FN)
    CheckpointStore(config=cfg).save(
        "snapserve", variables, epoch=1, tag=FINAL_TAG,
        meta={"request": {"function_name": "snap-serve",
                          "model_type": "snap-serve"}})

    # --- half 1: the chaos storm (int8 KV + spec=self + prefix sharing) ---
    streams = 8 if quick else 12
    chaos_cfg = dataclasses.replace(
        cfg, kv_quant="int8", serving_spec="self", spec_exit_layer=1,
        spec_k=2, serving_slots=3, serving_chunk_steps=4,
        serving_page_tokens=4, serving_prefix_cache=True,
        pool_audit_interval=0.05)
    rng = np.random.default_rng(11)
    sysp = [int(t) for t in rng.integers(1, 101, size=12)]
    prompts = [sysp + [int(t) for t in rng.integers(1, 101, size=3 + i)]
               for i in range(2)]      # the prefix-shared pair
    prompts += [[int(t) for t in rng.integers(1, 101, size=l)]
                for l in (3, 9, 5, 12, 7, 16, 4, 10, 6, 14)[:streams - 2]]
    max_news = ([14, 9, 6, 17, 8, 11, 12, 16] * 2)[:streams]
    tokens: Dict[int, list] = {}
    finished = {"n": 0}
    retried = {"n": 0}
    res_lock = threading.Lock()
    with LocalCluster(config=chaos_cfg) as cluster:
        from ..api.errors import EngineFaultError

        def gen(prompt, n):
            return cluster.scheduler.generate(GenerateRequest(
                model_id="snapserve", prompts=[prompt], max_new_tokens=n))

        refs = [gen(p, n)["tokens"][0][:n]
                for p, n in zip(prompts, max_news)]
        dec = cluster.ps._decoders["snapserve"][0]

        def worker(i):
            try:
                r = gen(prompts[i], max_news[i])
            except EngineFaultError as err:
                # a row the fault caught fully-dispatched (pages already
                # released) is unsalvageable BY DESIGN: its waiter gets the
                # deterministic retryable 503 + partial tokens, and doing
                # what the envelope says must land on the rebuilt engine
                assert err.retryable and err.status_code == 503
                assert err.partial_tokens is not None
                with res_lock:
                    retried["n"] += 1
                r = gen(prompts[i], max_news[i])
            with res_lock:
                tokens[i] = r["tokens"][0][:max_news[i]]
                finished["n"] += 1

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(streams)]
        for t in threads:
            t.start()
        # arm the fault once the FIRST token of the storm lands: every
        # stream is mid-flight (resident mid-decode or queued), none done
        state = {"armed": True}

        def poison(fn):
            def boom(*a, **kw):
                if state["armed"]:
                    state["armed"] = False
                    raise RuntimeError("scenario-injected device fault")
                return fn(*a, **kw)
            return boom

        deadline = time.time() + 300
        while time.time() < deadline:
            with dec._cond:
                hot = any(r is not None and r.out for r in dec._slot_rows)
            if hot:
                break
            time.sleep(0.005)
        live_at_fault = streams - finished["n"]
        dec._dispatch_chunk_paged = poison(dec._dispatch_chunk_paged)
        dec._dispatch_spec_chunk = poison(dec._dispatch_spec_chunk)
        for t in threads:
            t.join(timeout=600)
        assert not state["armed"], "the injected fault never fired"
        assert len(tokens) == streams, (
            f"only {len(tokens)}/{streams} streams completed after the "
            f"fault")
        mismatched = [i for i in range(streams) if tokens[i] != refs[i]]
        assert not mismatched, (
            f"recovery moved sampled tokens in streams {mismatched}")
        chk = dec._pool.check()
        assert chk["held"] == chk["trie_pages"], f"leaked pages: {chk}"
        if dec._pool.trie is not None:
            dec._pool.trie.flush()
            assert dec._pool.check()["held"] == 0
        metrics = traced_http.get(f"{cluster.ps_api.url}/metrics",
                                  timeout=10).text

    def counter(name):
        return sum(
            float(l.rsplit(" ", 1)[1]) for l in metrics.splitlines()
            if l.startswith(name + "{") or l.startswith(name + " "))

    chaos = {
        "streams": streams, "prefix_shared": 2, "live_at_fault":
        live_at_fault, "kv_quant": "int8", "spec": "self",
        "parity_streams": streams, "retried_streams": retried["n"],
        "snapshot_saved": counter("kubeml_serving_snapshot_saved_total"),
        "snapshot_restored": counter(
            "kubeml_serving_snapshot_restored_total"),
        "snapshot_replayed": counter(
            "kubeml_serving_snapshot_replayed_total"),
        "snapshot_failed": counter("kubeml_serving_snapshot_failed_total"),
        "pool_audit_runs": counter("kubeml_serving_pool_audit_runs_total"),
        "pool_audit_failures": counter(
            "kubeml_serving_pool_audit_failures_total"),
    }
    assert chaos["live_at_fault"] >= 8, (
        f"only {chaos['live_at_fault']} streams were live at the fault")
    assert chaos["snapshot_replayed"] >= 1, (
        "no snapshot replayed through the fault (counters from the ps "
        "/metrics scrape)")
    # every snapshot failure must map to a stream the retryable-503
    # contract re-ran (doomed draining rows fail without a counter)
    assert chaos["snapshot_failed"] <= retried["n"]
    assert chaos["pool_audit_runs"] >= 1, "the pool-audit watchdog never ran"
    assert chaos["pool_audit_failures"] == 0
    row["chaos"] = chaos

    # --- half 2: graceful drain, restored by a process born later ---
    # (CPU-box only: both hops boot a PS in a child while this process,
    # which served half 1, still holds the accelerator — and a chip belongs
    # to one process at a time)
    snap_dir = str(Path(cfg.data_root) / "serving_snapshots_demo")
    shutil.rmtree(snap_dir, ignore_errors=True)
    env = dict(os.environ, KUBEML_DATA_ROOT=str(cfg.data_root),
               KUBEML_SNAP_DIR=snap_dir)
    # the drain hop serves plain f32 (raw KMS1 float pages, bit-exact):
    # the chaos half already covered the int8 + spec composition
    for k in ("KUBEML_KV_QUANT", "KUBEML_SERVING_SPEC"):
        env.pop(k, None)
    repo_root = str(Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")

    def hop(script, tag):
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              cwd=repo_root, capture_output=True, text=True,
                              timeout=900)
        for line in proc.stdout.splitlines():
            if line.startswith(tag + " "):
                return json.loads(line[len(tag) + 1:])
        raise AssertionError(
            f"{tag} process failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")

    drained = hop(_DRAIN_PROC, "DRAIN_RESULT")
    assert drained["gate_429"], "draining ps did not 429 new admissions"
    assert len(drained["files"]) == 2, drained
    for plen, partial in drained["partials"].items():
        ref = drained["refs"][plen]
        assert partial and partial == ref[:len(partial)], (
            f"partial tokens not a prefix of the reference (plen={plen})")
    restored = hop(_RESTORE_PROC, "RESTORE_RESULT")["restored"]
    assert len(restored) == 2, restored
    by_rid = {f["request_id"]: str(f["prompt_len"])
              for f in drained["files"]}
    for rec in restored:
        assert rec["done"] and not rec["error"], rec
        ref = drained["refs"][by_rid[rec["request_id"]]]
        got = rec["tokens"][0][:rec["lengths"][0]]
        assert got == ref, (
            f"cross-process restore moved tokens for {rec['request_id']}")
    leftovers = [f for f in os.listdir(snap_dir)
                 if f.endswith(".kms")] if os.path.isdir(snap_dir) else []
    assert not leftovers, f"restored snapshots not consumed: {leftovers}"
    row["drain"] = {
        "snapshots_written": len(drained["files"]),
        "restored": len(restored),
        "partials_prefix_of_reference": True,
        "gate_429": True,
        "cross_process_parity_requests": len(restored),
    }
    row["status"] = "ok"
    return row


# elastic-observability demo function: a tiny MLP whose DATASET carries a
# controllable host-side brake — when the sentinel file named by
# KUBEML_ELASTIC_OBS_BRAKE exists, every round's transform sleeps, slowing
# the epoch past the policy's 1.2x slowdown threshold. The scenario flips
# the brake mid-run to drive a REAL scale-down decision deterministically
# (epoch-time jitter alone cannot guarantee one on a shared CI box).
_ELASTIC_OBS_FN = """
import os
import time

import flax.linen as nn
import optax

from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset

_BRAKE = os.environ.get("KUBEML_ELASTIC_OBS_BRAKE", "")
_SLEEP_S = float(os.environ.get("KUBEML_ELASTIC_OBS_SLEEP", "0.6"))


class Net(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(32)(x))
        return nn.Dense(10)(x)


class Ds(KubeDataset):
    def __init__(self):
        super().__init__("elastic-obs")

    def transform(self, x, y):
        # controlled straggler: one sleep per round slab while the brake
        # sentinel exists (host data path — the device program is untouched)
        if _BRAKE and os.path.exists(_BRAKE):
            time.sleep(_SLEEP_S)
        return x, y


class Model(KubeModel):
    def __init__(self):
        super().__init__(Ds())

    def build(self):
        return Net()

    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
"""


def run_elastic_observability(config: Optional[Config] = None,
                              quick: bool = True) -> dict:
    """The elastic-training decision-observability proof (PR 13): drive a
    live elastic K-AVG job through >= 1 scale-up and >= 1 scale-down and
    record the whole chain:

    * every transition retrievable via ``GET /jobs/{id}/decisions``
      (controller proxy) with its from->to, direction, enumerated reason,
      and full policy inputs — and rendered by ``kubeml decisions``;
    * ``kubeml_scale_decisions_total{direction,reason}`` on /metrics;
    * ``kubeml_job_parallelism`` and ``kubeml_job_worker_divergence``
      per-job series present in ``GET /metrics/history`` (the tsdb sample
      the `kubeml top` training rows read);
    * the per-epoch History record carrying worker divergence, loss
      spread, and round skew.

    The scale-down is driven deterministically: after the policy has
    banked a fast cached epoch time, the scenario creates the brake
    sentinel (see ``_ELASTIC_OBS_FN``) and the next epoch lands past the
    1.2x slowdown threshold. Returns the machine-readable row
    ``scripts/elastic_obs_demo.sh`` appends to
    ``results/elastic_obs.jsonl``."""
    import os
    import tempfile

    from ..api.config import get_config

    cfg = config or get_config()
    cfg.ensure_dirs()
    row: Dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "scenario": "elastic-obs", "quick": bool(quick)}
    # restore-on-exit, same discipline as run_slo_overload's webhook swap:
    # a later in-process scenario must not inherit this run's brake path
    prior_brake = os.environ.get("KUBEML_ELASTIC_OBS_BRAKE")
    brake = prior_brake or str(Path(tempfile.mkdtemp()) / "brake")
    os.environ["KUBEML_ELASTIC_OBS_BRAKE"] = brake

    def wait_for(pred, timeout, what):
        t0 = time.time()
        while time.time() - t0 < timeout:
            v = pred()
            if v:
                return v
            time.sleep(0.2)
        raise RuntimeError(f"timed out waiting for {what}")

    epochs = 20 if quick else 32
    try:
        return _run_elastic_observability(cfg, epochs, brake, row, wait_for)
    finally:
        if prior_brake is None:
            os.environ.pop("KUBEML_ELASTIC_OBS_BRAKE", None)
        else:
            os.environ["KUBEML_ELASTIC_OBS_BRAKE"] = prior_brake


def _run_elastic_observability(cfg, epochs, brake, row, wait_for) -> dict:
    """The scenario body (see :func:`run_elastic_observability`)."""
    import contextlib
    import io
    from collections import Counter as _Counter

    from ..cli import main as cli_main
    from ..controller.client import KubemlClient
    from ..cluster import LocalCluster
    from ..scheduler.decisions import REASONS
    from ..utils import traced_http

    with LocalCluster(config=cfg) as cluster:
        client = KubemlClient(cluster.controller_url)
        x, y = synth_images(256, (8, 8, 1), 10, 0)
        client.datasets().create("elastic-obs", x, y, x[:64], y[:64])
        client.functions().create("elastic-obs", _ELASTIC_OBS_FN)
        req = TrainRequest(
            batch_size=16, epochs=epochs, dataset="elastic-obs", lr=0.01,
            function_name="elastic-obs",
            options=TrainOptions(default_parallelism=2, k=2,
                                 validate_every=0, save_model=False))
        job_id = client.networks().train(req)
        row["job_id"] = job_id

        # phase A (brake off): the first epoch report always scales up
        # (cache seeded at infinity); let the policy bank >= 2 fast cached
        # epochs so the brake's slowdown compares against a fast baseline
        wait_for(lambda: client.tasks().decisions(job_id)["total"] >= 3,
                 180, "three recorded decisions (new-task + 2 reports)")
        Path(brake).touch()
        t_brake = time.time()
        try:
            wait_for(lambda: any(
                d["direction"] == "down"
                for d in client.tasks().decisions(job_id)["decisions"]),
                180, "a scale-down decision after the brake")
            row["down_latency_s"] = round(time.time() - t_brake, 2)
        finally:
            # release the brake so the remaining epochs finish quickly (and
            # often earn a second scale-up on the recovered epoch time)
            with contextlib.suppress(OSError):
                Path(brake).unlink()

        # the job need not run its full epoch budget: the decisions are in
        client.tasks().stop(job_id)
        wait_for(lambda: all(t.job_id != job_id
                             for t in client.tasks().list()),
                 120, "the job to finish")

        # --- the audit trail: complete, enumerated, inputs attached ---
        data = client.tasks().decisions(job_id)
        decisions = data["decisions"]
        directions = [d["direction"] for d in decisions]
        assert "up" in directions, f"no scale-up recorded: {directions}"
        assert "down" in directions, f"no scale-down recorded: {directions}"
        for d in decisions:
            assert d["reason"] in REASONS, f"unenumerated reason: {d}"
            inputs = d["inputs"]
            assert inputs["cap"] >= 1 and inputs["slowdown_threshold"] > \
                inputs["speedup_threshold"] > 0, f"inputs missing: {d}"
        down = next(d for d in decisions if d["direction"] == "down")
        assert down["inputs"]["elapsed"] >= (
            down["inputs"]["cached"] * down["inputs"]["slowdown_threshold"]), \
            f"down decision inputs don't justify it: {down}"
        row["decisions"] = {
            "total": data["total"],
            "directions": dict(_Counter(directions)),
            "reasons": dict(_Counter(d["reason"] for d in decisions)),
            "transitions": [[d["from"], d["to"]] for d in decisions],
        }

        # --- the decision counters on the exposition ---
        metrics = traced_http.get(f"{cluster.ps_api.url}/metrics",
                                  timeout=10).text
        assert 'kubeml_scale_decisions_total{direction="up"' in metrics
        assert 'kubeml_scale_decisions_total{direction="down"' in metrics

        # --- per-job training series in the embedded tsdb ---
        hist = client.metrics_history(match="kubeml_job_", stats=True)
        series = hist["series"]
        par_key = f'kubeml_job_parallelism{{jobid="{job_id}"}}'
        div_key = f'kubeml_job_worker_divergence{{jobid="{job_id}"}}'
        assert par_key in series and series[par_key].get("samples"), \
            f"no parallelism series sampled (have {sorted(series)[:8]}...)"
        assert div_key in series, "no worker-divergence series sampled"
        par_values = sorted({v for _t, v in series[par_key]["samples"]})
        row["history_series"] = {
            "parallelism_levels_sampled": par_values,
            "divergence_latest": series[div_key].get("latest"),
            "series_total": len(series),
        }

        # --- the per-epoch History record carries the signals ---
        h = client.histories().get(job_id)
        assert h.worker_divergence and h.loss_spread, \
            "history record has no statistical-efficiency signals"
        assert len(set(h.parallelism)) >= 2, \
            f"parallelism never moved in history: {h.parallelism}"
        row["history_record"] = {
            "epochs": len(h.train_loss),
            "parallelism": h.parallelism,
            # nanmean: an unmeasured epoch records NaN to keep the lists
            # index-aligned, and must not poison the summary
            "divergence_mean": float(np.nanmean(h.worker_divergence)),
            "loss_spread_mean": float(np.nanmean(h.loss_spread)),
            # null placeholders in the jsonl row, same as the wire form
            "round_skew": [None if v != v else v for v in h.round_skew],
        }

        # --- the operator surface: `kubeml decisions <job-id>` renders ---
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli_main(["--url", cluster.controller_url,
                           "decisions", job_id])
        assert rc == 0 and "REASON" in buf.getvalue(), \
            "kubeml decisions did not render the audit trail"
        row["cli_rows"] = buf.getvalue().count("\n") - 1
        row["status"] = "ok"
    return row


def run_all(config: Optional[Config] = None, quick: bool = True,
            names: Optional[List[str]] = None,
            max_parallelism: Optional[int] = None) -> List[ScenarioResult]:
    from ..api.config import get_config

    cfg = config or get_config()
    cfg.ensure_dirs()
    known = [s.name for s in scenarios()] + ["elastic-multijob"]
    if names:
        unknown = [n for n in names if n not in known]
        if unknown:
            raise ValueError(f"unknown scenario name(s) {unknown}; known: {known}")
    results = []
    # quick (CI) mode caps elastic growth at 4 to bound compile time; full
    # mode runs unbounded by default — the engine background-precompiles the
    # next scale-up level during each epoch (engine/job._precompile_next_level),
    # which removed the synchronous recompile stall that forced round 1's cap
    if max_parallelism is None and quick:
        max_parallelism = 4
    with ExperimentDriver(cfg, max_parallelism=max_parallelism) as driver:
        for sc in scenarios():
            if names and sc.name not in names:
                continue
            results.append(driver.run(sc, quick=quick))
        if not names or "elastic-multijob" in names:
            results.append(driver.run_elastic_multijob(quick=quick))
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="kubeml-tpu benchmark scenarios")
    p.add_argument("--quick", action="store_true", help="CI-sized data and epochs")
    p.add_argument("--only", nargs="*", default=None, help="scenario names to run")
    p.add_argument("--out", default=None, help="write results JSON here")
    p.add_argument("--max-parallelism", type=int, default=None,
                   help="cap elastic growth (default: unbounded in full mode, "
                        "4 in --quick)")
    p.add_argument("--usage-out", default=None,
                   help="sample host/device utilization to this JSONL while "
                        "the scenarios run (benchmarks/sampler.py — the "
                        "reference's experiment-side CPU/mem sidecar)")
    args = p.parse_args(argv)
    try:
        import contextlib

        ctx = contextlib.nullcontext()
        if args.usage_out:
            from .sampler import ResourceSampler

            ctx = ResourceSampler(args.usage_out, tag="scenarios")
        with ctx:
            results = run_all(quick=args.quick, names=args.only,
                              max_parallelism=args.max_parallelism)
    except ValueError as e:
        print(f"error: {e}", file=__import__("sys").stderr)
        return 2
    payload = [r.to_dict() for r in results]
    print(json.dumps(payload, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
    failed = [r.name for r in results if r.status != "ok"]
    return 1 if failed else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
