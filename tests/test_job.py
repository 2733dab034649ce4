"""End-to-end TrainJob tests — the minimum slice: LeNet on a synthetic MNIST-shaped
dataset, one job from storage through K-AVG rounds to validation and history."""

import numpy as np
import pytest

from kubeml_tpu.api.types import History, TrainOptions, TrainRequest
from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.engine.job import TrainJob
from kubeml_tpu.models.lenet import LeNet
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.storage import HistoryStore, ShardStore


def synthetic_mnist(n, seed=0):
    """Learnable 28x28x1 task: the class is the brightest of 10 row bands."""
    r = np.random.default_rng(seed)
    x = r.normal(0, 1.0, size=(n, 28, 28, 1)).astype(np.float32)
    y = r.integers(0, 10, size=(n,))
    for i in range(n):
        band = int(y[i])
        x[i, band * 2 : band * 2 + 3, :, :] += 0.9
    return x, y.astype(np.int64)


class MnistDataset(KubeDataset):
    def __init__(self):
        super().__init__("mnist")

    def transform(self, x, y):
        return x.astype(np.float32), y


class KubeLeNet(KubeModel):
    def __init__(self):
        super().__init__(MnistDataset())

    def build(self):
        return LeNet(num_classes=10)

    def configure_optimizers(self):
        import optax

        return optax.sgd(self.lr, momentum=0.9)


@pytest.fixture
def mnist_store(tmp_config):
    store = ShardStore(config=tmp_config)
    xtr, ytr = synthetic_mnist(640, seed=1)
    xte, yte = synthetic_mnist(128, seed=2)
    store.create("mnist", xtr, ytr, xte, yte)
    return store


def _request(**kw):
    opts = kw.pop("options", {})
    return TrainRequest(
        model_type="lenet",
        batch_size=kw.pop("batch_size", 32),
        epochs=kw.pop("epochs", 2),
        dataset="mnist",
        lr=kw.pop("lr", 0.05),
        function_name="lenet",
        options=TrainOptions(precision="f32", **opts),
    )


def test_end_to_end_single_worker(mnist_store, tmp_config):
    req = _request(options={"default_parallelism": 1, "static_parallelism": True, "k": 4})
    job = TrainJob("job00001", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    hist = job.train()
    assert len(hist.train_loss) == 2
    assert len(hist.accuracy) == 2
    # learnable task: loss must drop and accuracy beat random (10%)
    assert hist.train_loss[-1] < hist.train_loss[0]
    assert hist.accuracy[-1] > 20.0
    # history persisted
    assert HistoryStore(config=tmp_config).get("job00001").accuracy == hist.accuracy
    assert job.final_variables is not None


def test_end_to_end_four_workers(mnist_store, tmp_config):
    req = _request(options={"default_parallelism": 4, "static_parallelism": True, "k": 2})
    job = TrainJob("job00002", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    hist = job.train()
    assert hist.parallelism == [4, 4]
    assert hist.train_loss[-1] < hist.train_loss[0]


def test_elastic_parallelism_callback(mnist_store, tmp_config):
    calls = []

    def policy(state):
        calls.append((state.parallelism, state.elapsed_time))
        return 4 if state.parallelism == 2 else state.parallelism

    req = _request(epochs=3, options={"default_parallelism": 2, "k": 2})
    job = TrainJob("job00003", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config), on_epoch_end=policy)
    hist = job.train()
    assert len(calls) == 3
    assert all(t > 0 for _, t in calls)
    assert hist.parallelism == [2, 4, 4]  # resize applied from epoch 2 on


def test_metrics_callback_and_goal_accuracy(mnist_store, tmp_config):
    updates = []
    req = _request(epochs=20, options={
        "default_parallelism": 2, "static_parallelism": True, "k": 4,
        "goal_accuracy": 30.0,
    })
    job = TrainJob("job00004", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config),
                   on_metrics=updates.append)
    hist = job.train()
    # goal accuracy (30%) on a learnable task must trigger early stop
    assert len(hist.train_loss) < 20
    assert hist.accuracy[-1] >= 30.0
    assert updates and updates[-1].job_id == "job00004"
    assert updates[-1].parallelism == 2


def test_sparse_averaging_k_minus_one(mnist_store, tmp_config):
    req = _request(options={"default_parallelism": 2, "static_parallelism": True, "k": -1})
    job = TrainJob("job00005", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    hist = job.train()
    assert len(hist.train_loss) == 2


def test_stop_event(mnist_store, tmp_config):
    req = _request(epochs=50, options={"default_parallelism": 1, "static_parallelism": True})
    job = TrainJob("job00006", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    job.stop()  # stop before starting: loop must exit immediately
    hist = job.train()
    assert len(hist.train_loss) == 0


def test_infer_after_training(mnist_store, tmp_config):
    req = _request(epochs=1, options={"default_parallelism": 1, "static_parallelism": True})
    job = TrainJob("job00007", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    job.train()
    x, _ = synthetic_mnist(8, seed=9)
    preds = job.infer(x)
    assert preds.shape == (8,)
    assert preds.dtype.kind in "iu"


def test_validate_every_zero_skips_validation(mnist_store, tmp_config):
    req = _request(epochs=1, options={
        "default_parallelism": 1, "static_parallelism": True, "validate_every": 0,
    })
    job = TrainJob("job00008", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    hist = job.train()
    assert hist.accuracy == []
    assert hist.validation_loss == []


def test_non_divisor_batch_size_trains(mnist_store, tmp_config):
    """Regression: batch sizes that don't divide doc-period samples must work."""
    req = _request(batch_size=48, epochs=1,
                   options={"default_parallelism": 2, "static_parallelism": True, "k": 1})
    job = TrainJob("job00009", req, KubeLeNet(), store=mnist_store,
                   history_store=HistoryStore(config=tmp_config))
    hist = job.train()
    assert len(hist.train_loss) == 1


def test_transient_accelerator_error_retried(mnist_store, tmp_config):
    """A round that fails with a transient RPC-style fault (e.g. a
    preempted backend dropping the connection) is retried and the job
    completes; a non-transient error still fails the job immediately."""
    from kubeml_tpu.engine.failures import is_transient_accelerator_error

    assert is_transient_accelerator_error(
        RuntimeError("UNAVAILABLE: Connection reset by peer"))
    assert not is_transient_accelerator_error(ValueError("bad shapes"))
    # bare INTERNAL is how genuine XLA program/compiler bugs present — NOT
    # transient unless corroborated by an RPC/transport-layer marker
    assert not is_transient_accelerator_error(
        RuntimeError("INTERNAL: Mosaic failed to lower module"))
    assert is_transient_accelerator_error(
        RuntimeError("INTERNAL: RPC stream terminated unexpectedly"))
    assert is_transient_accelerator_error(
        RuntimeError("INTERNAL: transport closed: CONNECTION aborted"))

    job = TrainJob(
        "retryjob", _request(epochs=1, options=dict(default_parallelism=1, k=2,
                                                    static_parallelism=True)),
        KubeLeNet(), store=mnist_store, history_store=HistoryStore(config=tmp_config),
    )
    real = job.trainer.sync_round
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("UNAVAILABLE: backend preempted")
        return real(*a, **kw)

    job.trainer.sync_round = flaky
    hist = job.train()
    assert len(hist.train_loss) == 1
    assert calls["n"] >= 3  # two transient failures were retried

    job2 = TrainJob(
        "failjob", _request(epochs=1, options=dict(default_parallelism=1, k=2,
                                                   static_parallelism=True)),
        KubeLeNet(), store=mnist_store, history_store=HistoryStore(config=tmp_config),
    )

    def broken(*a, **kw):
        raise RuntimeError("some real bug")

    job2.trainer.sync_round = broken
    from kubeml_tpu.api.errors import KubeMLError

    with pytest.raises(KubeMLError):
        job2.train()
