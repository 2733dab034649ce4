"""Scheduler, PS, function registry, and full in-process cluster tests.

The end-to-end test is the formalization of the reference's manual integration
harness (reference: ml/tests/integration.go boots controller+scheduler+PS as
goroutines in one process) — here it's a pytest fixture over LocalCluster with
every HTTP surface live.
"""

import time

import numpy as np
import pytest

from kubeml_tpu.api.types import JobState, TrainOptions, TrainRequest, TrainTask
from kubeml_tpu.scheduler.policy import ThroughputBasedPolicy, next_power_down, next_power_up
from kubeml_tpu.scheduler.queue import TaskQueue

from conftest import make_blobs, wait_job_done as _wait_done

# A complete user function source: tiny MLP KubeModel (fast to compile).
FN_SOURCE = '''
import flax.linen as nn
import optax
from kubeml_tpu import KubeModel, KubeDataset


class TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(32)(x))
        return nn.Dense(10)(x)


class BlobDataset(KubeDataset):
    def __init__(self):
        super().__init__("blobs")


class TinyModel(KubeModel):
    def __init__(self):
        super().__init__(BlobDataset())

    def build(self):
        return TinyNet()

    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
'''


def _task(job_id="j1", default_parallelism=4, parallelism=0, elapsed=-1.0):
    return TrainTask(
        job_id=job_id,
        parameters=TrainRequest(
            function_name="f", dataset="d",
            options=TrainOptions(default_parallelism=default_parallelism),
        ),
        state=JobState(parallelism=parallelism, elapsed_time=elapsed),
    )


class TestPolicy:
    def test_topology_steps(self):
        assert next_power_up(1, 16) == 2
        assert next_power_up(2, 16) == 4
        assert next_power_up(3, 16) == 4
        assert next_power_up(8, 8) == 8
        assert next_power_down(8) == 4
        assert next_power_down(5) == 4
        assert next_power_down(1) == 1

    def test_first_call_uses_default(self):
        p = ThroughputBasedPolicy(default_parallelism=4, max_parallelism=8)
        par, is_new = p.calculate_parallelism(_task())
        assert (par, is_new) == (4, True)

    def test_speedup_scales_up_slowdown_scales_down(self):
        p = ThroughputBasedPolicy(default_parallelism=4, max_parallelism=16)
        p.calculate_parallelism(_task(elapsed=-1.0))
        # first epoch report: 10s cached as inf -> new task path already consumed;
        # report epoch times now
        par, is_new = p.calculate_parallelism(_task(parallelism=4, elapsed=10.0))
        assert not is_new and par == 8  # 10.0 <= inf * anything -> grow
        # slower epoch beyond 1.2x -> halve
        par, _ = p.calculate_parallelism(_task(parallelism=8, elapsed=13.0))
        assert par == 4
        # in the dead zone (1.05x..1.2x) -> keep
        par, _ = p.calculate_parallelism(_task(parallelism=4, elapsed=14.5))
        assert par == 4

    def test_limit_parallelism_freezes_scale_up(self):
        p = ThroughputBasedPolicy(default_parallelism=2, max_parallelism=8, limit_parallelism=True)
        p.calculate_parallelism(_task(default_parallelism=2))
        par, _ = p.calculate_parallelism(_task(parallelism=2, elapsed=1.0))
        assert par == 2

    def test_finish_evicts_cache(self):
        p = ThroughputBasedPolicy(default_parallelism=4, max_parallelism=8)
        p.calculate_parallelism(_task())
        p.task_finished("j1")
        _, is_new = p.calculate_parallelism(_task())
        assert is_new

    def test_stale_update_after_finish_is_dropped(self):
        # an epoch-end update queued behind finish_job must return the drop
        # sentinel, not reseed the cache / resurrect the job
        p = ThroughputBasedPolicy(default_parallelism=4, max_parallelism=8)
        p.calculate_parallelism(_task())
        p.task_finished("j1")
        assert p.calculate_parallelism(_task(parallelism=4, elapsed=10.0)) is None
        assert "j1" not in p._time_cache
        # a fresh submission reusing the id starts cleanly
        par, is_new = p.calculate_parallelism(_task())
        assert is_new and par == 4


class TestQueue:
    def test_fifo(self):
        q = TaskQueue()
        q.push(_task("a"))
        q.push(_task("b"))
        assert q.pop().job_id == "a"
        assert q.pop().job_id == "b"
        assert q.pop(timeout=0.01) is None

    def test_len(self):
        q = TaskQueue()
        assert len(q) == 0
        q.push(_task())
        assert len(q) == 1


class TestRegistry:
    def test_create_load_subclass(self, tmp_config):
        from kubeml_tpu.functions.registry import FunctionRegistry
        from kubeml_tpu.runtime.model import KubeModel

        reg = FunctionRegistry(config=tmp_config)
        reg.create("tiny", FN_SOURCE)
        model = reg.load("tiny")
        assert isinstance(model, KubeModel)
        assert [f.name for f in reg.list()] == ["tiny"]
        reg.delete("tiny")
        assert reg.list() == []

    def test_main_contract(self, tmp_config):
        from kubeml_tpu.functions.registry import FunctionRegistry

        reg = FunctionRegistry(config=tmp_config)
        reg.create("viamain", FN_SOURCE + "\ndef main():\n    return TinyModel()\n")
        assert reg.load("viamain") is not None

    def test_bad_source_rejected_and_not_stored(self, tmp_config):
        from kubeml_tpu.api.errors import KubeMLError
        from kubeml_tpu.functions.registry import FunctionRegistry

        reg = FunctionRegistry(config=tmp_config)
        with pytest.raises(KubeMLError):
            reg.create("bad", "this is not python (")
        assert not reg.exists("bad")
        with pytest.raises(KubeMLError):
            reg.create("nomodel", "x = 1\n")
        assert not reg.exists("nomodel")

    def test_duplicate_rejected(self, tmp_config):
        from kubeml_tpu.api.errors import KubeMLError
        from kubeml_tpu.functions.registry import FunctionRegistry

        reg = FunctionRegistry(config=tmp_config)
        reg.create("tiny", FN_SOURCE)
        with pytest.raises(KubeMLError):
            reg.create("tiny", FN_SOURCE)


class TestMetrics:
    def test_update_render_clear(self):
        from kubeml_tpu.api.types import MetricUpdate
        from kubeml_tpu.ps.metrics import MetricsRegistry

        m = MetricsRegistry()
        m.task_started("train")
        m.update(MetricUpdate(job_id="abc", train_loss=1.5, accuracy=42.0,
                              validation_loss=2.0, parallelism=4, epoch_duration=3.0,
                              round_seconds=[0.2, 0.4], merge_seconds=0.05))
        text = m.render()
        assert 'kubeml_job_train_loss{jobid="abc"} 1.5' in text
        assert 'kubeml_job_parallelism{jobid="abc"} 4.0' in text
        assert 'kubeml_job_running_total{type="train"} 1' in text
        # the flattened timings became real distributions
        assert "# TYPE kubeml_job_epoch_seconds histogram" in text
        assert 'kubeml_job_epoch_seconds_bucket{jobid="abc",le="5"} 1' in text
        assert 'kubeml_job_round_seconds_count{jobid="abc"} 2' in text
        assert 'kubeml_job_merge_seconds_bucket{jobid="abc",le="0.05"} 1' in text
        m.clear("abc")
        m.task_finished("train")
        text = m.render()
        # gauges clear with the job (reference metrics.go:100-106) ...
        assert 'kubeml_job_train_loss{jobid="abc"}' not in text
        assert 'kubeml_job_running_total{type="train"} 0' in text
        # ... but histograms linger: they are cumulative and the finished
        # job's latency distribution IS the artifact operators scrape
        assert 'kubeml_job_epoch_seconds_count{jobid="abc"} 1' in text

    def test_histogram_job_label_cap(self):
        from kubeml_tpu.api.types import MetricUpdate
        from kubeml_tpu.ps.metrics import MAX_HISTOGRAM_JOBS, MetricsRegistry

        m = MetricsRegistry()
        n = MAX_HISTOGRAM_JOBS + 8
        for i in range(n):
            m.update(MetricUpdate(job_id=f"job{i:03d}", epoch_duration=1.0))
        text = m.render()
        # oldest jobs evicted, newest retained, bounded total
        assert 'kubeml_job_epoch_seconds_count{jobid="job000"}' not in text
        assert f'kubeml_job_epoch_seconds_count{{jobid="job{n-1:03d}"}} 1' in text
        kept = text.count("kubeml_job_epoch_seconds_count{")
        assert kept == MAX_HISTOGRAM_JOBS


@pytest.fixture
def cluster(tmp_config):
    from kubeml_tpu.cluster import LocalCluster

    with LocalCluster(config=tmp_config) as c:
        yield c


class TestClusterEndToEnd:
    def test_full_train_pipeline_over_http(self, cluster):
        from kubeml_tpu.controller.client import KubemlClient

        client = KubemlClient(cluster.controller_url)
        assert client.health()

        x, y = make_blobs(256, shape=(8, 8, 1))
        xt, yt = make_blobs(64, shape=(8, 8, 1), seed=1)
        summary = client.datasets().create("blobs", x, y, xt, yt)
        assert summary.train_set_size == 256
        assert [d.name for d in client.datasets().list()] == ["blobs"]

        client.functions().create("tiny", FN_SOURCE)
        assert [f["name"] for f in client.functions().list()] == ["tiny"]

        req = TrainRequest(
            model_type="tiny", batch_size=16, epochs=2, dataset="blobs", lr=0.05,
            function_name="tiny",
            options=TrainOptions(default_parallelism=2, k=2, static_parallelism=True),
        )
        job_id = client.networks().train(req)
        assert len(job_id) == 8
        _wait_done(client, job_id)

        hist = client.histories().get(job_id)
        assert len(hist.train_loss) == 2
        assert len(hist.accuracy) >= 1
        assert hist.parallelism == [2, 2]

        # unknown dataset/function rejected up front
        from kubeml_tpu.api.errors import KubeMLError

        with pytest.raises(KubeMLError):
            client.networks().train(
                TrainRequest(batch_size=16, epochs=1, dataset="nope", function_name="tiny")
            )
        with pytest.raises(KubeMLError):
            client.networks().train(
                TrainRequest(batch_size=16, epochs=1, dataset="blobs", function_name="nope")
            )

        # /generate over the full HTTP chain: a non-causal model is a clean
        # 400 (the KV-cache decode contract), never a 500
        with pytest.raises(KubeMLError) as ei:
            client.networks().generate(job_id, [[1, 2, 3]], max_new_tokens=2)
        assert ei.value.status_code < 500

        # history CRUD
        assert client.histories().prune() >= 1
        client.datasets().delete("blobs")
        assert client.datasets().list() == []

    def test_elastic_parallelism_updates(self, cluster):
        from kubeml_tpu.controller.client import KubemlClient

        client = KubemlClient(cluster.controller_url)
        x, y = make_blobs(512, shape=(8, 8, 1))
        client.datasets().create("blobs", x, y, x[:64], y[:64])
        client.functions().create("tiny", FN_SOURCE)
        req = TrainRequest(
            batch_size=16, epochs=4, dataset="blobs", lr=0.05, function_name="tiny",
            options=TrainOptions(default_parallelism=2, k=2, static_parallelism=False,
                                 validate_every=0),
        )
        job_id = client.networks().train(req)
        _wait_done(client, job_id)
        hist = client.histories().get(job_id)
        assert len(hist.parallelism) == 4
        # elastic: parallelism must have been re-evaluated and stay topology-legal
        assert all(p in (1, 2, 4, 8) for p in hist.parallelism)

    def test_stop_task(self, cluster):
        from kubeml_tpu.controller.client import KubemlClient

        client = KubemlClient(cluster.controller_url)
        x, y = make_blobs(1024, shape=(8, 8, 1))
        client.datasets().create("blobs", x, y, x[:64], y[:64])
        client.functions().create("tiny", FN_SOURCE)
        req = TrainRequest(
            batch_size=8, epochs=50, dataset="blobs", lr=0.05, function_name="tiny",
            options=TrainOptions(default_parallelism=2, k=1, static_parallelism=True),
        )
        job_id = client.networks().train(req)
        deadline = time.time() + 60
        while time.time() < deadline:
            tasks = client.tasks().list()
            if any(t.job_id == job_id for t in tasks):
                break
            time.sleep(0.1)
        # a task is listed from the moment its slot is reserved; until the
        # job object exists the PS answers a stop with 409 "still starting"
        from kubeml_tpu.api.errors import KubeMLError

        while True:
            try:
                client.tasks().stop(job_id)
                break
            except KubeMLError as e:
                if e.status_code != 409 or time.time() > deadline:
                    raise
                time.sleep(0.05)
        _wait_done(client, job_id)

    def test_prometheus_metrics_endpoint(self, cluster):
        import requests

        text = requests.get(f"{cluster.ps_api.url}/metrics", timeout=5).text
        assert "kubeml_job_running_total" in text

    def test_checkpoint_serving_applies_preprocess(self, cluster):
        """Post-finish inference (served from the final checkpoint) must run
        the model's device-side preprocess exactly like live inference: a
        uint8-dequant model's served predictions have to match predictions
        computed locally from the exported weights WITH preprocess applied."""
        import jax.numpy as jnp

        from kubeml_tpu.controller.client import KubemlClient
        from kubeml_tpu.storage.checkpoint import CheckpointStore

        fn_quant = FN_SOURCE.replace(
            "    def configure_optimizers(self):",
            "    def preprocess(self, x):\n"
            "        import jax.numpy as jnp\n"
            "        return x.astype(jnp.float32) / 127.5 - 1.0\n\n"
            "    def configure_optimizers(self):",
        )
        client = KubemlClient(cluster.controller_url)
        r = np.random.default_rng(0)
        y = r.integers(0, 4, size=256).astype(np.int64)
        x = np.clip(r.normal(size=(256, 8, 8, 1)) * 30 + 128 + 20 * y[:, None, None, None],
                    0, 255).astype(np.uint8)
        client.datasets().create("blobs", x, y, x[:64], y[:64])
        client.functions().create("quant", fn_quant)
        req = TrainRequest(
            batch_size=16, epochs=2, dataset="blobs", lr=0.05, function_name="quant",
            options=TrainOptions(default_parallelism=1, k=2, static_parallelism=True),
        )
        job_id = client.networks().train(req)
        _wait_done(client, job_id)

        probe = x[:8]
        served = np.asarray(client.networks().infer(job_id, probe))

        # local reference: exported weights + preprocess applied by hand
        from kubeml_tpu.api.config import get_config
        from kubeml_tpu.functions.registry import FunctionRegistry

        ck = CheckpointStore(config=get_config()).restore(job_id, tag="final")
        model = FunctionRegistry(config=get_config()).load("quant")
        pre = model.preprocess(jnp.asarray(probe))
        expected = np.asarray(model.infer(ck.variables, pre))
        np.testing.assert_array_equal(served, expected)

    def test_concurrent_jobs_stress(self, cluster):
        """Race-condition stress over the live HTTP surface: 5 jobs submitted
        from concurrent threads against one shared dataset/function, one
        stopped mid-flight — every job must finish, leave a history record,
        clear the PS task index, and clear its Prometheus gauges (the
        reference hand-rolls this safety with mutexes and has no test for it:
        SURVEY §5 race detection: none)."""
        import threading

        import requests

        from kubeml_tpu.controller.client import KubemlClient

        client = KubemlClient(cluster.controller_url)
        x, y = make_blobs(256, shape=(8, 8, 1))
        client.datasets().create("blobs", x, y, x[:64], y[:64])
        client.functions().create("tiny", FN_SOURCE)

        n_jobs = 5
        ids: list = [None] * n_jobs
        errors: list = []

        def submit(i):
            try:
                req = TrainRequest(
                    batch_size=16, epochs=2 + (i % 2), dataset="blobs", lr=0.05,
                    function_name="tiny",
                    options=TrainOptions(default_parallelism=1 + (i % 2), k=2,
                                         static_parallelism=True, validate_every=0),
                )
                ids[i] = client.networks().train(req)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(str(e))

        threads = [threading.Thread(target=submit, args=(i,)) for i in range(n_jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors and all(ids), (errors, ids)
        assert len(set(ids)) == n_jobs  # unique job ids under concurrent mint

        # stop one job as soon as it shows up in the index
        deadline = time.time() + 60
        while time.time() < deadline:
            if any(t.job_id == ids[0] for t in client.tasks().list()):
                break
            time.sleep(0.05)
        client.tasks().stop(ids[0])

        for j in ids:
            _wait_done(client, j, timeout=180)

        # every job left a history record; the index and gauges are clean
        for j in ids:
            hist = client.histories().get(j)
            assert hist.id == j
        assert client.tasks().list() == []
        # every per-job GAUGE clears on finish (reference metrics.go:100-106);
        # per-job HISTOGRAM series deliberately linger — the distribution is
        # the artifact, bounded by MAX_HISTOGRAM_JOBS eviction (metrics.py)
        from kubeml_tpu.ps.metrics import GAUGES

        text = requests.get(f"{cluster.ps_api.url}/metrics", timeout=5).text
        for j in ids:
            for metric in GAUGES:
                assert f'{metric}{{jobid="{j}"}}' not in text, metric
        assert 'kubeml_job_running_total{type="train"} 0' in text


# --- controller client service discovery (VERDICT r5 missing-2) ---

def test_client_service_discovery(monkeypatch):
    """URL resolution chain: explicit arg > KUBEML_CONTROLLER_URL env >
    process config; when nothing resolves, the error names all three."""
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.controller.client import (KubemlClient,
                                              resolve_controller_url)

    assert resolve_controller_url("http://explicit:1") == "http://explicit:1"

    monkeypatch.setenv("KUBEML_CONTROLLER_URL", "http://envhost:9")
    assert resolve_controller_url() == "http://envhost:9"
    assert KubemlClient().url == "http://envhost:9"
    # explicit still wins over the env
    assert resolve_controller_url("http://explicit:1") == "http://explicit:1"

    monkeypatch.delenv("KUBEML_CONTROLLER_URL")
    from kubeml_tpu.api.config import get_config

    assert resolve_controller_url() == get_config().controller_url

    # all three unresolvable: a clear error naming each source
    import kubeml_tpu.api.config as config_mod

    def broken():
        raise RuntimeError("no config here")

    monkeypatch.setattr(config_mod, "get_config", broken)
    with pytest.raises(KubeMLError) as e:
        resolve_controller_url()
    msg = str(e.value)
    assert "url=" in msg
    assert "KUBEML_CONTROLLER_URL" in msg
    assert "api.config" in msg
