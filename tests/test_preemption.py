"""Multi-tenant preemption: priority queue + fair share, checkpoint-and-yield
engines, the PS preempt path with grace escalation, the preemption
controller's overload decisions, the `kubeml jobs` operator view, journal
quarantine, and the heavy end-to-end proofs (SIGKILL mid-yield resume, the
colocation scenario) on the slow tier."""

import threading
import time

import numpy as np
import pytest

from conftest import make_blobs
from kubeml_tpu.api.types import (JobState, JobStateEnum, TrainOptions,
                                  TrainRequest, TrainTask)
from kubeml_tpu.scheduler.queue import TaskQueue, TenantUsage

from test_controlplane import FN_SOURCE


def _task(job_id, priority=0, tenant="", elapsed=-1.0, parallelism=0):
    return TrainTask(
        job_id=job_id,
        parameters=TrainRequest(
            function_name="f", dataset="d",
            options=TrainOptions(priority=priority, tenant=tenant)),
        state=JobState(parallelism=parallelism, elapsed_time=elapsed),
    )


# --- priority queue + fair share ---


class TestPriorityQueue:
    def test_higher_class_pops_first(self):
        q = TaskQueue()
        q.push(_task("low", priority=0))
        q.push(_task("high", priority=10))
        q.push(_task("mid", priority=5))
        assert [q.pop().job_id for _ in range(3)] == ["high", "mid", "low"]

    def test_fifo_within_class(self):
        q = TaskQueue()
        for i in range(4):
            q.push(_task(f"j{i}", priority=3))
        assert [q.pop().job_id for _ in range(4)] == ["j0", "j1", "j2", "j3"]

    def test_fair_share_tie_break_across_tenants(self):
        usage = TenantUsage()
        usage.charge("heavy", 1000.0)
        usage.charge("light", 1.0)
        q = TaskQueue(usage=usage)
        q.push(_task("h1", priority=0, tenant="heavy"))
        q.push(_task("l1", priority=0, tenant="light"))
        q.push(_task("h2", priority=0, tenant="heavy"))
        # light tenant first despite arriving second; FIFO within heavy
        assert [q.pop().job_id for _ in range(3)] == ["l1", "h1", "h2"]

    def test_priority_beats_fair_share(self):
        usage = TenantUsage()
        usage.charge("hog", 1e9)
        q = TaskQueue(usage=usage)
        q.push(_task("cheap", priority=0, tenant="frugal"))
        q.push(_task("urgent", priority=9, tenant="hog"))
        assert q.pop().job_id == "urgent"

    def test_depths_and_snapshot(self):
        q = TaskQueue()
        q.push(_task("a", priority=0))
        q.push(_task("b", priority=5, tenant="t"))
        q.push(_task("c", priority=5))
        assert q.depths() == {0: 1, 5: 2}
        snap = q.snapshot()
        assert [s["job_id"] for s in snap] == ["b", "c", "a"]
        assert snap[0]["priority"] == 5 and snap[0]["tenant"] == "t"
        assert len(q) == 3 and q.job_ids() == {"a", "b", "c"}

    def test_single_class_single_tenant_is_plain_fifo(self):
        q = TaskQueue()
        for i in range(5):
            q.push(_task(f"j{i}"))
        assert [q.pop().job_id for _ in range(5)] == [f"j{i}" for i in range(5)]


class TestOptionsValidation:
    def test_priority_bounds(self):
        with pytest.raises(ValueError):
            TrainOptions(priority=-1)
        with pytest.raises(ValueError):
            TrainOptions(priority=1001)
        with pytest.raises(ValueError):
            TrainOptions(priority=True)  # bool must not coerce
        assert TrainOptions(priority=1000).priority == 1000

    def test_tenant_charset(self):
        with pytest.raises(ValueError):
            TrainOptions(tenant="bad tenant!")
        with pytest.raises(ValueError):
            TrainOptions(tenant="x" * 65)
        assert TrainOptions(tenant="team-a.prod").tenant == "team-a.prod"


class _SchedPSStub:
    """Minimal PS surface Scheduler.__init__/submit_train touch."""

    def __init__(self):
        from kubeml_tpu.ps.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()

    def list_tasks(self):
        return []


def test_scheduler_charges_tenant_usage(tmp_config):
    from kubeml_tpu.scheduler.scheduler import Scheduler

    sched = Scheduler(_SchedPSStub(), config=tmp_config, max_parallelism=4)
    # an epoch-end report charges parallelism x elapsed to the tenant
    sched.update_job(_task("j1", tenant="acme", elapsed=10.0, parallelism=4))
    assert sched.usage.get("acme") == pytest.approx(40.0)
    # fresh submissions (elapsed -1) charge nothing
    sched.update_job(_task("j2", tenant="acme"))
    assert sched.usage.get("acme") == pytest.approx(40.0)
    # and the queue gauges are wired into the PS registry at render time
    text = sched.ps.metrics.render()
    assert "kubeml_scheduler_queue_depth" in text


# --- journal quarantine (satellite) ---


def test_journal_quarantines_corrupt_entries(tmp_config, caplog):
    from kubeml_tpu.ps.journal import JobJournal

    j = JobJournal(config=tmp_config)
    j.record("good1", TrainRequest(function_name="f", dataset="d"))
    bad = j.dir / "bad1.json"
    bad.write_text("{not json at all")
    with caplog.at_level("WARNING"):
        entries = j.pending()
    assert [e["job_id"] for e in entries] == ["good1"]
    assert not bad.exists()
    quarantined = j.dir / "bad1.json.corrupt"
    assert quarantined.exists()
    assert quarantined.read_text() == "{not json at all"
    assert any("quarantined" in r.message for r in caplog.records)
    # the next boot pays no re-parse and logs no second warning
    caplog.clear()
    with caplog.at_level("WARNING"):
        assert [e["job_id"] for e in j.pending()] == ["good1"]
    assert not any("corrupt" in r.message for r in caplog.records)


# --- preemption controller decisions (unit, fake PS/scheduler) ---


class _FakePS:
    def __init__(self):
        self.telemetry = {}
        self.jobs = []
        self.preempts = []

    def serving_telemetry(self):
        return self.telemetry

    def jobs_snapshot(self, include_journal=True):
        return self.jobs

    def preempt_task(self, job_id, reason="x"):
        self.preempts.append((job_id, reason))


class _FakeScheduler:
    def __init__(self):
        self.usage = TenantUsage()
        self.submitted = []

    def submit_train(self, req):
        self.submitted.append(req)
        return req.job_id


def _ctrl_config(tmp_path, **over):
    from kubeml_tpu.api.config import Config

    cfg = Config(data_root=tmp_path / "kubeml")
    cfg.preempt_queue_depth = 4
    cfg.preempt_overload_rate = 1.0
    cfg.preempt_p99 = 0.0
    cfg.preempt_sustain = 2
    cfg.preempt_resume_sustain = 2
    cfg.preempt_cooldown = 0.0
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def test_controller_preempts_lowest_priority_after_sustain(tmp_path):
    from kubeml_tpu.scheduler.preemption import PreemptionController

    ps, sched = _FakePS(), _FakeScheduler()
    sched.usage.charge("hog", 500.0)
    ctrl = PreemptionController(sched, ps, config=_ctrl_config(tmp_path))
    ps.jobs = [
        {"job_id": "crit", "status": "running", "priority": 8, "tenant": ""},
        {"job_id": "be-a", "status": "running", "priority": 0, "tenant": "x"},
        {"job_id": "be-b", "status": "running", "priority": 0, "tenant": "hog"},
    ]
    ps.telemetry = {"m": {"queue_depth": 10.0}}
    ctrl.tick()
    assert ps.preempts == []  # one sample is not a sustained overload
    ctrl.tick()
    # lowest class; within it the heaviest tenant yields first
    assert ps.preempts == [("be-b", "serving-overload")]


def test_controller_p99_and_rate_signals(tmp_path):
    from kubeml_tpu.scheduler.preemption import PreemptionController

    ctrl = PreemptionController(
        _FakeScheduler(), _FakePS(),
        config=_ctrl_config(tmp_path, preempt_p99=0.5, preempt_queue_depth=0,
                            preempt_overload_rate=0.0))
    assert ctrl.overloaded({"queue_depth": 0, "p99": 0.6, "overload_rate": 0})
    assert not ctrl.overloaded({"queue_depth": 0, "p99": 0.4,
                                "overload_rate": 0})
    ctrl2 = PreemptionController(
        _FakeScheduler(), _FakePS(), config=_ctrl_config(tmp_path))
    # the windowed overload_per_second from serving stats feeds the rate
    ctrl2.ps.telemetry = {"m": {"queue_depth": 0.0,
                                "overload_per_second": 3.0}}
    assert ctrl2.overloaded(ctrl2.signals())


def test_controller_parks_and_requeues_when_calm(tmp_path):
    from kubeml_tpu.scheduler.preemption import PreemptionController

    ps, sched = _FakePS(), _FakeScheduler()
    ctrl = PreemptionController(sched, ps, config=_ctrl_config(tmp_path))
    req = TrainRequest(function_name="f", dataset="d")
    ctrl.park("jobA", req)
    assert ctrl.parked_ids() == ["jobA"]
    ps.telemetry = {"m": {"queue_depth": 10.0}}
    ctrl.tick()  # overloaded: nothing requeues
    assert sched.submitted == []
    ps.telemetry = {"m": {"queue_depth": 0.0}}
    ctrl.tick()
    assert sched.submitted == []  # calm once: not sustained yet
    ctrl.tick()
    assert [r.job_id for r in sched.submitted] == ["jobA"]
    assert sched.submitted[0].options.resume is True
    assert ctrl.parked_ids() == []


def test_controller_requeue_deferred_on_conflict(tmp_path):
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.scheduler.preemption import PreemptionController

    ps, sched = _FakePS(), _FakeScheduler()

    def conflict(req):
        raise KubeMLError("still active", 409)

    sched.submit_train = conflict
    ctrl = PreemptionController(sched, ps, config=_ctrl_config(tmp_path))
    ctrl.park("jobA", TrainRequest(function_name="f", dataset="d"))
    assert ctrl.requeue_parked() == 0
    assert ctrl.parked_ids() == ["jobA"]  # kept for the next calm tick


# --- checkpoint-and-yield: the TrainJob engine directly ---


def _blob_model():
    import flax.linen as nn
    import optax

    from kubeml_tpu.data.dataset import KubeDataset
    from kubeml_tpu.runtime.model import KubeModel

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

    class Ds(KubeDataset):
        def __init__(self):
            super().__init__("pblobs")

    class Model(KubeModel):
        def __init__(self):
            super().__init__(Ds())

        def build(self):
            return Tiny()

        def configure_optimizers(self):
            return optax.sgd(self.lr, momentum=0.9)

    return Model()


@pytest.fixture
def blob_store(tmp_config):
    from kubeml_tpu.storage.store import ShardStore

    store = ShardStore(config=tmp_config)
    x, y = make_blobs(256, shape=(8, 8, 1))
    store.create("pblobs", x, y, x[:64], y[:64])
    return store


def test_trainjob_checkpoint_and_yield_then_resume(blob_store, tmp_config):
    from kubeml_tpu.engine.job import TrainJob
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore
    from kubeml_tpu.storage.history import HistoryStore

    ckpts = CheckpointStore(config=tmp_config)
    hist_store = HistoryStore(config=tmp_config)

    def make_job(resume):
        req = TrainRequest(
            function_name="pb", dataset="pblobs", epochs=12, batch_size=16,
            lr=0.05,
            options=TrainOptions(default_parallelism=2, static_parallelism=True,
                                 k=2, precision="f32", validate_every=0,
                                 resume=resume))
        return TrainJob("py01", req, _blob_model(), store=blob_store,
                        history_store=hist_store, checkpoint_store=ckpts)

    job = make_job(resume=False)
    t = threading.Thread(target=job.train, daemon=True)
    t.start()
    deadline = time.time() + 120
    while time.time() < deadline and len(job.history.train_loss) < 2:
        time.sleep(0.02)
    assert len(job.history.train_loss) >= 2, "job made no progress"
    job.preempt()
    t.join(60)
    assert not t.is_alive()
    assert job.preempted
    done = len(job.history.train_loss)
    assert 2 <= done < 12, f"preempt should land mid-run, got {done} epochs"
    # the yield checkpoint is the newest epoch tag; NO final export exists
    tags = ckpts.tags("py01")
    assert FINAL_TAG not in tags
    assert ckpts.latest_epoch("py01") == done - 1
    # history persisted without an error marker
    h = hist_store.get("py01")
    assert not (isinstance(h.task, dict) and h.task.get("error"))

    # resume completes the request and exports the final model
    job2 = make_job(resume=True)
    hist = job2.train()
    assert not job2.preempted
    assert len(hist.train_loss) == 12
    assert all(np.isfinite(l) for l in hist.train_loss)
    assert FINAL_TAG in ckpts.tags("py01")


def test_preempt_before_first_epoch_is_clean(blob_store, tmp_config):
    """Preempted before any epoch completed: no checkpoint to write, status
    still preempted, nothing corrupted — resume simply restarts."""
    from kubeml_tpu.engine.job import TrainJob
    from kubeml_tpu.storage.checkpoint import CheckpointStore
    from kubeml_tpu.storage.history import HistoryStore

    req = TrainRequest(
        function_name="pb", dataset="pblobs", epochs=3, batch_size=16,
        options=TrainOptions(default_parallelism=2, static_parallelism=True,
                             k=2, precision="f32", validate_every=0))
    job = TrainJob("py02", req, _blob_model(), store=blob_store,
                   history_store=HistoryStore(config=tmp_config),
                   checkpoint_store=CheckpointStore(config=tmp_config))
    job.preempt()  # before train() even starts
    hist = job.train()
    assert job.preempted
    assert len(hist.train_loss) <= 1
    assert "final" not in CheckpointStore(config=tmp_config).tags("py02")


def test_spmd_job_checkpoint_and_yield(tmp_config):
    """The SPMD engine honors checkpoint-and-yield too: preempt mid-run
    writes an epoch checkpoint (no final export) and reports preempted."""
    from kubeml_tpu.engine.spmd_job import SPMDJob
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.storage import CheckpointStore, HistoryStore, ShardStore
    from kubeml_tpu.storage.checkpoint import FINAL_TAG

    from test_spmd_job import LM_FN, token_data

    store = ShardStore(config=tmp_config)
    xtr, xte = token_data(128, seed=1), token_data(32, seed=2)
    store.create("tokens", xtr, np.zeros(len(xtr), np.int64),
                 xte, np.zeros(len(xte), np.int64))
    reg = FunctionRegistry(config=tmp_config)
    reg.create("lmfn", LM_FN)
    model = reg.load("lmfn")
    model._set_params(lr=1e-3, batch_size=16, epoch=0, k=1, task="train")
    req = TrainRequest(
        batch_size=16, epochs=8, dataset="tokens", lr=1e-3,
        function_name="lmfn",
        options=TrainOptions(engine="spmd", precision="f32",
                             validate_every=0, mesh_shape={"dp": 2}))
    ckpts = CheckpointStore(config=tmp_config)
    job = SPMDJob("spmdp1", req, model, store=store,
                  history_store=HistoryStore(config=tmp_config),
                  checkpoint_store=ckpts)
    t = threading.Thread(target=job.train, daemon=True)
    t.start()
    deadline = time.time() + 180
    while time.time() < deadline and len(job.history.train_loss) < 1:
        time.sleep(0.02)
    assert job.history.train_loss, "SPMD job made no progress"
    job.preempt()
    t.join(120)
    assert not t.is_alive()
    assert job.preempted
    done = len(job.history.train_loss)
    assert 1 <= done < 8
    tags = ckpts.tags("spmdp1")
    assert FINAL_TAG not in tags
    assert ckpts.latest_epoch("spmdp1") == done - 1


# --- PS grace escalation (a job that refuses to yield) ---


class _StubbornJob:
    """Ignores every cooperative signal — the hard-kill escalation target."""

    def preempt(self):
        pass

    def stop(self):
        pass


class _SchedStub:
    def __init__(self):
        self.finished = []
        self.preempted = []
        self.usage = TenantUsage()

    def finish_job(self, job_id):
        self.finished.append(job_id)

    def job_preempted(self, task):
        self.preempted.append(task)


def test_grace_escalation_tears_down_a_stubborn_job(tmp_config):
    from kubeml_tpu.ps.parameter_server import ParameterServer, _JobRecord

    ps = ParameterServer(config=tmp_config)
    sched = _SchedStub()
    ps.bind_scheduler(sched)
    task = TrainTask(job_id="stub1",
                     parameters=TrainRequest(function_name="f", dataset="d"),
                     status=JobStateEnum.RUNNING)
    record = _JobRecord(task=task, job=_StubbornJob(), thread=None)
    ps._jobs["stub1"] = record
    ps.metrics.task_started("train")
    ps.preempt_task("stub1", reason="test", grace=0.3)
    deadline = time.time() + 5
    while time.time() < deadline and "stub1" in ps._jobs:
        time.sleep(0.05)
    assert "stub1" not in ps._jobs, "grace watchdog never tore the job down"
    assert task.status == JobStateEnum.PREEMPTED
    assert record.keep_journal is True
    # the requeue hand-off fired and both counters landed
    assert sched.finished == ["stub1"]
    assert [t.job_id for t in sched.preempted] == ["stub1"]
    assert ps.metrics._preemptions.get("test") == 1
    assert ps.metrics._preemptions.get("hard-kill") == 1
    assert ps.metrics._yield_hist.count == 1
    text = ps.metrics.render()
    assert 'kubeml_preemptions_total{reason="test"} 1' in text
    assert "kubeml_preempt_yield_seconds_bucket" in text


def test_preempt_unknown_job_404(tmp_config):
    from kubeml_tpu.api.errors import JobNotFoundError
    from kubeml_tpu.ps.parameter_server import ParameterServer

    ps = ParameterServer(config=tmp_config)
    with pytest.raises(JobNotFoundError):
        ps.preempt_task("nope")


def test_failed_preempt_delivery_rolls_back_yield_state(tmp_config):
    """A preempt whose signal never reached the job (runner unreachable,
    job still starting) must not leave the record marked mid-yield: the
    retry is again 'first' (watchdog + metric), and the victim picker does
    not skip the job as already-yielding forever."""
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.ps.parameter_server import ParameterServer, _JobRecord

    ps = ParameterServer(config=tmp_config)
    task = TrainTask(job_id="boot1",
                     parameters=TrainRequest(function_name="f", dataset="d"),
                     status=JobStateEnum.RUNNING)
    record = _JobRecord(task=task, job=None, thread=None)  # still starting
    ps._jobs["boot1"] = record
    with pytest.raises(KubeMLError) as ei:
        ps.preempt_task("boot1", reason="x")
    assert ei.value.status_code == 409
    assert record.preempt_t0 is None  # rolled back: a retry is 'first' again
    assert record.keep_journal is True  # resumability deliberately sticks
    assert not [j for j in ps.jobs_snapshot(include_journal=False)
                if j["preempting"]]
    assert ps.metrics._preemptions == {}  # no decision was delivered


def test_preempt_reason_cardinality_cap(tmp_config):
    """Folding overflow reasons into 'other' must not itself mint a series
    past MAX_PREEMPT_REASONS."""
    from kubeml_tpu.ps.metrics import MAX_PREEMPT_REASONS, MetricsRegistry

    m = MetricsRegistry()
    for i in range(MAX_PREEMPT_REASONS + 5):
        m.preemption(f"r{i}")
    assert len(m._preemptions) <= MAX_PREEMPT_REASONS
    assert m._preemptions["other"] == 6  # the overflow went somewhere visible


def test_parse_grace_rejects_garbage():
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.api.types import parse_grace_seconds

    assert parse_grace_seconds(None) is None
    assert parse_grace_seconds(0) == 0.0
    assert parse_grace_seconds(2.5) == 2.5
    for bad in ("fast", [1], True, -1, float("nan")):
        with pytest.raises(KubeMLError) as ei:
            parse_grace_seconds(bad)
        assert ei.value.status_code == 400


# --- the jobs operator view ---


def test_jobs_view_merges_queued_running_preempted(tmp_config):
    from kubeml_tpu.controller.controller import Controller
    from kubeml_tpu.ps.parameter_server import ParameterServer
    from kubeml_tpu.scheduler.scheduler import Scheduler
    from kubeml_tpu.storage.checkpoint import CheckpointStore

    ps = ParameterServer(config=tmp_config)
    sched = Scheduler(ps, config=tmp_config, max_parallelism=4)  # NOT started
    ps.bind_scheduler(sched)
    sched.usage.charge("hog", 100.0)

    def submit(jid, priority, tenant):
        sched.submit_train(TrainRequest(
            job_id=jid, function_name="f", dataset="d",
            options=TrainOptions(priority=priority, tenant=tenant)))

    submit("q-low-hog", 0, "hog")
    submit("q-high", 7, "")
    submit("q-low-new", 0, "newbie")
    # a journaled-but-not-live job with checkpoints = preempted awaiting requeue
    pre_req = TrainRequest(function_name="g", dataset="d",
                           options=TrainOptions(priority=2, tenant="hog"))
    ps._journal.record("parked1", pre_req)
    CheckpointStore(config=tmp_config).save(
        "parked1", {"w": np.zeros(2, np.float32)}, epoch=3)

    controller = Controller(sched, ps, config=tmp_config)
    jobs = controller._jobs(None)
    by_id = {j["job_id"]: j for j in jobs}
    # queued first, in pop order: priority desc, fair share within class
    assert [j["job_id"] for j in jobs[:3]] == ["q-high", "q-low-new",
                                               "q-low-hog"]
    assert by_id["q-high"]["status"] == "queued"
    assert by_id["parked1"]["status"] == "preempted"
    assert by_id["parked1"]["resume_epoch"] == 4
    assert by_id["parked1"]["tenant"] == "hog"
    assert by_id["parked1"]["priority"] == 2


# --- end-to-end: threaded preempt -> auto-requeue -> completion ---


def _wait_job_done(cluster, job_id, epochs, timeout=300):
    from kubeml_tpu.api.errors import JobNotFoundError

    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            hist = cluster.history_store.get(job_id)
        except JobNotFoundError:
            hist = None
        in_index = any(t.job_id == job_id for t in cluster.ps.list_tasks())
        queued = any(j["job_id"] == job_id
                     for j in cluster.scheduler.jobs_snapshot())
        if (hist is not None and len(hist.train_loss) >= epochs
                and not in_index and not queued):
            return hist
        time.sleep(0.1)
    raise TimeoutError(f"job {job_id} did not complete")


@pytest.mark.preempt
def test_threaded_preempt_requeues_and_completes(tmp_config, capsys):
    """Operator preempt on a threaded job: checkpoint-and-yield, status
    `preempted`, automatic requeue with resume=True (no controller), full
    completion, metrics on the PS /metrics, journal cleared — plus the
    `kubeml jobs` CLI against the live cluster."""
    from kubeml_tpu import cli
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.controller.client import KubemlClient
    from kubeml_tpu.ps.journal import JobJournal
    from kubeml_tpu.utils import traced_http

    epochs = 10
    with LocalCluster(config=tmp_config) as cluster:
        client = KubemlClient(cluster.controller_url)
        x, y = make_blobs(256, shape=(8, 8, 1))
        client.datasets().create("blobs", x, y, x[:64], y[:64])
        client.functions().create("ptiny", FN_SOURCE)
        req = TrainRequest(
            function_name="ptiny", dataset="blobs", epochs=epochs,
            batch_size=16, lr=0.05,
            options=TrainOptions(default_parallelism=2, static_parallelism=True,
                                 k=2, validate_every=0,
                                 priority=1, tenant="research"))
        job_id = client.networks().train(req)
        # let it actually train a bit, then preempt through the controller API
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                cluster.ps.metrics.get("kubeml_job_train_loss", job_id)
                break  # at least one epoch's metrics pushed
            except KeyError:
                time.sleep(0.05)
        client.tasks().preempt(job_id, reason="operator-test")
        hist = _wait_job_done(cluster, job_id, epochs)
        assert len(hist.train_loss) == epochs
        assert all(np.isfinite(l) for l in hist.train_loss)
        assert not (isinstance(hist.task, dict) and hist.task.get("error"))
        # metrics on the live /metrics scrape
        text = traced_http.get(f"{cluster.ps_api.url}/metrics",
                               timeout=10).text
        assert 'kubeml_preemptions_total{reason="operator-test"} 1' in text
        assert "kubeml_preempt_yield_seconds_bucket" in text
        assert "kubeml_scheduler_queue_depth" in text
        # journal cleared with the successful completion
        assert JobJournal(config=tmp_config).pending() == []
        # the CLI jobs view runs against the live controller
        assert cli.main(["--url", cluster.controller_url, "jobs"]) == 0
        out = capsys.readouterr().out
        assert "no jobs" in out  # everything completed
        assert cli.main(["--url", cluster.controller_url, "jobs",
                         "--json"]) == 0


# --- chaos proof: SIGKILL mid-yield, resume uncorrupted ---


@pytest.mark.preempt
@pytest.mark.chaos
def test_sigkill_mid_yield_resumes_uncorrupted(tmp_config):
    """The acceptance scenario: a standalone job is preempted and its runner
    SIGKILLed mid-yield/mid-checkpoint. Because checkpoint publish is atomic
    and the journal entry was kept, the PS marks it `preempted` (not failed),
    requeues it with resume=True, and the resumed run restores an
    UNCORRUPTED checkpoint and completes with finite losses."""
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.ps.journal import JobJournal

    tmp_config.standalone_jobs = True
    epochs = 30
    with LocalCluster(config=tmp_config) as cluster:
        x, y = make_blobs(256, shape=(8, 8, 1))
        cluster.store.create("blobs", x, y, x[:64], y[:64])
        cluster.registry.create("ktiny", FN_SOURCE)
        req = TrainRequest(
            function_name="ktiny", dataset="blobs", epochs=epochs,
            batch_size=16, lr=0.05,
            options=TrainOptions(default_parallelism=2, static_parallelism=True,
                                 k=2, validate_every=0, checkpoint_every=1))
        job_id = cluster.scheduler.submit_train(req)
        # wait for the first epoch checkpoint so resume has a base
        ckpt_dir = tmp_config.checkpoints_dir / job_id
        deadline = time.time() + 240
        while time.time() < deadline:
            if ckpt_dir.exists() and any(ckpt_dir.iterdir()):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("no checkpoint appeared before the kill")
        with cluster.ps._lock:
            record = cluster.ps._jobs.get(job_id)
        assert record is not None and record.proc is not None
        proc = record.proc
        cluster.ps.preempt_task(job_id, reason="chaos")
        # the kill races the yield: depending on timing it lands mid-round,
        # mid-yield-checkpoint, or just after — all must resume cleanly
        time.sleep(0.05)
        try:
            proc.kill()  # SIGKILL
        except Exception:
            pass
        hist = _wait_job_done(cluster, job_id, epochs, timeout=420)
        assert len(hist.train_loss) == epochs
        assert all(np.isfinite(l) for l in hist.train_loss)
        assert not (isinstance(hist.task, dict) and hist.task.get("error"))
        # the resumed job finished cleanly: journal cleared, counter visible
        assert JobJournal(config=tmp_config).pending() == []
        text = cluster.ps.metrics.render()
        assert 'kubeml_preemptions_total{reason="chaos"}' in text


# --- the colocation flagship (serving burst preempts training) ---


@pytest.mark.preempt
def test_colocation_burst_preempts_and_training_resumes(tmp_config,
                                                        monkeypatch):
    """scenario_support.run_colocation under burst-sized thresholds: the
    preemption controller reclaims the training job, serving keeps being
    served, and the resumed run reaches final-loss parity with the
    uninterrupted baseline."""
    monkeypatch.setenv("KUBEML_PREEMPT_MONITOR", "1")
    monkeypatch.setenv("KUBEML_PREEMPT_INTERVAL", "0.2")
    monkeypatch.setenv("KUBEML_PREEMPT_QUEUE_DEPTH", "3")
    monkeypatch.setenv("KUBEML_PREEMPT_OVERLOAD_RATE", "1.0")
    monkeypatch.setenv("KUBEML_PREEMPT_SUSTAIN", "2")
    monkeypatch.setenv("KUBEML_PREEMPT_RESUME_SUSTAIN", "5")
    monkeypatch.setenv("KUBEML_PREEMPT_COOLDOWN", "10")
    monkeypatch.setenv("KUBEML_SERVING_SLOTS", "2")
    monkeypatch.setenv("KUBEML_SERVING_QUEUE_LIMIT", "6")
    from kubeml_tpu.api.config import Config, set_config
    from scenario_support import run_colocation

    cfg = Config(
        data_root=tmp_config.data_root,
        controller_port=tmp_config.controller_port,
        scheduler_port=tmp_config.scheduler_port,
        ps_port=tmp_config.ps_port,
        storage_port=tmp_config.storage_port,
    )
    assert cfg.preempt_monitor
    set_config(cfg)
    row = run_colocation(config=cfg, epochs=16)
    assert row["metrics"]["preemptions"] >= 1
    assert row["metrics"]["preemptions_total_visible"]
    assert row["metrics"]["yield_histogram_visible"]
    assert row["metrics"]["queue_gauge_visible"]
    assert row["resumed"]["epochs"] == 16
    assert row["resumed"]["loss_parity"], row["resumed"]
    assert row["serving"]["requests_after_reclaim"] > 0
