"""Elastic-training decision observability (PR 13).

Three layers of coverage:

* :class:`ThroughputBasedPolicy` boundary behavior — EXACTLY at the
  1.05x/1.2x thresholds, the pow2 cap floor on non-pow2 caps,
  reseed-after-preempt id reuse, stale-update drops — edges the policy
  previously had no dedicated tests for;
* the :mod:`kubeml_tpu.scheduler.decisions` audit trail itself — bounded
  retention (per job and across jobs), the CLOSED reason enum (a
  drift-guard that fails when the policy emits a reason the enum doesn't
  name OR names one the policy can never emit), counter monotonicity;
* the ``GET /jobs/{id}/decisions`` route through the scheduler HTTP
  facade and the full cluster (controller proxy + client + CLI), plus the
  K-AVG round-statistics signals landing in MetricUpdate/History/tsdb.
"""

import threading

import numpy as np
import pytest

from kubeml_tpu.api.types import (History, JobState, MetricUpdate,
                                  TrainOptions, TrainRequest, TrainTask)
from kubeml_tpu.scheduler.decisions import (DIRECTIONS, REASONS,
                                            DecisionLog, ScaleDecision)
from kubeml_tpu.scheduler.policy import (SPEEDUP_THRESHOLD,
                                         SLOWDOWN_THRESHOLD,
                                         ThroughputBasedPolicy,
                                         next_power_down)

pytestmark = pytest.mark.elastic


def _task(job_id="j1", default_parallelism=4, parallelism=0, elapsed=-1.0):
    return TrainTask(
        job_id=job_id,
        parameters=TrainRequest(
            function_name="f", dataset="d",
            options=TrainOptions(default_parallelism=default_parallelism),
        ),
        state=JobState(parallelism=parallelism, elapsed_time=elapsed),
    )


def _seeded_policy(job="j1", cached=10.0, **kw):
    """A policy whose epoch-time cache for ``job`` holds ``cached``."""
    p = ThroughputBasedPolicy(default_parallelism=4, max_parallelism=16, **kw)
    p.calculate_parallelism(_task(job))          # new-task: cache = inf
    p.calculate_parallelism(_task(job, parallelism=4, elapsed=cached))
    return p


# --- policy boundary behavior -------------------------------------------


class TestPolicyBoundaries:
    def test_exactly_at_speedup_threshold_scales_up(self):
        # elapsed == cached * 1.05 satisfies `elapsed <= cached * 1.05`
        p = _seeded_policy(cached=10.0)
        par, _ = p.calculate_parallelism(
            _task(parallelism=4, elapsed=10.0 * SPEEDUP_THRESHOLD))
        assert par == 8

    def test_just_above_speedup_threshold_holds(self):
        p = _seeded_policy(cached=10.0)
        par, _ = p.calculate_parallelism(
            _task(parallelism=4, elapsed=10.0 * SPEEDUP_THRESHOLD + 1e-6))
        assert par == 4

    def test_exactly_at_slowdown_threshold_scales_down(self):
        # elapsed == cached * 1.2 satisfies `elapsed >= cached * 1.2`
        p = _seeded_policy(cached=10.0)
        par, _ = p.calculate_parallelism(
            _task(parallelism=4, elapsed=10.0 * SLOWDOWN_THRESHOLD))
        assert par == 2

    def test_just_below_slowdown_threshold_holds(self):
        p = _seeded_policy(cached=10.0)
        par, _ = p.calculate_parallelism(
            _task(parallelism=4, elapsed=10.0 * SLOWDOWN_THRESHOLD - 1e-6))
        assert par == 4

    def test_pow2_cap_floor_on_non_pow2_caps(self):
        # the constructor floors the cap with next_power_down(max + 1) so
        # scale-up can never land on a topology-illegal level
        assert next_power_down(6 + 1) == 4
        assert ThroughputBasedPolicy(4, max_parallelism=6).max_parallelism == 4
        assert ThroughputBasedPolicy(4, max_parallelism=5).max_parallelism == 4
        # exact powers of two survive the floor unchanged
        assert ThroughputBasedPolicy(4, max_parallelism=8).max_parallelism == 8
        assert ThroughputBasedPolicy(4, max_parallelism=1).max_parallelism == 1
        # and a fast epoch at the floored cap holds, never exceeds it
        p = _seeded_policy(cached=10.0)
        p.max_parallelism = 4
        par, _ = p.calculate_parallelism(_task(parallelism=4, elapsed=1.0))
        assert par == 4

    def test_reseed_after_preempt_id_reuse(self):
        # preempt path: the job finishes (stale guard records it), then the
        # SAME id is resubmitted with resume=True — the fresh submission
        # must clear the finished mark and start cleanly as a new task
        p = _seeded_policy(cached=10.0)
        p.task_finished("j1")
        assert p.calculate_parallelism(
            _task(parallelism=4, elapsed=12.0)) is None  # stale drop
        par, is_new = p.calculate_parallelism(_task("j1"))
        assert is_new and par == 4
        # and elasticity resumes against a fresh cache (inf -> scale up)
        par, _ = p.calculate_parallelism(_task(parallelism=4, elapsed=9.0))
        assert par == 8

    def test_unseen_live_job_reseeds_cache(self):
        # policy swapped mid-run: keep parallelism, reseed, then resume
        p = ThroughputBasedPolicy(4, max_parallelism=16)
        par, is_new = p.calculate_parallelism(_task(parallelism=4, elapsed=10.0))
        assert (par, is_new) == (4, False)
        par, _ = p.calculate_parallelism(_task(parallelism=4, elapsed=9.0))
        assert par == 8  # 9.0 <= 10.0 * 1.05

    def test_limit_parallelism_records_limited_hold(self):
        p = _seeded_policy(cached=10.0, limit_parallelism=True)
        log = DecisionLog()
        p.bind_decision_log(log)
        par, _ = p.calculate_parallelism(_task(parallelism=4, elapsed=1.0))
        assert par == 4
        assert log.for_job("j1")[-1]["reason"] == "limited"


# --- the decision log ----------------------------------------------------


class TestDecisionLog:
    def _d(self, job="j", reason="steady", **kw):
        direction = REASONS[reason][0]
        return ScaleDecision(job_id=job, from_p=4, to_p=4,
                             direction=direction, reason=reason, **kw)

    def test_bounded_per_job_retention_keeps_newest(self):
        log = DecisionLog(per_job=4)
        for i in range(10):
            log.record(self._d(elapsed=float(i)))
        kept = log.for_job("j")
        assert len(kept) == 4
        assert [d["seq"] for d in kept] == [7, 8, 9, 10]  # newest, in order
        assert log.total("j") == 10  # ever-recorded count survives the ring

    def test_bounded_job_count_evicts_oldest_job(self):
        log = DecisionLog(per_job=4, max_jobs=3)
        for j in ("a", "b", "c", "d"):
            log.record(self._d(job=j))
        assert log.jobs() == ["b", "c", "d"]
        assert log.for_job("a") == []
        # the seq counter SURVIVES ring eviction: a long-lived job whose
        # ring was evicted by newer jobs must not restart at seq 1 (the
        # per-job sequence is documented monotonic, total() ever-recorded)
        d = log.record(self._d(job="a"))
        assert d.seq == 2 and log.total("a") == 2

    def test_counts_are_cumulative_across_eviction(self):
        log = DecisionLog(per_job=2, max_jobs=1)
        for j in ("a", "b", "c"):
            log.record(self._d(job=j, reason="speedup"))
        assert log.counts() == {("up", "speedup"): 3}

    def test_unenumerated_reason_rejected(self):
        log = DecisionLog()
        with pytest.raises(ValueError, match="unenumerated"):
            log.record(ScaleDecision(job_id="j", from_p=1, to_p=2,
                                     direction="up", reason="vibes"))
        with pytest.raises(ValueError, match="direction"):
            log.record(ScaleDecision(job_id="j", from_p=1, to_p=2,
                                     direction="down", reason="speedup"))

    def test_concurrent_records_stay_consistent(self):
        log = DecisionLog(per_job=1000)
        def work():
            for _ in range(100):
                log.record(self._d(reason="speedup"))
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert log.total("j") == 400
        assert log.counts() == {("up", "speedup"): 400}
        assert [d["seq"] for d in log.for_job("j")] == list(range(1, 401))


def test_reason_enum_is_closed_drift_guard():
    """Exercise EVERY policy path and require the emitted reason set to be
    exactly :data:`REASONS`: a reason the policy emits but the enum doesn't
    name fails at record time; a reason the enum names but no path emits
    fails here — the vocabulary cannot drift in either direction. Every
    reason's direction must also be a member of :data:`DIRECTIONS`."""
    assert {d for d, _ in REASONS.values()} <= set(DIRECTIONS)

    log = DecisionLog()
    p = ThroughputBasedPolicy(default_parallelism=4, max_parallelism=8)
    p.bind_decision_log(log)
    p.calculate_parallelism(_task("j1"))                              # new-task
    p.calculate_parallelism(_task("j1", parallelism=4, elapsed=10.0))  # speedup (vs inf)
    p.calculate_parallelism(_task("j1", parallelism=8, elapsed=10.0))  # at-cap
    p.calculate_parallelism(_task("j1", parallelism=8, elapsed=13.0))  # slowdown
    p.calculate_parallelism(_task("j1", parallelism=1, elapsed=20.0))  # at-floor
    p.calculate_parallelism(_task("j1", parallelism=4, elapsed=22.0))  # steady
    p.calculate_parallelism(_task("j2", parallelism=4, elapsed=10.0))  # reseed
    p.task_finished("j1")
    assert p.calculate_parallelism(
        _task("j1", parallelism=4, elapsed=10.0)) is None              # stale-drop
    limited = ThroughputBasedPolicy(4, max_parallelism=8,
                                    limit_parallelism=True)
    limited.bind_decision_log(log)
    limited.calculate_parallelism(_task("j3"))
    limited.calculate_parallelism(_task("j3", parallelism=4, elapsed=1.0))  # limited

    emitted = {reason for _dir, reason in log.counts()}
    assert emitted == set(REASONS), (
        f"reason enum drifted: enum-only={set(REASONS) - emitted}, "
        f"emitted-only={emitted - set(REASONS)}")


# --- the metrics surface -------------------------------------------------


def test_scale_decision_counters_and_job_gauges_render():
    from kubeml_tpu.ps.metrics import MetricsRegistry

    reg = MetricsRegistry()
    log = DecisionLog()
    log.record(ScaleDecision(job_id="j", from_p=2, to_p=4,
                             direction="up", reason="speedup"))
    reg.set_decision_source(log.counts)
    reg.update(MetricUpdate(job_id="abc", train_loss=1.0, parallelism=4,
                            epoch_duration=2.0, round_seconds=[0.1, 0.3],
                            round_divergence=[0.01, 0.02],
                            round_loss_spread=[0.5],
                            round_skew_ratio=3.0))
    text = reg.render()
    assert ('kubeml_scale_decisions_total{direction="up",reason="speedup"} 1'
            in text)
    # the statistical-efficiency histograms, on ratio-scaled buckets
    assert "# TYPE kubeml_job_worker_divergence histogram" in text
    assert 'kubeml_job_worker_divergence_count{jobid="abc"} 2' in text
    assert 'kubeml_job_worker_divergence_bucket{jobid="abc",le="0.01"} 1' in text
    assert 'kubeml_job_loss_spread_count{jobid="abc"} 1' in text
    assert 'kubeml_job_round_skew_ratio_bucket{jobid="abc",le="3"} 1' in text
    # epoch progress gauge: without the wire field it counts pushes...
    assert 'kubeml_job_epoch{jobid="abc"} 1.0' in text
    reg.update(MetricUpdate(job_id="abc", parallelism=4, epoch_duration=2.0))
    assert 'kubeml_job_epoch{jobid="abc"} 2.0' in reg.render()
    # ...and the job-reported count wins when present (resume-correct: a
    # job resuming at epoch 5 must not read as epoch 3)
    reg.update(MetricUpdate(job_id="abc", parallelism=4, epoch_duration=2.0,
                            epoch=5))
    assert 'kubeml_job_epoch{jobid="abc"} 5.0' in reg.render()
    # the tsdb sampler's snapshot carries parallelism AND the signal means
    snap = reg.job_gauges_snapshot()
    assert snap[("kubeml_job_parallelism", "abc")] == 4.0
    assert snap[("kubeml_job_worker_divergence", "abc")] == pytest.approx(0.015)
    assert snap[("kubeml_job_round_skew_ratio", "abc")] == 3.0
    # ... and clears with the job
    reg.clear("abc")
    assert not reg.job_gauges_snapshot()


def test_ps_sampler_folds_training_series_into_tsdb(tmp_config):
    """Satellite 1: MetricUpdate.parallelism (and the signal gauges) must
    land in the embedded time-series store under the exposition's own
    name/label scheme, and the scale-decision counters next to them."""
    from kubeml_tpu.ps.parameter_server import ParameterServer

    ps = ParameterServer(config=tmp_config)
    from kubeml_tpu.scheduler.scheduler import Scheduler

    sched = Scheduler(ps, config=tmp_config, max_parallelism=8)
    ps.bind_scheduler(sched)
    sched.policy.calculate_parallelism(_task("jobA", default_parallelism=2))
    ps.metrics.update(MetricUpdate(job_id="jobA", train_loss=0.5,
                                   parallelism=2, epoch_duration=1.0,
                                   round_divergence=[0.02],
                                   round_skew_ratio=1.5))
    ps.sampler.tick()
    hist = ps.metrics_history(match="kubeml_", stats=True)
    series = hist["series"]
    assert 'kubeml_job_parallelism{jobid="jobA"}' in series
    assert series['kubeml_job_parallelism{jobid="jobA"}']["latest"] == 2.0
    assert 'kubeml_job_worker_divergence{jobid="jobA"}' in series
    assert ('kubeml_scale_decisions_total{direction="new",reason="new-task"}'
            in series)


# --- the HTTP surface ----------------------------------------------------


def test_scheduler_api_serves_decisions_route(tmp_config):
    """GET /jobs/{id}/decisions end to end over the scheduler facade,
    without booting a full cluster."""
    from kubeml_tpu.ps.metrics import MetricsRegistry
    from kubeml_tpu.scheduler.scheduler import Scheduler
    from kubeml_tpu.scheduler.transport import SchedulerAPI, SchedulerClient

    class StubPS:
        metrics = MetricsRegistry()

        def list_tasks(self):
            return []

    sched = Scheduler(StubPS(), config=tmp_config, max_parallelism=8)
    sched.policy.calculate_parallelism(_task("web1", default_parallelism=2))
    sched.policy.calculate_parallelism(
        _task("web1", parallelism=2, elapsed=5.0))
    api = SchedulerAPI(sched, config=tmp_config).start()
    try:
        client = SchedulerClient(api.url)
        out = client.job_decisions("web1")
        assert out["job_id"] == "web1" and out["total"] == 2
        reasons = [d["reason"] for d in out["decisions"]]
        assert reasons == ["new-task", "speedup"]
        inputs = out["decisions"][1]["inputs"]
        assert inputs["elapsed"] == 5.0 and inputs["cached"] is None  # inf
        assert inputs["cap"] == 8
        # unknown job: an empty trail, not an error (the audit may simply
        # have evicted it)
        assert client.job_decisions("nope")["decisions"] == []
    finally:
        api.stop()


# --- K-AVG round statistics ---------------------------------------------


class TestRoundStats:
    def _trainer(self, enabled, **kw):
        import sys
        sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
        from test_kavg import TinyModel

        from kubeml_tpu.engine.kavg import KAvgTrainer

        t = KAvgTrainer(TinyModel(), precision="f32", donate=False, **kw)
        t.round_stats = enabled  # explicit, independent of ambient env
        return t

    def _round(self, n=4, steps=2, b=8, seed=0):
        r = np.random.default_rng(seed)
        x = r.normal(size=(n, steps, b, 8)).astype(np.float32)
        y = r.integers(0, 4, size=(n, steps, b)).astype(np.int32)
        m = np.ones((n, steps, b), np.float32)
        return x, y, m

    def test_stats_off_is_bit_identical_to_stats_on_weights(self):
        """KUBEML_ROUND_STATS=0 restores the uninstrumented round program;
        the instrumented one must be a pure observer — identical weights
        and loss bit for bit, stats only on the side."""
        import jax

        x, y, m = self._round()
        rng = jax.random.PRNGKey(0)
        on = self._trainer(True)
        off = self._trainer(False)
        v_on = on.init_variables(rng, x[0, 0], 4)
        v_off = off.init_variables(rng, x[0, 0], 4)
        o_on, l_on = on.sync_round(v_on, x, y, m, rng, lr=0.05)
        o_off, l_off = off.sync_round(v_off, x, y, m, rng, lr=0.05)
        assert float(l_on) == float(l_off)
        for a, b_ in zip(jax.tree.leaves(o_on), jax.tree.leaves(o_off)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
        assert on.last_round_stats is not None
        assert off.last_round_stats is None

    def test_divergence_matches_hand_computation(self):
        """The on-chip reduction == numpy: weighted Frobenius norm of
        (stacked - participant mean) over the mean's norm; spread ==
        max - min participating worker loss."""
        import jax

        x, y, m = self._round(seed=3)
        rng = jax.random.PRNGKey(1)
        t = self._trainer(True)
        v = t.init_variables(rng, x[0, 0], 4)
        wm = np.array([1, 1, 1, 0], np.float32)  # worker 3 masked out
        t.sync_round(v, x, y, m, rng, lr=0.05, worker_mask=wm)
        spread, divergence = np.asarray(t.last_round_stats)

        # hand simulation: per-worker K SGD steps (reusing the fidelity
        # harness from test_kavg), then the same reductions in numpy
        import optax
        import jax.numpy as jnp
        from test_kavg import TinyModel

        model = TinyModel(lr=0.05)
        variables = model.init(rng, jnp.asarray(x[0, 0]))
        tx = optax.sgd(0.05)
        finals, losses = [], []
        rngs = jax.random.split(rng, 4)
        for w in range(4):
            p = variables["params"]
            opt = tx.init(p)
            wl = []
            for s in range(x.shape[1]):
                step_rng = jax.random.fold_in(rngs[w], s)

                def loss_fn(pp):
                    logits, _ = model.forward(
                        {"params": pp}, jnp.asarray(x[w, s]), train=True,
                        rng=step_rng)
                    return optax.softmax_cross_entropy_with_integer_labels(
                        logits, jnp.asarray(y[w, s])).mean()

                l, g = jax.value_and_grad(loss_fn)(p)
                upd, opt = tx.update(g, opt, p)
                p = optax.apply_updates(p, upd)
                wl.append(float(l))
            finals.append(jax.tree.map(np.asarray, p))
            losses.append(float(np.mean(wl)))
        active = losses[:3]
        np.testing.assert_allclose(spread, max(active) - min(active),
                                   rtol=1e-4)
        mean = jax.tree.map(
            lambda *ls: np.mean(np.stack(ls[:3]), axis=0), *finals)
        num = den = 0.0
        for leaf_m, *leaf_ws in zip(jax.tree.leaves(mean),
                                    *(jax.tree.leaves(f) for f in finals)):
            for w in range(3):
                num += float(((leaf_ws[w] - leaf_m) ** 2).sum())
            den += float((leaf_m ** 2).sum())
        want = np.sqrt(num / 3.0) / np.sqrt(den)
        np.testing.assert_allclose(divergence, want, rtol=1e-3)

    def test_job_pushes_signals_and_records_history(self, tmp_config):
        """A threaded TrainJob must push round_divergence/spread/skew with
        its MetricUpdate and append the epoch means to its History."""
        import sys
        sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
        import flax.linen as nn
        import optax

        from conftest import make_blobs
        from kubeml_tpu.data.dataset import KubeDataset
        from kubeml_tpu.engine.job import TrainJob
        from kubeml_tpu.runtime.model import KubeModel
        from kubeml_tpu.storage.store import ShardStore

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                return nn.Dense(10)(x.reshape((x.shape[0], -1)))

        class Ds(KubeDataset):
            def __init__(self):
                super().__init__("blobs")

        class Model(KubeModel):
            def __init__(self):
                super().__init__(Ds())

            def build(self):
                return Net()

            def configure_optimizers(self):
                return optax.sgd(self.lr)

        store = ShardStore(config=tmp_config)
        x, y = make_blobs(128, shape=(8, 8, 1))
        store.create("blobs", x, y, x[:32], y[:32])
        updates = []
        job = TrainJob(
            "statjob",
            TrainRequest(batch_size=16, epochs=2, dataset="blobs", lr=0.05,
                         function_name="f",
                         options=TrainOptions(default_parallelism=2, k=1,
                                              static_parallelism=True,
                                              validate_every=0,
                                              save_model=False,
                                              precision="f32")),
            Model(),
            store=store,
            on_metrics=updates.append,
        )
        hist = job.train()
        assert len(updates) == 2
        for u in updates:
            assert u.round_divergence and all(
                v >= 0 for v in u.round_divergence)
            assert u.round_loss_spread
            assert len(u.round_divergence) == len(u.round_seconds)
            if len(u.round_seconds) >= 2:
                assert u.round_skew_ratio >= 1.0
        # with instrumentation on the signal lists stay INDEX-ALIGNED with
        # train_loss (an unmeasured epoch would record NaN, never skip)
        assert len(hist.worker_divergence) == len(hist.train_loss) == 2
        assert len(hist.loss_spread) == 2
        assert len(hist.round_skew) == 2  # 1-round epochs record NaN
        # the wire form is strict JSON (NaN placeholders cross as null and
        # round-trip back to NaN in memory)
        wire = hist.to_json()
        assert "NaN" not in wire
        restored = History.from_json(wire)
        assert restored.worker_divergence == hist.worker_divergence
        assert all(v != v for v in restored.round_skew)  # NaN restored


# --- full-cluster end to end (slow tier) ---------------------------------


@pytest.fixture
def cluster(tmp_config):
    from kubeml_tpu.cluster import LocalCluster

    with LocalCluster(config=tmp_config) as c:
        yield c


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


def test_decisions_route_end_to_end(cluster):
    """The heavy e2e: an elastic job through the full HTTP chain, then the
    decision log via the controller proxy, the decision counters on
    /metrics, the parallelism/divergence series in /metrics/history, and
    the `kubeml decisions` rendering."""
    import contextlib
    import io
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from conftest import make_blobs, wait_job_done

    from kubeml_tpu.cli import main as cli_main
    from kubeml_tpu.controller.client import KubemlClient

    client = KubemlClient(cluster.controller_url)
    x, y = make_blobs(256, shape=(8, 8, 1))
    client.datasets().create("blobs", x, y, x[:64], y[:64])
    client.functions().create("tiny", FN_SOURCE)
    req = TrainRequest(
        batch_size=16, epochs=3, dataset="blobs", lr=0.05,
        function_name="tiny",
        options=TrainOptions(default_parallelism=2, k=2,
                             static_parallelism=False, validate_every=0))
    job_id = client.networks().train(req)
    wait_job_done(client, job_id)

    data = client.tasks().decisions(job_id)
    decisions = data["decisions"]
    # one new-task decision + one per epoch report
    assert data["total"] == 1 + 3
    assert decisions[0]["reason"] == "new-task"
    for d in decisions:
        assert d["reason"] in REASONS
        assert d["direction"] in DIRECTIONS
        assert set(d["inputs"]) == {"cached", "elapsed", "speedup_threshold",
                                    "slowdown_threshold", "cap",
                                    "limit_parallelism"}
    # decision counters visible on the PS exposition
    import requests

    text = requests.get(f"{cluster.ps_api.url}/metrics", timeout=5).text
    assert 'kubeml_scale_decisions_total{direction="new",reason="new-task"}' \
        in text
    # the tsdb sampled the training gauges while the job ran
    hist = client.metrics_history(match="kubeml_job_")
    assert any(k.startswith("kubeml_job_parallelism{") for k in hist["series"])
    assert any(k.startswith("kubeml_job_worker_divergence{")
               for k in hist["series"])
    # the operator command renders the trail through the controller proxy
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["--url", cluster.controller_url, "decisions", job_id])
    out = buf.getvalue()
    assert rc == 0 and "new-task" in out and "REASON" in out
