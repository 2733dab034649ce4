"""Tracing + failure-injection subsystem tests (SURVEY §5: the reference has
neither tracing nor chaos; partial-failure semantics mirror util.go:144-166)."""

import json
import threading

import numpy as np
import pytest

from kubeml_tpu.api.errors import MergeError
from kubeml_tpu.engine.failures import FailureInjector, WorkerHealth
from kubeml_tpu.utils.tracing import Tracer

from test_job import KubeLeNet, _request, mnist_store  # noqa: F401


# --- Tracer ---


def test_tracer_disabled_records_nothing():
    t = Tracer()
    with t.span("x"):
        pass
    assert t.spans() == []


def test_tracer_spans_and_summary():
    t = Tracer(enabled=True)
    with t.span("outer", job="j1"):
        with t.span("inner"):
            pass
        with t.span("inner"):
            pass
    assert len(t.spans()) == 3
    assert len(t.spans("inner")) == 2
    s = t.summary()
    assert s["inner"]["count"] == 2
    assert s["outer"]["count"] == 1
    assert s["outer"]["max_s"] >= s["inner"]["max_s"]
    assert t.spans("outer")[0].attrs == {"job": "j1"}


def test_tracer_record_external_duration():
    t = Tracer(enabled=True)
    t.record("device_step", 0.25, round=3)
    (s,) = t.spans()
    assert s.duration == 0.25 and s.attrs["round"] == 3


def test_tracer_chrome_export_and_flush(tmp_path):
    t = Tracer(enabled=True)
    with t.span("epoch", epoch=0):
        pass
    path = t.flush(tmp_path / "trace.json")
    data = json.loads(path.read_text())
    # one process-name row per service, then the spans with their identity
    row, ev = data["traceEvents"]
    assert row["ph"] == "M" and row["args"] == {"name": t.service}
    assert ev["name"] == "epoch" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["args"]["epoch"] == 0
    (s,) = t.spans()
    assert ev["args"]["trace_id"] == s.trace_id
    assert ev["args"]["span_id"] == s.span_id


def test_tracer_thread_safety():
    t = Tracer(enabled=True)

    def worker():
        for _ in range(200):
            with t.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [x.start() for x in threads]
    [x.join() for x in threads]
    assert len(t.spans()) == 1600


def test_tracer_concurrent_nesting_stays_per_thread():
    """The context stack is thread-local: concurrent threads nesting spans
    must each see only their OWN parent links (a shared stack would cross-
    wire parent ids under contention)."""
    t = Tracer(enabled=True)

    def worker(i):
        for _ in range(50):
            with t.span(f"outer{i}"):
                with t.span(f"inner{i}"):
                    pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    [x.start() for x in threads]
    [x.join() for x in threads]
    by_id = {s.span_id: s for s in t.spans()}
    for s in t.spans():
        if s.name.startswith("inner"):
            i = s.name[len("inner"):]
            parent = by_id[s.parent_id]
            assert parent.name == f"outer{i}"
            assert parent.trace_id == s.trace_id
        else:
            assert s.parent_id is None  # every outer is its own trace root


def test_tracer_max_spans_drop_counter(monkeypatch):
    from kubeml_tpu.utils import tracing

    monkeypatch.setattr(tracing, "MAX_SPANS", 5)
    t = Tracer(enabled=True)
    for i in range(9):
        t.record(f"s{i}", 0.01)
    assert len(t.spans()) == 5
    assert t.dropped == 4
    # ring semantics: the OLDEST spans evicted, so a long-lived service
    # still records new tasks' traces after weeks of server spans
    assert [s.name for s in t.spans()] == ["s4", "s5", "s6", "s7", "s8"]
    t.clear()
    assert t.dropped == 0 and t.spans() == []


# --- trace identity / W3C propagation ---


def test_traceparent_round_trip():
    from kubeml_tpu.utils.tracing import (TraceContext, new_span_id,
                                          new_trace_id, parse_traceparent)

    ctx = TraceContext(new_trace_id(), new_span_id())
    header = ctx.traceparent()
    assert header == f"00-{ctx.trace_id}-{ctx.span_id}-01"
    assert parse_traceparent(header) == ctx
    # malformed/invalid inputs decode to None, never raise
    for bad in (None, "", "garbage", "00-zz-xx-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace id
                "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # zero span id
                "ff-" + "a" * 32 + "-" + "b" * 16 + "-01"):  # version ff
        assert parse_traceparent(bad) is None


def test_span_identity_nesting_and_inbound_context():
    from kubeml_tpu.utils import tracing

    t = Tracer(enabled=True, service="svc")
    with t.span("root") as root:
        with t.span("child") as child:
            pass
    assert root.trace_id == child.trace_id
    assert child.parent_id == root.span_id and root.parent_id is None
    # an inbound context (the HTTP server binding a traceparent) parents the
    # next span even though no local span is open
    ctx = tracing.TraceContext(tracing.new_trace_id(), tracing.new_span_id())
    with tracing.use_context(ctx):
        assert tracing.current_context() == ctx
        hdrs = tracing.trace_headers({"X-Other": "1"})
        assert hdrs["traceparent"] == ctx.traceparent()
        assert hdrs["X-Other"] == "1"
        with t.span("served") as s:
            pass
    assert s.trace_id == ctx.trace_id and s.parent_id == ctx.span_id
    assert tracing.current_context() is None
    assert tracing.trace_headers() == {}


def test_two_process_propagation(tmp_path):
    """A child PROCESS handed a traceparent must record spans carrying the
    parent's trace_id with parent_id pointing at the parent span — the
    cross-process stitch the control plane relies on."""
    import subprocess
    import sys

    from kubeml_tpu.utils import tracing

    t = Tracer(enabled=True, service="parent")
    child_script = (
        "import json, sys\n"
        "from kubeml_tpu.utils import tracing\n"
        "t = tracing.Tracer(enabled=True, service='child')\n"
        "ctx = tracing.parse_traceparent(sys.argv[1])\n"
        "with tracing.use_context(ctx):\n"
        "    with t.span('child.work', job='j1'):\n"
        "        pass\n"
        "print(json.dumps([s.to_dict() for s in t.spans()]))\n"
    )
    with t.span("parent.request", job="j1") as parent_span:
        header = tracing.current_context().traceparent()
        out = subprocess.run(
            [sys.executable, "-c", child_script, header],
            capture_output=True, text=True, timeout=120, check=True,
        )
    (child,) = json.loads(out.stdout)
    assert child["trace_id"] == parent_span.trace_id
    assert child["parent_id"] == parent_span.span_id
    assert child["service"] == "child"
    assert child["pid"] != parent_span.to_dict()["pid"]
    # the merged chrome export renders one process row per service
    merged = tracing.merge_chrome_trace(
        [parent_span.to_dict(), child])
    rows = [e["args"]["name"] for e in merged["traceEvents"]
            if e["ph"] == "M"]
    assert rows == ["parent", "child"]


# --- FailureInjector ---


def test_injector_schedule_and_determinism():
    a = FailureInjector(schedule={1: [0, 2]}, seed=7)
    b = FailureInjector(schedule={1: [0, 2]}, seed=7)
    for _ in range(3):
        np.testing.assert_array_equal(a.mask(4), b.mask(4))
    c = FailureInjector(schedule={1: [0, 2]})
    assert c.mask(4).tolist() == [1, 1, 1, 1]
    assert c.mask(4).tolist() == [0, 1, 0, 1]  # round 1: workers 0 and 2 down
    assert c.mask(4).tolist() == [1, 1, 1, 1]


def test_injector_keep_one_alive():
    inj = FailureInjector(prob=1.0, seed=0)
    for _ in range(10):
        m = inj.mask(4)
        assert m.sum() == 1.0  # everything fails except the guaranteed survivor


def test_injector_total_failure_allowed_when_disabled():
    inj = FailureInjector(prob=1.0, keep_one_alive=False)
    assert inj.mask(4).sum() == 0.0


# --- WorkerHealth ---


def test_health_threshold_and_recovery():
    h = WorkerHealth(threshold=2)
    assert h.update(np.array([1, 0, 1])) == []
    assert h.update(np.array([1, 0, 1])) == [1]  # second consecutive failure
    assert h.update(np.array([1, 0, 1])) == []  # already reported
    assert h.persistent == {1}
    assert h.suggest_parallelism(3) == 2
    h.update(np.array([1, 1, 1]))  # worker 1 recovers
    assert h.persistent == set()
    assert h.suggest_parallelism(3) == 3


def test_health_multiple_dead():
    h = WorkerHealth(threshold=1)
    h.update(np.array([0, 0, 1, 1]))
    assert h.suggest_parallelism(4) == 2
    assert h.suggest_parallelism(1) == 1  # floor


def test_health_all_dead_round_floors_at_one():
    """Re-mesh edge: EVERY worker persistently failed still leaves a 1-wide
    mesh suggestion (the collective cannot shrink to zero shards); with the
    injector's keep_one_alive the all-dead mask never reaches health in the
    first place — the guaranteed survivor resets its own count."""
    h = WorkerHealth(threshold=1)
    assert sorted(h.update(np.zeros(4))) == [0, 1, 2, 3]
    assert h.persistent == {0, 1, 2, 3}
    assert h.suggest_parallelism(4) == 1  # floor 1, never 0
    # the keep_one_alive injector cannot produce that mask: one worker always
    # survives, so at most n-1 cross the threshold per round
    inj = FailureInjector(prob=1.0, seed=3, keep_one_alive=True)
    h2 = WorkerHealth(threshold=1)
    h2.update(inj.mask(4))
    assert len(h2.persistent) == 3
    assert h2.suggest_parallelism(4) == 1  # 4 - 3, already the floor


def test_health_dead_beyond_current_parallelism_does_not_shrink():
    """parallelism_after_death counts only persistently dead workers BELOW
    the current width: after an elastic shrink, a stale higher index must
    not shrink the mesh again."""
    h = WorkerHealth(threshold=1)
    h.update(np.array([1, 1, 1, 0]))  # worker 3 persistently dead
    assert h.suggest_parallelism(4) == 3
    # mesh already shrunk to 2: the dead index 3 is out of range
    assert h.suggest_parallelism(2) == 2


def test_health_reset_clears_consecutive_counts_after_shrink():
    """Worker indices renumber on a re-mesh, so consecutive-failure counts
    must NOT transfer: a worker one round short of the threshold before the
    shrink starts from zero after reset()."""
    h = WorkerHealth(threshold=3)
    h.update(np.array([1, 0]))
    h.update(np.array([1, 0]))  # worker 1 at 2 of 3
    assert h.persistent == set()
    h.reset()  # the job re-meshed; indices renumbered
    assert h.update(np.array([1, 0])) == []  # count restarted at 1, not 3
    assert h.persistent == set()
    h.update(np.array([1, 0]))
    assert h.update(np.array([1, 0])) == [1]  # three POST-reset rounds trip it


# --- TrainJob integration ---


def _chaos_job(job_id, req, store, cfg, chaos, **kw):
    from kubeml_tpu.engine.job import TrainJob
    from kubeml_tpu.storage import CheckpointStore, HistoryStore

    return TrainJob(
        job_id, req, KubeLeNet(), store=store,
        history_store=HistoryStore(config=cfg),
        checkpoint_store=CheckpointStore(config=cfg), chaos=chaos, **kw,
    )


def test_job_survives_injected_failures(mnist_store, tmp_config):
    """Rounds with failed workers average over the survivors (util.go:144-166)."""
    req = _request(epochs=2, options={"default_parallelism": 4,
                                      "static_parallelism": True, "k": 2})
    chaos = FailureInjector(prob=0.3, seed=3)
    job = _chaos_job("chaos1", req, mnist_store, tmp_config, chaos)
    hist = job.train()
    assert len(hist.train_loss) == 2
    assert all(np.isfinite(l) for l in hist.train_loss)


def test_job_total_failure_round_errors(mnist_store, tmp_config):
    """Zero healthy workers in a round is a hard MergeError (job.go:388-391)."""
    from kubeml_tpu.api.errors import KubeMLError

    req = _request(epochs=1, options={"default_parallelism": 2,
                                      "static_parallelism": True, "k": 2})
    chaos = FailureInjector(prob=1.0, keep_one_alive=False)
    job = _chaos_job("chaos2", req, mnist_store, tmp_config, chaos)
    with pytest.raises((MergeError, KubeMLError)):
        job.train()


def test_job_health_shrinks_parallelism(mnist_store, tmp_config):
    """A persistently dead worker shrinks the mesh at the epoch boundary."""
    # worker 3 fails every round from the start
    schedule = {r: [3] for r in range(200)}
    chaos = FailureInjector(schedule=schedule)
    req = _request(epochs=3, options={"default_parallelism": 4,
                                      "static_parallelism": False, "k": 2})
    job = _chaos_job("chaos3", req, mnist_store, tmp_config, chaos,
                     health_threshold=2)
    hist = job.train()
    assert hist.parallelism[0] == 4
    assert hist.parallelism[-1] == 3, f"no health re-mesh: {hist.parallelism}"
    assert all(np.isfinite(l) for l in hist.train_loss)


def test_round_with_no_effective_participants_keeps_weights(tmp_config, rng):
    """If every data-bearing worker is masked but a fully-padded worker stays
    'healthy', the round must keep the pre-round weights — never average an
    empty set into zeros (and the loss reads NaN so the host can filter it)."""
    import jax
    import optax

    from kubeml_tpu.engine.kavg import KAvgTrainer
    from kubeml_tpu.runtime.model import KubeModel
    from kubeml_tpu.data.dataset import KubeDataset
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x.reshape((x.shape[0], -1)))

    class Ds(KubeDataset):
        def __init__(self):
            super().__init__("unused")

    class M(KubeModel):
        def __init__(self):
            super().__init__(Ds())

        def build(self):
            return Tiny()

        def configure_optimizers(self):
            return optax.sgd(0.1)

    trainer = KAvgTrainer(M(), precision="f32")
    n, k, b = 2, 1, 4
    x = rng.normal(size=(n, k, b, 3)).astype(np.float32)
    y = rng.integers(0, 4, size=(n, k, b)).astype(np.int64)
    mask = np.zeros((n, k, b), np.float32)
    mask[0] = 1.0  # worker 0 has data, worker 1 is fully padded
    variables = trainer.init_variables(jax.random.PRNGKey(0), x[0, 0], n)
    before = trainer.reference_variables(variables)
    # chaos kills worker 0 (the only data-bearing one); worker 1 stays healthy
    worker_mask = np.array([0.0, 1.0], np.float32)
    out_vars, loss = trainer.sync_round(
        variables, x, y, mask, jax.random.PRNGKey(1), lr=0.1,
        worker_mask=worker_mask,
    )
    assert np.isnan(float(loss))  # skipped-round marker
    after = trainer.reference_variables(out_vars)
    for a, b_ in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_job_chaos_prob_option_via_request(mnist_store, tmp_config):
    """TrainOptions.chaos_prob wires the injector without constructing one."""
    req = _request(epochs=1, options={"default_parallelism": 2,
                                      "static_parallelism": True, "k": 2,
                                      "chaos_prob": 0.5})
    job = _chaos_job("chaos4", req, mnist_store, tmp_config, chaos=None)
    assert job.chaos is not None
    hist = job.train()
    assert np.isfinite(hist.train_loss[0])


def test_job_emits_trace_spans(mnist_store, tmp_config, tmp_path):
    from kubeml_tpu.utils import tracing

    tracer = tracing.get_tracer()
    tracer.clear()
    tracer.enable(tmp_path)
    try:
        req = _request(epochs=1, options={"default_parallelism": 2,
                                          "static_parallelism": True, "k": 2})
        job = _chaos_job("traced", req, mnist_store, tmp_config, chaos=None)
        job.train()
        names = {s.name for s in tracer.spans()}
        assert {"job.epoch", "job.round", "job.validate"} <= names
        path = tracer.flush()
        data = json.loads(path.read_text())
        events = data["traceEvents"]
        rows = [e for e in events if e["ph"] == "M"]
        assert len(rows) == len({s.service for s in tracer.spans()})
        assert len(events) - len(rows) == len(tracer.spans())
    finally:
        tracer.disable()
        tracer.clear()


def test_device_profile_writes_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.utils.tracing import (CLOCK_TIE, device_profile,
                                          get_tracer)

    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.enabled = False
    try:
        with tracer.span("before"):   # tracer off: not recorded
            pass
        with device_profile(tmp_path / "prof"):
            with tracer.span("inside", k=1):   # the block turns it on
                jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones(8)))
        assert not tracer.enabled   # and leaves it as it found it
    finally:
        tracer.enabled = was_on
        tracer.clear()
    # the profiler's files, with the clock tie as a host annotation
    (pb,) = (tmp_path / "prof").rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(pb))
    assert any(e.name == CLOCK_TIE for plane in data.planes
               for line in plane.lines for e in line.events)
    # and beside them the block's spans, the tie first
    chrome = json.loads((tmp_path / "prof" / "kubeml-spans.json").read_text())
    names = [e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert names == [CLOCK_TIE, "inside"]
