"""Standalone job process — the reference's dedicated per-job pod, re-done as a
TPU-VM subprocess.

Reference parity: the PS creates a pod+service per job running
``/kubeml --jobPort 9090 --jobId <id>`` and talks HTTP to it
(reference: ml/pkg/ps/job_pod.go:96-217); the job pod serves
``/start /update /next /stop /health`` (reference: ml/pkg/train/api.go:141-149).
Here the PS spawns ``python -m kubeml_tpu.engine.job_runner --job-id <id>``;
the runner binds an ephemeral port, prints ``LISTENING <port>`` for the parent,
and serves:

* ``POST /start``  — TrainTask JSON; loads the function, runs TrainJob on a thread
* ``POST /update`` — scheduler's parallelism answer (the reference schedulerCh)
* ``DELETE /stop`` — cooperative stop
* ``POST /infer``  — serve the live model
* ``GET /state``   — status + epochs completed
* ``GET /health``  — readiness (the PS polls like pod-readiness, job_pod.go:18-63)

There is no ``/next`` barrier: the K-AVG merge is an on-chip collective inside
the job process, so the reference's worker<->merger HTTP rendezvous has no
counterpart (SURVEY §7). Epoch-end elasticity keeps the reference's loop shape:
runner -> scheduler ``/job`` -> PS ``/update/{id}`` -> runner ``/update``.
At exit the runner reports to the PS via ``POST /finish/{jobId}`` and the PS
reaps the process (the reference's jobFinished, ps/api.go:266-327).
"""

from __future__ import annotations

import argparse
import logging
import sys
import threading
from typing import Optional

log = logging.getLogger("kubeml.jobrunner")


class JobRunner:
    """One job's HTTP surface + lifecycle inside its own process."""

    def __init__(self, job_id: str, config=None, port: int = 0):
        from ..api.config import get_config
        from ..utils.httpd import Router, Service

        self.cfg = config or get_config()
        self.job_id = job_id
        self.job = None
        self.thread: Optional[threading.Thread] = None
        # the /start request's trace context, re-bound to the training thread
        # so the job's spans stitch under the submitting request's trace
        self._trace_ctx = None
        self.status = "starting"
        self.exit_error: Optional[str] = None
        self.done = threading.Event()
        # per-epoch reference weights served over the native tensor socket
        # (the RedisAI-role channel: the PS pulls weights and serves live
        # /infer locally instead of HTTP-JSON round-tripping payloads here).
        # Started lazily in _start — only K-AVG jobs publish into it.
        self._tensor_store = None
        self._tensor_server = None
        # the HTTP weight seam (engine/dataplane.WeightsWire): the same
        # per-epoch reference weights, binary-encoded with the configured
        # codec, served on GET /weights — the delta-compressed fallback when
        # the native socket is off or unbuilt (it used to be HTTP-JSON
        # /infer payload round-trips)
        self._weights_wire = None
        # writer-side delta state for the tensor-store channel: unchanged
        # leaves skip the socket write and keep their old manifest version
        self._publish_state = None
        # at most one publish runs at a time, OFF the training thread; a
        # publish superseded while queued is dropped (only the newest
        # epoch's weights matter to the serving path)
        self._publish_pending = None
        self._publish_thread: Optional[threading.Thread] = None
        # a FRESH box per epoch-end request: a late answer for epoch N must not
        # satisfy epoch N+1's wait (the PS allocates per-request _UpdateBoxes
        # for the same reason)
        self._update_box: Optional[list] = None  # [Event, parallelism]
        # dataplane counter hand-off to the PS (this process has no scraped
        # /metrics route — the epoch push is how weights.encode.* reaches
        # the PS exposition): each push cuts the delta since the last cut
        # into a SEQUENCED batch; unacked batches re-ride every push until
        # a client-observed success, and the PS applies each seq at most
        # once — neither a lost request nor a lost response can drop or
        # double-count bytes
        self._dp_cut: dict = {}  # counter snapshot at the last batch cut
        self._dp_unacked: list = []  # [{"seq", "phases"}] awaiting PS ack
        self._dp_seq = 0
        self._lock = threading.Lock()

        router = Router(f"job-{job_id}")
        router.route("POST", "/start", self._start)
        router.route("POST", "/update", self._update)
        router.route("DELETE", "/stop", self._stop)
        router.route("POST", "/preempt", self._preempt)
        router.route("POST", "/infer", self._infer)
        router.route("POST", "/generate", self._generate)
        router.route("GET", "/weights", self._weights)
        router.route("GET", "/state", self._state)
        self.service = Service(router, self.cfg.host, port)

    def _start_tensor_server(self) -> None:
        store = None
        try:
            from ..native.bindings import TensorServer, TensorStore

            store = TensorStore()
            if not store.native:
                store.close()
                log.info("native tensor store unavailable; PS will serve live "
                         "/infer over HTTP")
                return
            sock = self.cfg.job_socket_path(self.job_id)
            sock.unlink(missing_ok=True)
            self._tensor_server = TensorServer(store, str(sock))
            self._tensor_store = store
            log.info("tensor server for %s at %s", self.job_id, sock)
        except Exception:
            if store is not None and self._tensor_store is None:
                store.close()  # don't leak the native handle
            log.exception("tensor server start failed (non-fatal; HTTP infer "
                          "fallback remains)")

    def _publish_weights(self, variables: dict, epoch: int) -> None:
        """Epoch-weights hook, called on the TRAINING thread with a host
        snapshot. The publish itself (hashing, socket writes, wire encode)
        runs on a background thread so the next epoch's rounds dispatch
        while the weights move — weight publication is off the critical
        path. Queued-but-superseded publishes are dropped: only the newest
        epoch matters to the serving channel."""
        with self._lock:
            self._publish_pending = (variables, epoch)
            # the worker only exits after clearing _publish_thread under
            # THIS lock with pending empty, so a non-None handle means the
            # fresh item will be drained — no lost-wakeup race
            if self._publish_thread is not None:
                return
            self._publish_thread = threading.Thread(
                target=self._publish_worker, name=f"publish-{self.job_id}",
                daemon=True)
            self._publish_thread.start()

    def _publish_worker(self) -> None:
        from ..engine.dataplane import WeightsWire
        from ..native.weights import PublishState, publish_variables
        from ..utils import tracing

        while True:
            with self._lock:
                item = self._publish_pending
                self._publish_pending = None
                if item is None:
                    self._publish_thread = None
                    return
            variables, epoch = item
            try:
                with tracing.use_context(self._trace_ctx), \
                        tracing.bind_task(self.job_id), \
                        tracing.get_tracer().span("runner.publish_weights",
                                                  service="worker",
                                                  job=self.job_id,
                                                  epoch=epoch):
                    store = self._tensor_store
                    if store is not None:  # racing shutdown: silently skip
                        if self._publish_state is None:
                            self._publish_state = PublishState()
                        # delta publish: unchanged leaves skip the store
                        # write and keep their old manifest leaf version
                        # (publish_variables accounts bytes + bandwidth)
                        publish_variables(store, variables, epoch + 1,
                                          state=self._publish_state)
                    wire = self._weights_wire
                    if wire is None:
                        wire = self._weights_wire = WeightsWire()
                    wire.publish(variables, epoch + 1)
            except Exception:
                log.exception("%s: weight publish failed (non-fatal)",
                              self.job_id)

    def _join_publisher(self, timeout: float = 60.0) -> None:
        with self._lock:
            thread = self._publish_thread
            self._publish_pending = None
        if thread is not None and thread.is_alive():
            thread.join(timeout=timeout)

    def _weights(self, req):
        """``GET /weights[?since=N]`` — the live reference weights as one
        binary dataplane payload (docs/api.md wire conventions): the delta
        against ``since`` when the caller is exactly one version behind, a
        full snapshot otherwise, 204 when current. Replaces JSON-of-floats
        payload round-trips on the PS serving seam."""
        from ..api.errors import KubeMLError
        from ..engine import dataplane
        from ..utils.httpd import Response

        wire = self._weights_wire
        if wire is None:
            raise KubeMLError(
                f"job {self.job_id} has published no weights yet", 404)
        since = req.arg("since")
        try:
            since = int(since) if since is not None else None
        except ValueError:
            raise KubeMLError(f"invalid since={since!r}", 400)
        got = wire.get(since)
        if got is None:
            raise KubeMLError(
                f"job {self.job_id} has published no weights yet", 404)
        payload, version = got
        headers = {dataplane.VERSION_HEADER: str(version)}
        if payload == "current":
            return Response(b"", status=204, headers=headers,
                            content_type=dataplane.CONTENT_TYPE)
        return Response(payload, content_type=dataplane.CONTENT_TYPE,
                        headers=headers)

    # --- routes ---

    def _start(self, req):
        from ..api.errors import KubeMLError
        from ..api.types import TrainTask
        from ..functions.registry import FunctionRegistry
        from ..storage.checkpoint import CheckpointStore
        from ..storage.history import HistoryStore
        from ..storage.store import ShardStore
        with self._lock:
            if self.job is not None:
                raise KubeMLError(f"job {self.job_id} already started", 400)
            task = TrainTask.parse_request(req.json() or {})
            from ..utils import tracing

            self._trace_ctx = (tracing.current_context()
                               or tracing.parse_traceparent(task.trace_parent))
            request = task.parameters
            model = FunctionRegistry(config=self.cfg).load(request.function_name)
            model._set_params(lr=request.lr, batch_size=request.batch_size,
                              epoch=0, k=request.options.k, task="train")
            request.options.default_parallelism = (
                task.state.parallelism or request.options.default_parallelism
            )
            from . import job_class_for
            from .job import TrainJob

            job_cls = job_class_for(request.options)
            extra = {}
            if job_cls is TrainJob and self.cfg.tensor_sockets:
                self._start_tensor_server()
            if job_cls is TrainJob:
                # always publish epoch weights: even without the native
                # socket, the HTTP /weights seam serves the delta-encoded
                # binary payload the PS pulls (engine/dataplane.py) — the
                # JSON /infer round-trip is the last resort, not the plan
                extra["on_epoch_weights"] = self._publish_weights
            self.job = job_cls(
                self.job_id, request, model,
                store=ShardStore(config=self.cfg),
                history_store=HistoryStore(config=self.cfg),
                checkpoint_store=CheckpointStore(config=self.cfg),
                on_epoch_end=self._epoch_end,
                on_metrics=self._push_metrics,
                **extra,
            )
            self.thread = threading.Thread(target=self._run, name=f"job-{self.job_id}",
                                           daemon=True)
            self.status = "running"
            self.thread.start()
        return {}

    def _run(self) -> None:
        # stall auto-recycle (VERDICT r4 weak-7): a user step wedged inside
        # a traced program in THIS runner may hold the accelerator while the
        # PS's timeout frees the slot — abandoning the thread would leak the
        # device. The runner self-terminates instead (exit 74): process
        # teardown releases the accelerator client, the PS's runner-death
        # monitor marks the job failed and frees the slot, and the next job
        # gets a clean device in a fresh runner.
        from ..utils import tracing
        from ..utils.watchdog import arm_stall_watchdog

        import time as _time

        self.job.heartbeat = _time.time()
        guard = arm_stall_watchdog(
            self.job, self.cfg.function_timeout,
            f"standalone job {self.job_id}",
            recovery=("the accelerator is released with the process, the PS "
                      "marks the job FAILED and frees the slot; it is NOT "
                      "resumed"))
        try:
            with tracing.use_context(self._trace_ctx), \
                    tracing.bind_task(self.job_id):
                self.job.train()
            if getattr(self.job, "preempted", False):
                self.status = "preempted"
            else:
                self.status = ("stopped" if self.job.stop_event.is_set()
                               else "finished")
        except Exception as e:
            self.status = "failed"
            self.exit_error = str(e)
            log.error("job %s failed: %s", self.job_id, e)
        finally:
            guard.set()
            self._notify_ps_finished()
            # deliver this process's spans to the PS span collector BEFORE
            # signaling done — the parent may reap us right after
            tracing.post_task_spans(self.cfg.ps_url, self.job_id)
            self.done.set()

    def _update(self, req):
        body = req.json() or {}
        with self._lock:
            box = self._update_box
        if box is None:
            log.warning("job %s: update with no pending epoch-end request", self.job_id)
            return {}
        box[1] = int(body["parallelism"])
        box[0].set()
        return {}

    def request_stop(self) -> None:
        """Cooperative stop: flag the job AND unblock a pending epoch-end wait
        (used by the /stop route and the SIGTERM handler alike)."""
        if self.job is not None:
            self.job.stop()
        with self._lock:
            if self._update_box is not None:
                self._update_box[0].set()

    def _stop(self, req):
        from ..api.errors import JobNotFoundError

        if self.job is None:
            raise JobNotFoundError(self.job_id)
        self.request_stop()
        return {}

    def _preempt(self, req):
        """``POST /preempt`` — checkpoint-and-yield: the job exits at the
        next round boundary, writes a resume checkpoint, and reports the
        ``preempted`` terminal status to the PS (which keeps the journal
        entry so the scheduler can requeue it with resume=True). Idempotent:
        a redelivered preempt on an already-yielding job is a no-op."""
        from ..api.errors import JobNotFoundError

        if self.job is None:
            raise JobNotFoundError(self.job_id)
        self.job.preempt()
        with self._lock:
            if self._update_box is not None:
                self._update_box[0].set()  # unblock a pending epoch-end wait
        return {"status": "preempting"}

    def _infer(self, req):
        import numpy as np

        from ..api.errors import KubeMLError

        if self.job is None:
            raise KubeMLError(f"job {self.job_id} not started", 503)
        body = req.json() or {}
        return {"predictions": np.asarray(self.job.infer(np.asarray(body["data"]))).tolist()}

    def _generate(self, req):
        from ..api.errors import KubeMLError
        from ..api.types import GenerateRequest

        if self.job is None:
            raise KubeMLError(f"job {self.job_id} not started", 503)
        if not hasattr(self.job, "generate"):
            raise KubeMLError(
                f"job {self.job_id}'s engine does not serve generation", 400)
        return self.job.generate(GenerateRequest.parse_request(req.json() or {}))

    def _state(self, req):
        epochs = len(self.job.history.train_loss) if self.job is not None else 0
        return {"job_id": self.job_id, "status": self.status, "epochs": epochs,
                "error": self.exit_error}

    # --- control-plane callbacks ---

    def _epoch_end(self, state) -> int:
        """Reference loop shape: job -> scheduler /job; answer arrives on /update
        (via PS). Timeout keeps a dead scheduler from wedging training. The
        epoch-end POST is idempotency-keyed so a retried delivery cannot
        double-enqueue the same re-evaluation."""
        from ..api.types import TrainTask
        from ..utils import traced_http as requests
        from ..utils import tracing

        box = [threading.Event(), 0]
        with self._lock:
            self._update_box = box
        ctx = tracing.current_context() or self._trace_ctx
        task = TrainTask(job_id=self.job_id, parameters=self.job.request, state=state,
                         trace_parent=ctx.traceparent() if ctx else "")
        try:
            requests.post(f"{self.cfg.scheduler_url}/job", json=task.to_dict(),
                          timeout=requests.timeouts(10),
                          idempotency_key=True)
        except requests.RequestException as e:
            log.warning("job %s: scheduler unreachable (%s); keeping parallelism",
                        self.job_id, e)
            return state.parallelism
        try:
            if not box[0].wait(self.cfg.update_timeout):
                log.warning(
                    "job %s: scheduler at %s answered no parallelism update "
                    "within %.0fs (KUBEML_UPDATE_TIMEOUT); keeping "
                    "parallelism", self.job_id, self.cfg.scheduler_url,
                    self.cfg.update_timeout)
                return state.parallelism
            if self.job.stop_event.is_set():
                return state.parallelism
            return box[1] or state.parallelism
        finally:
            with self._lock:
                if self._update_box is box:
                    self._update_box = None  # late answers hit the warning path

    def _push_metrics(self, update) -> None:
        from ..utils import profiler
        from ..utils import traced_http as requests

        snap = profiler.counters_snapshot()["dataplane"]
        phases = {}
        for phase, agg in snap.items():
            prev = self._dp_cut.get(phase, {})
            delta = {k: max(agg[k] - prev.get(k, 0), 0)
                     for k in ("bytes", "seconds", "events")}
            if any(delta.values()):
                phases[phase] = delta
        if phases:
            self._dp_seq += 1
            self._dp_unacked.append({"seq": self._dp_seq, "phases": phases})
            del self._dp_unacked[:-64]  # PS gone for 64 epochs: shed oldest
            self._dp_cut = {p: dict(a) for p, a in snap.items()}
        update.dataplane = list(self._dp_unacked)
        try:
            r = requests.post(f"{self.cfg.ps_url}/metrics/{self.job_id}",
                              json=update.to_dict(),
                              timeout=requests.timeouts(5),
                              idempotency_key=True)
        except requests.RequestException:
            log.debug("job %s: metrics push failed (PS down?)", self.job_id)
        else:
            # only a 2xx is an ack: traced_http RETURNS retryable-status
            # responses (429 overload, 504 deadline, chaos 500) instead of
            # raising, and a batch cleared on one of those vanished forever
            if r.status_code < 300:
                self._dp_unacked.clear()

    def _notify_ps_finished(self) -> None:
        from ..utils import traced_http as requests

        # keyed: the PS pops the job record on first delivery, so a retried
        # finish callback must replay, not 404 (the raced-runner dedup the
        # PS already needed, now explicit on the wire)
        try:
            requests.post(
                f"{self.cfg.ps_url}/finish/{self.job_id}",
                json={"error": self.exit_error, "status": self.status},
                timeout=requests.timeouts(10),
                idempotency_key=True,
            )
        except requests.RequestException as e:
            log.warning("job %s: PS finish notification failed: %s", self.job_id, e)

    # --- lifecycle ---

    def start(self) -> "JobRunner":
        self.service.start()
        return self

    def stop(self) -> None:
        self.service.stop()
        # the publish worker writes into the tensor store at epoch ends:
        # freeing the native handle under it would be a use-after-free, so
        # detach the store reference FIRST (the publisher checks it), then
        # quiesce the TRAINING thread (it is what enqueues publishes — a
        # live one could respawn the worker right after a join), then the
        # publish worker, and only then free the store
        store, self._tensor_store = self._tensor_store, None
        if self.thread is not None and self.thread.is_alive():
            if self.job is not None:
                self.job.stop()
            self.thread.join(timeout=60.0)
        self._join_publisher()
        if self._tensor_server is not None:
            self._tensor_server.stop()
            self._tensor_server = None
        if store is not None:
            store.close()
        try:
            self.cfg.job_socket_path(self.job_id).unlink(missing_ok=True)
        except OSError:
            pass

    @property
    def url(self) -> str:
        return self.service.url


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="kubeml-tpu standalone job runner")
    parser.add_argument("--job-id", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--linger", type=float, default=5.0,
                        help="seconds to keep serving after the job finishes")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s job-{args.job_id} %(name)s %(levelname)s "
               f"[trace=%(trace_id)s task=%(task_id)s] %(message)s",
    )
    from ..utils import tracing

    # this process IS the worker pod: its spans label as "worker" in the
    # merged trace, its log lines carry the bound trace/task ids
    tracing.get_tracer().service = "worker"
    tracing.add_log_context()
    from ..api.config import get_config

    cfg = get_config()
    # per-job log file (the reference streams per-job POD logs via
    # `kubectl logs job-<id>`, cmd/log.go:28-66; here the runner process IS
    # the pod, so it writes logs/job-<id>.log and `kubeml logs --id` reads it)
    try:
        log_dir = cfg.data_root / "logs"
        log_dir.mkdir(parents=True, exist_ok=True)
        handler = logging.FileHandler(log_dir / f"job-{args.job_id}.log")
        handler.setFormatter(logging.Formatter(
            f"%(asctime)s job-{args.job_id} %(name)s %(levelname)s %(message)s"
        ))
        logging.getLogger().addHandler(handler)
    except OSError as e:
        log.warning("per-job log file unavailable: %s", e)

    # fresh process: the persistent XLA cache turns the cold jit into a read
    from ..api.config import enable_compilation_cache

    enable_compilation_cache()
    runner = JobRunner(args.job_id, port=args.port).start()
    # the parent reads this line to learn the bound port (job_pod readiness)
    print(f"LISTENING {runner.service.port}", flush=True)
    import signal
    import time

    # the PS terminates runners with SIGTERM on cluster shutdown: request a
    # cooperative job stop — the job thread finishes its round, flushes
    # history/checkpoints in its finally, and sets `done` itself; only a
    # runner that never received /start exits immediately
    def _on_term(*_):
        if runner.job is not None:
            runner.request_stop()  # also unblocks a pending epoch-end wait
        else:
            runner.done.set()

    signal.signal(signal.SIGTERM, _on_term)
    try:
        # serve until the job completes (plus a linger for late /state reads);
        # a runner that never receives /start waits for the parent to kill it
        runner.done.wait()
        time.sleep(args.linger)
    except KeyboardInterrupt:
        if runner.job is not None:
            runner.job.stop()
    finally:
        runner.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
