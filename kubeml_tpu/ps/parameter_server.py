"""Parameter Server — job lifecycle manager.

The reference PS keeps an index of live train tasks, starts each one as a
dedicated job pod (or an in-process goroutine in threaded mode), routes
scheduler parallelism updates to the right job, and cleans up on finish
(reference: ml/pkg/ps/parameter_server.go:45-105, api.go:72-327,
job_pod.go:96-217). "Parameter server" is in name only there as here: weights
are exchanged by averaging, not gradient pushes (SURVEY §2.4).

TPU-native shape: jobs run as in-process threads next to the device mesh — the
generalization of the reference's threaded mode (ps/api.go:211-217), which is
the right default when the "cluster" is one TPU VM / slice. The epoch-end
elastic round-trip (job -> scheduler -> PS -> job) is preserved: the job thread
blocks in ``on_epoch_end`` until :meth:`update_task` delivers the scheduler's
answer, exactly like the reference job's ``schedulerCh``
(ml/pkg/train/job.go:196-215, ps/api.go:72-119).
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..api.config import Config, get_config
from ..api.errors import JobNotFoundError, KubeMLError
from ..api.types import JobState, JobStateEnum, MetricUpdate, TrainTask, generate_timeout
from ..engine.job import TrainJob
from ..functions.registry import FunctionRegistry
from ..storage.checkpoint import FINAL_TAG, CheckpointStore
from ..storage.history import HistoryStore
from ..storage.store import ShardStore
from ..utils import tracing
from ..utils.errorhook import report_error
from .metrics import MetricsRegistry
from .traces import TraceStore

log = logging.getLogger("kubeml.ps")

# finished-job serving cache: full weight pytrees are big, keep only a few
SERVING_CACHE_SIZE = 4

# resident continuous-batching decoders: each holds a slots x max_len KV slab
# in HBM, so keep fewer than the weight cache
DECODER_CACHE_SIZE = 2

# Seconds the job thread waits for the scheduler's parallelism answer before
# keeping its current parallelism (the reference blocks forever on schedulerCh;
# a timeout keeps a dead scheduler from wedging training). Config-driven:
# Config.update_timeout / KUBEML_UPDATE_TIMEOUT; this constant is the
# documented default only.
UPDATE_TIMEOUT = 30.0


def _float_types(variables) -> str:
    """The floating types a tree's leaves are held in, as a span shows them
    ("float32", "bfloat16,float32")."""
    import jax

    from ..serving.quant import is_floating

    return ",".join(sorted({str(l.dtype) for l in jax.tree.leaves(variables)
                            if is_floating(l)}))


def _decodes_tokens(module) -> bool:
    """Whether ``module`` is a token-in LM with a decode path: it has a
    ``max_len`` and its call takes what the engines pass it: ``decode``,
    ``positions`` and an admission's ``head_positions``."""
    if module is None or getattr(module, "max_len", None) is None:
        return False
    import inspect

    try:
        params = inspect.signature(module.__call__).parameters
    except (TypeError, ValueError):
        return False
    return all(name in params
               for name in ("decode", "positions", "head_positions"))


def _tree_bytes(variables) -> int:
    import jax

    return sum(int(getattr(l, "nbytes", 0))
               for l in jax.tree.leaves(variables))


@dataclass
class _UpdateBox:
    """One pending epoch-end answer (the job's schedulerCh)."""

    event: threading.Event = field(default_factory=threading.Event)
    parallelism: int = 0


@dataclass
class _JobRecord:
    task: TrainTask
    job: Optional[TrainJob]  # None while starting, and always for standalone jobs
    thread: Optional[threading.Thread]
    update_box: Optional[_UpdateBox] = None
    # standalone mode (reference: dedicated job pod, ps/job_pod.go)
    proc: Optional[object] = None  # subprocess.Popen
    url: Optional[str] = None  # the runner's HTTP endpoint
    # a job killed by a TRANSIENT fault (accelerator RPC, a peer process
    # dying) keeps its journal entry so the next supervised boot resubmits
    # it with resume=True — clearing it would turn crash recovery into a no-op
    keep_journal: bool = False
    # wall time of the first preempt request (None = never preempted): the
    # yield-latency clock, and the marker the grace watchdog checks
    preempt_t0: Optional[float] = None


class ParameterServer:
    def __init__(
        self,
        registry: Optional[FunctionRegistry] = None,
        store: Optional[ShardStore] = None,
        history_store: Optional[HistoryStore] = None,
        metrics: Optional[MetricsRegistry] = None,
        config: Optional[Config] = None,
        devices=None,
        dist=None,
    ):
        self.cfg = config or get_config()
        self.registry = registry or FunctionRegistry(config=self.cfg)
        self.store = store or ShardStore(config=self.cfg)
        self.history_store = history_store or HistoryStore(config=self.cfg)
        self.metrics = metrics or MetricsRegistry()
        # serving telemetry: /metrics renders each resident decoder's
        # counters/latency quantiles next to the training gauges
        self.metrics.set_serving_source(self._serving_telemetry)
        # embedded time-series store: the sampler polls the registry's
        # serving/scheduler signals into bounded rings (GET /metrics/history;
        # the SLO engine and `kubeml top` read windowed rates from it
        # instead of growing their own). The interval thread starts with
        # start_telemetry() (LocalCluster.start / PSAPI.start) — bare PS
        # objects in tests drive ticks manually via self.sampler.tick().
        from ..utils.timeseries import Sampler, TimeSeriesStore

        self.tsdb = TimeSeriesStore(capacity=self.cfg.tsdb_samples,
                                    max_series=self.cfg.tsdb_series)
        # some gauges wear counter names (_total): running_total is the
        # reference's name for a decremented gauge, slots_total a constant
        # capacity — marked so /metrics/history stats render quantiles,
        # not a bogus counter rate
        from .metrics import RUNNING, SERVING_GAUGES

        self.tsdb.mark_gauge(RUNNING)
        for metric in SERVING_GAUGES:
            if metric.endswith("_total"):
                self.tsdb.mark_gauge(metric)
        self.sampler = Sampler(self.tsdb, interval=self.cfg.tsdb_interval)
        self.sampler.add_collector(self._collect_series)
        # declarative SLO engine (ps/slo.py): objectives from KUBEML_SLOS,
        # multi-window burn rates over the tsdb, alert state machine firing
        # through the errorhook webhook. Evaluated on every sampler tick.
        from .slo import SLOEngine, parse_objectives

        self.slo = SLOEngine(
            self.tsdb, parse_objectives(self.cfg.slo_spec),
            fast_window=self.cfg.slo_fast_window,
            slow_window=self.cfg.slo_slow_window,
            for_s=self.cfg.slo_for,
            resolve_for_s=self.cfg.slo_resolve_for)
        self.sampler.add_tick_hook(self.slo.evaluate)
        self.metrics.set_slo_source(self.slo.metrics_source)
        # span collector: job runners/workers POST finished spans here, the
        # controller's /tasks/{id}/trace merges them with local spans
        self.traces = TraceStore()
        self.devices = devices
        self.scheduler = None  # bound after construction (circular dep)
        self._jobs: Dict[str, _JobRecord] = {}
        self._monitor: Optional[threading.Thread] = None  # standalone liveness watch
        self._serving_cache: Dict[str, tuple] = {}  # (model, vars, ckpt mtime)
        # seconds a cached tree's restore and hold took, until the decoder
        # built on it takes them into its stats (_get_decoder)
        self._serving_startup: Dict[str, Dict[str, float]] = {}
        # leaves of a cached tree that the hold's rule narrowed (_held), for
        # the telemetry of every decoder built on it
        self._serving_narrowed: Dict[str, int] = {}
        # (model, vars, epoch version, native.weights.FetchCache) — the
        # FetchCache makes per-epoch refreshes pull only the leaves whose
        # manifest version moved (delta fetch)
        self._socket_cache: Dict[str, tuple] = {}
        # HTTP weight seam (engine/dataplane): (model, vars, DeltaDecoder)
        # per live standalone job — the decoder holds the synced tree the
        # runner's delta payloads chain against. The decoder is STATEFUL, so
        # pull+decode serializes on a per-model lock (requests arrive on
        # ThreadingHTTPServer threads; two threads decoding the same delta
        # into one decoder would double-apply it)
        self._wire_cache: Dict[str, tuple] = {}
        self._wire_locks: Dict[str, threading.Lock] = {}
        self._decoders: Dict[str, tuple] = {}  # (BatchingDecoder, ckpt mtime)
        # requests replayed from KUBEML_SNAP_DIR at boot (ISSUE 20): each
        # row is {"model", "request_id", "file", "entry", "decoder"} — the
        # /serving/restored route reads completion state off the entry
        self._restored: List[dict] = []
        self._ckpt_store = CheckpointStore(config=self.cfg)
        from .journal import JobJournal

        # crash-recovery journal: accepted jobs persist until they finish so
        # a supervised restart resubmits them with resume=True (deploy docs)
        self._journal = JobJournal(config=self.cfg)
        self._lock = threading.RLock()
        # multi-host: the PS runs on process 0 and announces each job to the
        # follower processes over the host channel; jobs serialize on
        # _dist_lock because all processes must issue collectives in one
        # global order (see engine.follower module docstring)
        self.dist = dist
        self._dist_lock = threading.Lock()
        self._dist_run = 0  # per-announcement nonce (ack keys must be unique)

    def bind_scheduler(self, scheduler) -> None:
        self.scheduler = scheduler

    # --- task lifecycle (reference routes ps/api.go:335-345) ---

    def start_task(self, task: TrainTask) -> None:
        """`/start`: spin up the job (reference api.go:139-222) — as an
        in-process thread (reference threaded mode, ps/api.go:211-217) or, with
        ``standalone_jobs``, a dedicated subprocess speaking the job HTTP API
        (reference standalone mode, job_pod.go:96-217).

        The index slot is reserved atomically before the (slow) model load so
        two concurrent starts of the same job id can't both win; a failed start
        leaves a FAILED history record so clients polling the job don't see it
        silently vanish."""
        dist = self.dist if (self.dist is not None and self.dist.size > 1) else None
        if self.cfg.standalone_jobs:
            if dist is not None:
                raise KubeMLError(
                    "standalone job runners are a single-host deployment mode; "
                    "multi-host training runs jobs threaded on every process", 400
                )
            self._start_standalone(task)
            return
        req = task.parameters
        placeholder = self._reserve_slot(task)
        try:
            model = self.registry.load(req.function_name)
            model._set_params(
                lr=req.lr, batch_size=req.batch_size, epoch=0, k=req.options.k, task="train"
            )
            req.options.default_parallelism = (
                task.state.parallelism or req.options.default_parallelism
            )
            from ..engine import job_class_for

            job = job_class_for(req.options)(
                task.job_id,
                req,
                model,
                store=self.store,
                history_store=self.history_store,
                checkpoint_store=self._ckpt_store,
                on_epoch_end=lambda state, jid=task.job_id: self._epoch_end(jid, state),
                on_metrics=self.metrics.update,
                devices=self.devices,
                dist=dist,
            )
        except Exception as e:
            self._fail_start(task, e)
            raise
        runner = self._run_job if dist is None else self._run_job_dist
        # the job thread is a new root otherwise: hand it the submitting
        # request's trace context (bound by the scheduler loop / HTTP server
        # on THIS thread, or carried on the task) so job.* spans stitch
        ctx = tracing.current_context() or tracing.parse_traceparent(
            task.trace_parent)
        thread = threading.Thread(
            target=self._run_job_traced,
            args=(runner, ctx, task, job, placeholder),
            name=f"job-{task.job_id}", daemon=True
        )
        placeholder.job = job
        placeholder.thread = thread
        task.status = JobStateEnum.RUNNING
        self.metrics.task_started("train")
        thread.start()
        self._ensure_monitor()  # heartbeat watchdog (function guardrails)

    def _reserve_slot(self, task: TrainTask) -> _JobRecord:
        """Reserve the job-index slot atomically (duplicate start -> 400) and
        invalidate any cached finished-model weights for a reused id."""
        placeholder = _JobRecord(task=task, job=None, thread=None)
        with self._lock:
            if task.job_id in self._jobs:
                raise KubeMLError(f"job {task.job_id} already exists", 400)
            self._jobs[task.job_id] = placeholder
            self._serving_cache.pop(task.job_id, None)
            self._socket_cache.pop(task.job_id, None)
            self._wire_cache.pop(task.job_id, None)
        try:
            self._journal.record(task.job_id, task.parameters)
        except Exception:
            log.exception("journaling job %s failed (non-fatal)", task.job_id)
        return placeholder

    def _ensure_failure_history(self, job_id: str, request, error: str,
                                sync_report: bool = False) -> None:
        """Guarantee a History record exists for a dead job (completion pollers
        key off it); keeps any record the job itself managed to save. Also
        fires the optional error webhook (utils.errorhook — the reference's
        Sentry-hook counterpart, no-op unless KUBEML_ERROR_WEBHOOK is set);
        ``sync_report`` delivers it before returning — the stall watchdog
        os._exits right after this, which would kill an async thread."""
        report_error("job-failure", error, wait=sync_report, job_id=job_id)
        try:
            self.history_store.get(job_id)
        except Exception:
            from ..api.types import History

            self.history_store.save(History(
                id=job_id, task={"request": request.to_dict(), "error": error}
            ))

    def _fail_start(self, task: TrainTask, error: Exception) -> None:
        """Failed-start bookkeeping: FAILED status, slot freed, error history
        persisted so pollers see the outcome. Saves UNCONDITIONALLY — a reused
        job id may carry a stale success history from its previous run, and
        this submission's failure must not hide behind it."""
        from ..api.types import History

        task.status = JobStateEnum.FAILED
        with self._lock:
            self._jobs.pop(task.job_id, None)
        try:
            self._journal.clear(task.job_id)
        except Exception:
            pass
        report_error("job-start-failure", str(error), job_id=task.job_id)
        self.history_store.save(History(
            id=task.job_id,
            task={"request": task.parameters.to_dict(), "error": str(error)},
        ))

    # --- standalone mode (reference: ps/job_pod.go + train/client) ---

    def _start_standalone(self, task: TrainTask) -> None:
        import subprocess
        import sys

        from ..utils import traced_http as requests

        placeholder = self._reserve_slot(task)
        try:
            import jax

            if jax.default_backend() == "tpu":
                # measured on a v5e host: the runner binds its port, then
                # every /start dies in its first device call ("Unable to
                # initialize backend 'tpu': ... libtpu multi-process
                # lockfile") and the hand-over gives up after ten tries
                raise KubeMLError(
                    "STANDALONE_JOBS cannot run on a TPU host: a chip "
                    "belongs to one process, and this one (scheduler, PS, "
                    "serving) already holds it, so the job's runner process "
                    "could never open the device. Unset STANDALONE_JOBS to "
                    "train in-process (the default)", 400)
            env = dict(
                __import__("os").environ,
                KUBEML_DATA_ROOT=str(self.cfg.data_root),
                KUBEML_SCHEDULER_PORT=str(self.cfg.scheduler_port),
                KUBEML_PS_PORT=str(self.cfg.ps_port),
            )
            proc = subprocess.Popen(
                [sys.executable, "-m", "kubeml_tpu.engine.job_runner",
                 "--job-id", task.job_id, "--port", "0"],
                stdout=subprocess.PIPE, text=True, env=env,
            )
            # the runner prints its bound port first (pod-readiness parity,
            # job_pod.go:18-63); a crashed child yields EOF -> error out
            line = proc.stdout.readline().strip()
            if not line.startswith("LISTENING "):
                proc.kill()
                raise KubeMLError(
                    f"job runner for {task.job_id} failed to start: {line!r}", 500
                )
            url = f"http://{self.cfg.host}:{int(line.split()[1])}"
            # user training code prints to stdout inside the runner: drain the
            # pipe on a thread (into our log) or the child blocks once it fills
            threading.Thread(
                target=self._drain_runner_output, args=(task.job_id, proc.stdout),
                name=f"job-{task.job_id}-stdout", daemon=True,
            ).start()
            # publish proc/url BEFORE handing the task over: a job that fails
            # within milliseconds posts /finish immediately, and that callback
            # must find a routable record
            with self._lock:
                placeholder.proc = proc
                placeholder.url = url
            # hand the task over with retries (reference api.go:190-207);
            # the idempotency key makes redelivery safe — a /start whose
            # response was lost replays from the runner's record instead of
            # bouncing off "already started"
            import uuid

            last = None
            start_key = uuid.uuid4().hex
            for attempt in range(10):
                try:
                    # retryable=False: THIS loop is the retry schedule
                    # (reference-parity backoff) — layering the policy-stack
                    # retries under it would compound to 30 wire attempts.
                    # use_breaker=False: connection-refused during a normal
                    # runner boot must not open a breaker that then eats the
                    # later attempts the boot needs (the dest is this job's
                    # fresh ephemeral port — nothing to protect). The shared
                    # key still makes every redelivery replay-safe.
                    r = requests.post(f"{url}/start", json=task.to_dict(),
                                      timeout=requests.timeouts(30),
                                      idempotency_key=start_key,
                                      retryable=False, use_breaker=False)
                    if r.status_code < 400:
                        break
                    last = r.text
                except requests.RequestException as e:
                    last = str(e)
                time.sleep(0.2 * (attempt + 1))
            else:
                proc.kill()
                raise KubeMLError(
                    f"could not start job {task.job_id} on its runner: {last}", 500
                )
        except Exception as e:
            self._fail_start(task, e)
            raise
        task.status = JobStateEnum.RUNNING
        self.metrics.task_started("train")
        self._ensure_monitor()
        log.info("standalone job %s running at %s (pid %d)", task.job_id, url, proc.pid)

    def _fail_dead_record(self, job_id: str, record: _JobRecord, error: str) -> bool:
        """Shared teardown for a job whose runner/thread died without finishing:
        stale-record guard FIRST (a resubmitted live job must never get a
        spurious failure history), then history, then the guarded finish."""
        with self._lock:
            if self._jobs.get(job_id) is not record:
                return False  # already finished, or the id belongs to a new job
        if record.preempt_t0 is not None:
            # a preempted runner dying is the expected end of a hard yield
            # (or a crash mid-yield — equivalent: the atomic checkpoint and
            # the kept journal entry make it fully resumable), not a failure
            # to page on: PREEMPTED status routes it back into the requeue
            # path instead of the error webhook
            log.warning("preempted job %s terminated before a clean yield "
                        "(%s); resuming from its newest checkpoint", job_id,
                        error)
            record.task.status = JobStateEnum.PREEMPTED
            return self._finish(job_id, expect=record)
        record.task.status = JobStateEnum.FAILED
        self._ensure_failure_history(job_id, record.task.parameters, error)
        return self._finish(job_id, expect=record)

    def _handle_runner_death(self, job_id: str, record: _JobRecord) -> bool:
        """Cleanup after a runner died without its /finish callback (crash,
        OOM-kill, or the runner's own stall watchdog recycling a wedged
        device — exit 74). Returns whether this call performed the
        teardown."""
        from ..utils.watchdog import STALL_EXIT_CODE

        rc = record.proc.returncode
        if rc == STALL_EXIT_CODE:
            msg = (f"job runner stalled (no progress within "
                   f"KUBEML_FUNCTION_TIMEOUT) and recycled itself (exit "
                   f"{rc}) — the accelerator was released with the process")
        else:
            msg = f"job runner exited with code {rc}"
        handled = self._fail_dead_record(job_id, record, msg)
        if handled:
            log.error("standalone job %s runner exited (code %s) without "
                      "reporting; marked failed", job_id, record.proc.returncode)
        return handled

    def _ensure_monitor(self) -> None:
        """A liveness monitor for every job record: standalone runners (the
        reference's pod watch — a process that died without reporting is
        cleaned up) AND threaded jobs (the function-guardrail heartbeat: a
        job whose user code hangs inside a traced program goes stale and is
        failed, its slot freed — the reference gets this from Fission's
        1000s execution timeout killing the pod)."""
        with self._lock:
            if self._monitor is not None and self._monitor.is_alive():
                return
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="ps-job-monitor", daemon=True
            )
            self._monitor.start()

    def _monitor_loop(self) -> None:
        while True:
            time.sleep(2.0)
            with self._lock:
                records = list(self._jobs.items())
            if not records:
                # nothing to watch: let the thread retire (a new job re-arms
                # it via _ensure_monitor)
                with self._lock:
                    self._monitor = None
                return
            timeout = self.cfg.function_timeout
            for jid, record in records:
                if record.proc is not None:
                    if record.proc.poll() is not None:
                        self._handle_runner_death(jid, record)
                    continue
                job = record.job
                if (timeout and timeout > 0 and job is not None
                        and record.thread is not None
                        and record.thread.is_alive()):
                    dist = getattr(job, "dist", None)
                    if dist is not None and dist.size > 1:
                        # multi-host jobs serialize on the dist lock (a
                        # queued job's heartbeat legitimately goes stale) and
                        # an abandoned leader thread would poison that lock
                        # anyway — their stall guardrail is the per-process
                        # watchdog armed in _run_job_dist/run_follower
                        # (utils.watchdog.arm_stall_watchdog: a wedged rank
                        # self-terminates, the group fails fast, supervision
                        # restarts + journal resumes), plus the start-ack
                        # and broadcast timeouts
                        continue
                    stale = time.time() - getattr(job, "heartbeat", time.time())
                    # double the allowance while the first step's XLA compile
                    # runs (ADVICE r4: a cold compile can legitimately exceed
                    # the timeout; scaling with the knob keeps short test
                    # timeouts meaningful); engines clear the flag after the
                    # first round/step lands
                    cold = getattr(job, "heartbeat_cold", False)
                    allowed = timeout * (2.0 if cold else 1.0)
                    if stale > allowed:
                        self._handle_wedged_job(jid, record, stale, timeout,
                                                allowed)

    def _handle_wedged_job(self, job_id: str, record: _JobRecord,
                           stale: float, timeout: float,
                           allowed: float) -> None:
        """Fail a threaded job whose user code stopped making progress: the
        wedged thread is ABANDONED (Python cannot kill it; it leaks until
        process exit — the documented cost of in-process functions), the
        task goes FAILED, the slot frees, the scheduler is notified. The
        platform completes degraded instead of wedging (VERDICT r3 next-5)."""
        try:
            record.job.stop()  # cooperative; a truly wedged thread ignores it
        except Exception:
            pass
        extra = (f", cold-start allowance {allowed:g}s"
                 if allowed != timeout else "")
        handled = self._fail_dead_record(
            job_id, record,
            f"job made no progress for {stale:.0f}s (function execution "
            f"timeout {timeout:g}s; KUBEML_FUNCTION_TIMEOUT{extra}) — user "
            f"code abandoned")
        if handled:
            log.error("job %s: heartbeat stale for %.0fs; thread abandoned "
                      "and job marked failed", job_id, stale)

    @staticmethod
    def _drain_runner_output(job_id: str, stream) -> None:
        try:
            for line in stream:
                log.info("[job %s] %s", job_id, line.rstrip())
        except (ValueError, OSError):
            pass  # stream closed during reap

    def finish_standalone(self, job_id: str, status: str = "", error: Optional[str] = None) -> None:
        """`/finish/{jobId}` from the job runner (reference ps/api.go:266-327)."""
        with self._lock:
            record = self._jobs.get(job_id)
        if record is None or record.proc is None:
            raise JobNotFoundError(job_id)
        record.task.status = {
            "finished": JobStateEnum.FINISHED,
            "stopped": JobStateEnum.STOPPED,
            "failed": JobStateEnum.FAILED,
            "preempted": JobStateEnum.PREEMPTED,
        }.get(status, JobStateEnum.FINISHED if not error else JobStateEnum.FAILED)
        if record.task.status == JobStateEnum.PREEMPTED:
            # the runner may have been preempted directly (its /preempt route
            # is reachable without the PS) — the journal must survive anyway
            record.keep_journal = True
        self._finish(job_id)
        self._reap(record)

    def _reap(self, record: _JobRecord) -> None:
        def reap():
            try:
                record.proc.wait(timeout=30)
            except Exception:
                record.proc.kill()

        threading.Thread(target=reap, name="job-reaper", daemon=True).start()

    def prune_tasks(self) -> int:
        """`kubeml task prune` (reference cmd/task.go:62-117 deletes leaked job
        pods/services): clean up records whose job thread or runner process is
        dead but which never finished properly. Returns the count pruned."""
        with self._lock:
            candidates = list(self._jobs.items())
        pruned = 0
        for job_id, record in candidates:
            if record.proc is not None and record.proc.poll() is not None:
                if self._handle_runner_death(job_id, record):
                    pruned += 1
                continue
            # thread.ident is None while assigned-but-not-started (start_task
            # mid-flight) — that is a live job being born, not a leak
            if (record.proc is None and record.thread is not None
                    and record.thread.ident is not None
                    and not record.thread.is_alive()):
                if self._fail_dead_record(job_id, record,
                                          "job thread died without finishing"):
                    pruned += 1
        return pruned

    def shutdown_standalone_jobs(self) -> None:
        """Terminate any live job runner processes (cluster stop). Shutdown,
        not user /stop: journals survive for restart-and-resume."""
        with self._lock:
            records = [r for r in self._jobs.values() if r.proc is not None]
        for r in records:
            r.keep_journal = True
            try:
                r.proc.terminate()
            except Exception:
                pass

    def _run_job_dist(self, task: TrainTask, job: TrainJob, record=None) -> None:
        """Multi-host job thread: serialize on the dist lock (all processes
        must see one global collective order), announce the task to the
        follower processes, then run the job — every collective the job issues
        here is mirrored by the followers (engine.follower.run_follower).

        Start handshake: every follower acks that it constructed the job
        BEFORE anyone enters the first jitted program. A follower that can't
        (function or dataset missing on its host) would otherwise leave the
        leader hanging forever in a collective only some processes joined."""
        with self._dist_lock:
            run = self._dist_run
            self._dist_run += 1
            self.dist.broadcast_obj(
                {"cmd": "train", "task": task.to_dict(), "run": run}
            )
            errs = []
            for rank in range(1, self.dist.size):
                ack = self.dist.get(
                    f"kubeml/ack/{run}/{rank}", timeout_s=self.cfg.dist_ack_timeout
                )
                if ack is None:
                    errs.append(f"rank {rank}: no job-start ack (timeout)")
                elif ack != "ok":
                    errs.append(f"rank {rank}: {ack}")
            self.dist.broadcast_obj({"go": not errs})
            if errs:
                err = "follower(s) could not start the job: " + "; ".join(errs)
                log.error("job %s aborted before start: %s", task.job_id, err)
                task.status = JobStateEnum.FAILED
                self._ensure_failure_history(task.job_id, task.parameters, err)
                # expect: an abandoned thread waking here must not tear down
                # a resubmitted job that reused the id (same guard as
                # _run_job's finally)
                self._finish(task.job_id, expect=record)
                return
            # stall guardrail for the DIST job (the heartbeat monitor skips
            # dist jobs — abandoning this thread would poison the dist lock
            # and leave peers inside half-joined collectives): a wedge
            # terminates this process, the coordination service fatals the
            # group, supervision restarts it, the journal resumes the job
            from ..utils.watchdog import arm_stall_watchdog

            def on_stall(reason: str) -> None:
                if record is not None:
                    record.keep_journal = True
                self._ensure_failure_history(task.job_id, task.parameters,
                                             reason, sync_report=True)

            # re-stamp NOW: the heartbeat was set at job construction, and
            # this thread may have queued on the dist lock behind a long job
            # for arbitrarily long — arming against the stale stamp would
            # kill a job seconds after it finally starts
            job.heartbeat = time.time()
            guard = arm_stall_watchdog(
                job, self.cfg.function_timeout,
                f"dist job {task.job_id} (leader)", on_stall=on_stall)
            try:
                self._run_job(task, job, record)
            finally:
                guard.set()

    def stop_running_jobs(self) -> None:
        """Cooperative stop for every threaded job (multi-host shutdown must
        stop the running job FIRST — announce_shutdown waits on the dist lock
        its thread holds).

        This is the SHUTDOWN path, not a user /stop: the stopped jobs keep
        their journal entries so a supervised rolling restart resubmits them
        with resume=True — clearing here would make routine deploy restarts
        lose work that a kill -9 would have recovered."""
        with self._lock:
            records = [r for r in self._jobs.values() if r.job is not None]
        for record in records:
            record.keep_journal = True
            try:
                record.job.stop()
            except Exception:
                log.exception("stopping job failed")

    def announce_shutdown(self) -> None:
        """Release follower processes at cluster shutdown."""
        if self.dist is not None and self.dist.size > 1:
            with self._dist_lock:
                self.dist.broadcast_obj({"cmd": "shutdown"})

    def _run_job_traced(self, runner, ctx, task: TrainTask, job: TrainJob,
                        record) -> None:
        """Job-thread entry: bind the submitter's trace context + the task id
        (log/webhook correlation) and record one PS-side umbrella span for
        the job's whole run, then delegate to the real runner."""
        with tracing.use_context(ctx), tracing.bind_task(task.job_id):
            with tracing.get_tracer().span("ps.job.run", service="ps",
                                           job=task.job_id):
                runner(task, job, record)

    def _run_job(self, task: TrainTask, job: TrainJob, record=None) -> None:
        try:
            job.train()
            if getattr(job, "preempted", False):
                # checkpoint-and-yield: the job parked itself with a resume
                # checkpoint; the journal entry stays so it is requeued
                task.status = JobStateEnum.PREEMPTED
                if record is not None:
                    record.keep_journal = True
            else:
                task.status = (
                    JobStateEnum.STOPPED if job.stop_event.is_set()
                    else JobStateEnum.FINISHED
                )
            if record is not None and task.status == JobStateEnum.FINISHED:
                # a job that completed during shutdown must not be resubmitted
                # on the next boot, even if the shutdown path flagged it
                record.keep_journal = False
        except Exception as e:
            task.status = JobStateEnum.FAILED
            log.error("job %s failed: %s", task.job_id, e)
            # an abandoned thread waking with an exception after the monitor
            # already failed (and reported) this job must not page twice —
            # same staleness guard as _finish's expect
            current = True
            if record is not None:
                with self._lock:
                    current = self._jobs.get(task.job_id) is record
            if current:
                report_error("job-failure", str(e), job_id=task.job_id)
            from ..engine.failures import is_transient_accelerator_error

            if record is not None and is_transient_accelerator_error(e):
                # crash-class failure (accelerator RPC fault, a peer process
                # dying): keep the journal entry so a supervised restart
                # resubmits this job with resume=True
                record.keep_journal = True
        finally:
            # expect guards a thread that was ABANDONED by the heartbeat
            # monitor and wakes later: its slot may now belong to a
            # resubmitted job, which it must not tear down
            self._finish(task.job_id, expect=record)

    def _finish(self, job_id: str, expect: Optional[_JobRecord] = None) -> bool:
        """Job teardown (reference api.go:266-327): clear metrics, notify the
        scheduler, drop the index entry.

        ``expect`` guards against acting on a stale record: when the slot now
        holds a different record (same id resubmitted), nothing is torn down —
        otherwise a late crash-detector would kill the live replacement job
        and double-decrement the running gauge."""
        with self._lock:
            record = self._jobs.get(job_id)
            if record is None or (expect is not None and record is not expect):
                return False
            self._jobs.pop(job_id, None)
            self._socket_cache.pop(job_id, None)  # socket dies with the runner
            self._wire_cache.pop(job_id, None)  # so does the /weights route
            self._wire_locks.pop(job_id, None)
        if not record.keep_journal:
            try:
                self._journal.clear(job_id)
            except Exception:
                log.exception("clearing journal for %s failed (non-fatal)", job_id)
        self.metrics.clear(job_id)
        self.metrics.task_finished("train")
        if record.preempt_t0 is not None:
            # yield latency: preempt request -> slot freed (covers the round
            # drain, the yield checkpoint, and — on escalation — the grace)
            self.metrics.observe_yield(time.time() - record.preempt_t0)
        if self.scheduler is not None:
            try:
                self.scheduler.finish_job(job_id)
            except Exception:
                log.exception("notifying scheduler of %s finish failed", job_id)
            if record.task.status == JobStateEnum.PREEMPTED:
                # hand the parked job back: the preemption controller holds
                # it until pressure clears (or, without one, it requeues
                # immediately — behind whatever outranked it)
                try:
                    self.scheduler.job_preempted(record.task)
                except Exception:
                    log.exception("requeue of preempted job %s failed "
                                  "(journal entry remains for the next boot)",
                                  job_id)
        if record.update_box is not None:
            # unblock a job thread stuck waiting for a scheduler answer
            record.update_box.event.set()
        return True

    # --- elastic round-trip ---

    def _epoch_end(self, job_id: str, state: JobState) -> int:
        """Runs on the job thread: ask the scheduler, wait for update_task."""
        if self.scheduler is None:
            return state.parallelism
        with self._lock:
            record = self._jobs.get(job_id)
            if record is None:
                return state.parallelism
            box = _UpdateBox(parallelism=state.parallelism)
            record.update_box = box
            task = record.task
        task.state = state
        self.scheduler.update_job(task)
        timeout = self.cfg.update_timeout
        if not box.event.wait(timeout):
            log.warning(
                "job %s: scheduler at %s answered no parallelism update "
                "within %.0fs (KUBEML_UPDATE_TIMEOUT); keeping parallelism %d",
                job_id, self.cfg.scheduler_url, timeout, state.parallelism)
            return state.parallelism
        return box.parallelism

    def update_task(self, job_id: str, parallelism: int) -> None:
        """`/update/{jobId}`: scheduler's answer routed to the job (api.go:72-119)
        — in-process box for threaded jobs, HTTP for standalone runners
        (reference train/client/client.go:31-107)."""
        with self._lock:
            record = self._jobs.get(job_id)
        if record is None:
            raise JobNotFoundError(job_id)
        if record.url is not None:
            from ..utils import traced_http as requests

            try:
                requests.post(f"{record.url}/update",
                              json={"parallelism": parallelism},
                              timeout=requests.timeouts(10),
                              idempotency_key=True)
            except requests.RequestException as e:
                log.warning("job %s: update delivery failed: %s", job_id, e)
            return
        box = record.update_box
        if box is None:
            log.warning("job %s: update with no pending epoch-end request", job_id)
            return
        box.parallelism = parallelism
        box.event.set()

    # --- traces (span collection; no reference counterpart) ---

    def post_trace(self, task_id: str, spans: List[dict],
                   counters: Optional[dict] = None,
                   service: str = "") -> None:
        """``POST /traces/{taskId}``: a worker/job-runner process delivers its
        finished spans for a task (utils.tracing.post_task_spans), optionally
        with its data-plane counter snapshot (the `kubeml profile` byte
        budget per process)."""
        self.traces.add(task_id, spans)
        if counters:
            self.traces.add_counters(task_id, service or "worker", counters)

    def get_trace(self, task_id: str) -> dict:
        """The merged span set of a task: spans POSTed by remote processes
        plus this process's own (controller/scheduler/PS server spans and
        threaded-job spans share the global tracer). Deduped by span_id —
        in the all-in-one cluster the same tracer backs every service, and a
        runner that raced a retry may have delivered twice."""
        merged: List[dict] = []
        seen = set()
        for d in self.traces.get(task_id) + tracing.get_tracer().task_dicts(task_id):
            sid = d.get("span_id")
            if sid and sid in seen:
                continue
            if sid:
                seen.add(sid)
            merged.append(d)
        merged.sort(key=lambda d: d.get("start", 0.0))
        trace_ids = sorted({d["trace_id"] for d in merged if d.get("trace_id")})
        # counters: remote processes' snapshots plus this process's own (in
        # the all-in-one cluster the control plane IS the local process)
        counters = self.traces.get_counters(task_id)
        try:
            from ..utils import profiler

            counters.setdefault(tracing.get_tracer().service or "ps",
                                profiler.counters_snapshot())
        except Exception:
            pass
        return {"task_id": task_id, "trace_ids": trace_ids,
                "dropped": self.traces.dropped(task_id), "spans": merged,
                "counters": counters}

    # --- queries / control ---

    def list_tasks(self) -> List[TrainTask]:
        """`/tasks` (reference tasksApi proxies here)."""
        with self._lock:
            return [r.task for r in self._jobs.values()]

    def _resume_epoch(self, job_id: str) -> int:
        """The epoch a resumed job would restart at, from checkpoint METADATA
        only (mirrors engine/resume.select_resume_checkpoint's decision
        without reading any weight arrays — this is a listing, not a load)."""
        try:
            tags = self._ckpt_store.tags(job_id)
            last = self._ckpt_store.latest_epoch(job_id)
            start = 0 if last is None else last + 1
            if FINAL_TAG in tags:
                start = max(start, int(
                    self._ckpt_store.read_meta(job_id, FINAL_TAG).get("epoch", 0)))
            return start
        except Exception:
            return 0

    def jobs_snapshot(self, include_journal: bool = True) -> List[dict]:
        """The PS half of the `kubeml jobs` operator view: live records
        (running/starting/yielding) plus journaled-but-not-live jobs — the
        preempted/interrupted set awaiting requeue — with the epoch resume
        would restart at. ``include_journal=False`` skips the journal scan
        and checkpoint-metadata reads: the preemption controller's victim
        picker polls every tick and only needs the live records."""
        out = []
        with self._lock:
            records = list(self._jobs.items())
        live = set()
        for jid, r in records:
            live.add(jid)
            opts = r.task.parameters.options
            out.append({
                "job_id": jid,
                "status": r.task.status,
                "priority": int(getattr(opts, "priority", 0)),
                "tenant": str(getattr(opts, "tenant", "")),
                "function": r.task.parameters.function_name,
                "parallelism": r.task.state.parallelism,
                "preempting": r.preempt_t0 is not None,
            })
        if not include_journal:
            return out
        try:
            # read-only scan: an operator listing must not rename journal
            # files (quarantine belongs to the boot-time recovery path)
            pending = self._journal.pending(quarantine=False)
        except Exception:
            pending = []
        for entry in pending:
            jid = entry.get("job_id", "")
            if not jid or jid in live:
                continue
            req = entry.get("request", {}) or {}
            opts = req.get("options", {}) or {}
            out.append({
                "job_id": jid,
                "status": JobStateEnum.PREEMPTED,
                "priority": int(opts.get("priority", 0) or 0),
                "tenant": str(opts.get("tenant", "") or ""),
                "function": req.get("function_name", ""),
                "resume_epoch": self._resume_epoch(jid),
            })
        return out

    def serving_telemetry(self) -> dict:
        """{model_id: telemetry snapshot} across the resident decoders — the
        public read the preemption controller polls for overload signals
        (queue depth, 429 counters, request p99)."""
        return self._serving_telemetry()

    # --- embedded time-series store + SLO engine (PR 11) ---

    def start_telemetry(self) -> None:
        """Start the interval sampler (idempotent; no-op with KUBEML_TSDB=0).
        Called by LocalCluster.start / PSAPI.start — a bare PS in tests
        drives ``self.sampler.tick()`` manually instead."""
        if self.cfg.tsdb_enable:
            self.sampler.start()

    def stop_telemetry(self) -> None:
        self.sampler.stop()

    def _collect_series(self) -> Dict[str, float]:
        """One registry sample: every serving counter/gauge per model (the
        exposition's own name/label scheme so /metrics/history correlates
        1:1 with /metrics), scheduler queue depths, running-task gauges,
        the preemption counter, per-job TRAINING gauges (parallelism, loss,
        epoch progress, the statistical-efficiency signals — the elastic
        timeline `kubeml top` and the decision audit correlate against),
        and the scale-decision counters."""
        from .metrics import (PREEMPTIONS, QUEUE_DEPTH, RUNNING,
                              SCALE_DECISIONS, SERVING_COMPILES,
                              SERVING_COUNTERS, SERVING_GAUGES)

        out: Dict[str, float] = {}
        for model, snap in self._serving_telemetry().items():
            for table in (SERVING_COUNTERS, SERVING_GAUGES):
                for metric, (key, _help) in table.items():
                    v = snap.get(key)
                    if v is not None:
                        out[f'{metric}{{model="{model}"}}'] = float(v)
            # compiles: the exposition breaks this out per program; the
            # ring samples the per-model aggregate (rate answers "is this
            # engine still compiling?" — which program is in /metrics)
            comp = snap.get("compiles")
            if comp:
                out[f'{SERVING_COMPILES}{{model="{model}"}}'] = float(
                    sum(comp.values()))
        for kind, n in self.metrics.running_snapshot().items():
            out[f'{RUNNING}{{type="{kind}"}}'] = float(n)
        out[PREEMPTIONS] = float(
            sum(self.metrics.preemptions_snapshot().values()))
        for prio, n in self.metrics.queue_depths().items():
            out[f'{QUEUE_DEPTH}{{priority="{prio}"}}'] = float(n)
        # per-job training series (cleared from the registry when the job
        # finishes, so rings stop growing but retain the job's timeline)
        for (metric, jid), v in self.metrics.job_gauges_snapshot().items():
            out[f'{metric}{{jobid="{jid}"}}'] = float(v)
        for (direction, reason), n in self.metrics.decisions_snapshot().items():
            out[f'{SCALE_DECISIONS}{{direction="{direction}"'
                f',reason="{reason}"}}'] = float(n)
        return out

    def metrics_history(self, match: Optional[str] = None,
                        window: Optional[float] = None, stats: bool = False,
                        include_samples: bool = True,
                        stats_window: Optional[float] = None) -> dict:
        """`GET /metrics/history`: the sampled time-series rings, with
        windowed aggregates (rates for counters, quantiles for gauges) when
        ``stats`` is set — what `kubeml top` refreshes from."""
        return self.tsdb.history(
            match=match, window=window, stats=stats,
            include_samples=include_samples,
            stats_window=(stats_window if stats_window is not None
                          else self.cfg.top_window))

    def slo_status(self) -> dict:
        """`GET /slo`: objectives, burn rates, alert states, transitions."""
        return self.slo.status()

    def get_task(self, job_id: str) -> TrainTask:
        with self._lock:
            record = self._jobs.get(job_id)
        if record is None:
            raise JobNotFoundError(job_id)
        return record.task

    def preempt_task(self, job_id: str, reason: str = "operator",
                     grace: Optional[float] = None) -> None:
        """`/preempt/{jobId}` — checkpoint-and-yield (multi-tenant
        preemption): flag the job to exit at its next round boundary with a
        resume checkpoint and the ``preempted`` terminal status. The journal
        entry is kept however the yield ends, so the job is always
        resumable. A grace watchdog escalates to a hard kill after
        ``grace`` seconds (KUBEML_PREEMPT_GRACE): safe because checkpoint
        publish is atomic — a SIGKILL mid-yield leaves either the previous
        or the new checkpoint, never a torn one."""
        with self._lock:
            record = self._jobs.get(job_id)
        if record is None:
            raise JobNotFoundError(job_id)
        if grace is None:
            grace = self.cfg.preempt_grace
        first = record.preempt_t0 is None
        if first:
            record.preempt_t0 = time.time()
        # resume state must survive whatever happens next — set BEFORE any
        # signal so even an instant crash keeps the journal entry
        record.keep_journal = True
        try:
            if record.url is not None:
                from ..utils import traced_http as requests

                try:
                    r = requests.post(f"{record.url}/preempt",
                                      timeout=requests.timeouts(10),
                                      idempotency_key=True)
                except requests.RequestException as e:
                    raise KubeMLError(
                        f"job {job_id} runner unreachable: {e}", 502)
                if r.status_code >= 400:
                    from ..api.errors import error_from_envelope

                    raise error_from_envelope(r.content, r.status_code)
            elif record.job is None:
                raise KubeMLError(f"job {job_id} is still starting", 409)
            else:
                record.job.preempt()
                if record.update_box is not None:
                    # unblock a job thread waiting on the scheduler's
                    # epoch-end answer — the yield must not wait out
                    # KUBEML_UPDATE_TIMEOUT
                    record.update_box.event.set()
        except Exception:
            # the signal never reached the job: roll the yield clock back so
            # a retry is again "first" (starts the watchdog, counts the
            # metric) and the victim picker does not skip the job as
            # already-yielding forever. keep_journal deliberately stays set
            # — extra resumability is safe, a lost journal entry is not.
            if first:
                record.preempt_t0 = None
            raise
        if first:
            self.metrics.preemption(reason)
            log.info("preempting job %s (%s; grace %.0fs)", job_id, reason,
                     grace)
            threading.Thread(
                target=self._preempt_grace_watch, args=(job_id, record, grace),
                name=f"preempt-grace-{job_id}", daemon=True).start()

    def _preempt_grace_watch(self, job_id: str, record: _JobRecord,
                             grace: float) -> None:
        """Hard-kill escalation: a preempted job that has not freed its slot
        within the grace period is killed (standalone: SIGKILL the runner;
        threaded: the thread is abandoned like a wedged job). The teardown
        carries PREEMPTED status — the journal entry and the newest atomic
        checkpoint make the job fully resumable, so escalation converts an
        unbounded yield into a bounded one instead of losing the work."""
        deadline = record.preempt_t0 + max(0.0, grace)
        while time.time() < deadline:
            with self._lock:
                if self._jobs.get(job_id) is not record:
                    return  # yielded (or torn down) in time
            time.sleep(min(0.2, max(0.01, deadline - time.time())))
        with self._lock:
            if self._jobs.get(job_id) is not record:
                return
        log.warning("job %s did not yield within the %.0fs preempt grace; "
                    "hard-killing (checkpoint publish is atomic — the job "
                    "resumes from its newest checkpoint)", job_id, grace)
        self.metrics.preemption("hard-kill")
        record.task.status = JobStateEnum.PREEMPTED
        if record.proc is not None:
            try:
                record.proc.kill()
            except Exception:
                pass
            self._reap(record)
        else:
            try:
                record.job.stop()  # cooperative; a wedged thread ignores it
            except Exception:
                pass
        # expect-guarded: a yield that races the deadline must not tear down
        # a resubmitted job that reused the id
        self._finish(job_id, expect=record)

    def stop_task(self, job_id: str) -> None:
        """`/stop/{jobId}` -> job stop flag (reference train/api.go:129-134)."""
        with self._lock:
            record = self._jobs.get(job_id)
        if record is None:
            raise JobNotFoundError(job_id)
        if record.url is not None:
            from ..utils import traced_http as requests

            try:
                r = requests.delete(f"{record.url}/stop",
                                    timeout=requests.timeouts(10))
            except requests.RequestException as e:
                raise KubeMLError(f"job {job_id} runner unreachable: {e}", 502)
            if r.status_code >= 400:
                from ..api.errors import error_from_envelope

                raise error_from_envelope(r.content, r.status_code)
            return
        if record.job is None:
            raise KubeMLError(f"job {job_id} is still starting", 409)
        record.job.stop()

    def wait(self, job_id: str, timeout: Optional[float] = None) -> bool:
        """Join a job's thread (test/CLI convenience; reference polls task list).
        For standalone jobs, polls until the finish callback drops the record."""
        with self._lock:
            record = self._jobs.get(job_id)
        if record is None:
            return True
        if record.proc is not None:
            deadline = time.time() + (timeout if timeout is not None else 3600.0)
            while time.time() < deadline:
                with self._lock:
                    if self._jobs.get(job_id) is not record:
                        return True  # finished (or the id was reused — not ours)
                if record.proc.poll() is not None:
                    self._handle_runner_death(job_id, record)
                    return True
                time.sleep(0.1)
            return False
        if record.thread is None:
            return False  # still starting
        try:
            record.thread.join(timeout)
        except RuntimeError:
            return False  # created but not started yet (start_task mid-flight)
        return not record.thread.is_alive()

    def infer(self, model_id: str, data) -> list:
        """`/infer` serving path: run the live job's current model, or — once the
        job has finished — its exported final checkpoint (the reference can only
        serve live jobs because weights are deleted at job end, util.go:211-244)."""
        with self._lock:
            record = self._jobs.get(model_id)
        if record is None:
            return self._infer_from_checkpoint(model_id, data)
        if record.url is not None:
            # live standalone job: prefer the runner's tensor socket — the PS
            # pulls the latest epoch's reference weights once per version and
            # serves inference locally, so image payloads never round-trip
            # through the runner (the RedisAI-role channel; VERDICT round 1
            # gave the native TensorStore this job)
            try:
                out = self._infer_from_socket(model_id, record, data)
                if out is not None:
                    return out
            except Exception:
                log.debug("tensor-socket infer for %s failed; wire fallback",
                          model_id, exc_info=True)
            # second choice: pull the weights themselves over HTTP as one
            # binary dataplane payload (delta-encoded against what we hold —
            # engine/dataplane.py) and serve locally; the JSON /infer
            # round-trip below is the last resort
            try:
                out = self._infer_from_wire(model_id, record, data)
                if out is not None:
                    return out
            except Exception:
                log.debug("weight-wire infer for %s failed; HTTP fallback",
                          model_id, exc_info=True)
            from ..utils import traced_http as requests

            from ..api.errors import error_from_envelope

            r = requests.post(f"{record.url}/infer", json={"data": data},
                              timeout=requests.timeouts(60), retryable=True)
            if r.status_code >= 400:
                raise error_from_envelope(r.content, r.status_code)
            return r.json()["predictions"]
        if record.job is None:
            raise KubeMLError(f"job {model_id} is still starting", 503)
        self.metrics.task_started("inference")
        try:
            return np.asarray(record.job.infer(np.asarray(data))).tolist()
        finally:
            self.metrics.task_finished("inference")

    def generate(self, model_id: str, req):
        """`/generate`: autoregressive sampling from a causal-LM job (live
        in-process, live standalone via its runner, or finished via the final
        checkpoint). Extension — the reference serves forward passes only.

        Finished-checkpoint serving routes through the continuous batcher
        (kubeml_tpu.serving): concurrent requests coalesce into one resident
        batched decode loop instead of one program execution each. Returns a
        dict, or — when ``req.stream`` — a generator of JSON-line records
        (``{"row", "tokens"}`` deltas, then ``{"done", "lengths"}``)."""
        from ..api.types import GenerateRequest

        if not isinstance(req, GenerateRequest):
            req = GenerateRequest.parse_request({**req, "model_id": model_id})
        with self._lock:
            record = self._jobs.get(model_id)
        if record is not None and record.url is not None:
            from ..utils import traced_http as requests

            from ..api.errors import error_from_envelope

            # the runner serves one-shot only: forward without stream and
            # re-wrap below. First call on a new knob/shape combination pays
            # a ~20-27s XLA compile before any decoding; scale the budget
            # with the work so big-but-healthy requests don't surface as
            # transport failures
            fwd = {**req.to_dict(), "stream": False}
            r = requests.post(f"{record.url}/generate", json=fwd,
                              timeout=requests.timeouts(generate_timeout(req)),
                              retryable=True)
            if r.status_code >= 400:
                raise error_from_envelope(r.content, r.status_code)
            return self._maybe_stream(r.json(), req)
        if record is not None:
            if record.job is None:
                raise KubeMLError(f"job {model_id} is still starting", 503)
            if not hasattr(record.job, "generate"):
                raise KubeMLError(
                    f"job {model_id}'s engine does not serve generation", 400)
            self.metrics.task_started("inference")
            try:
                return self._maybe_stream(record.job.generate(req), req)
            finally:
                self.metrics.task_finished("inference")
        model, variables, mtime, mesh = self._load_serving(model_id)
        decoder = self._get_decoder(model_id, model, variables, mtime, mesh)
        if decoder is not None:
            entry = decoder.submit(req)
            if req.stream:
                return self._metered_stream(decoder.stream(entry))
            self.metrics.task_started("inference")
            try:
                return decoder.wait(entry, timeout=generate_timeout(req))
            finally:
                self.metrics.task_finished("inference")
        from ..models.generation import generate_from_request

        self.metrics.task_started("inference")
        try:
            return self._maybe_stream(
                generate_from_request(model.module,
                                      self._densified(variables), req), req)
        finally:
            self.metrics.task_finished("inference")

    @staticmethod
    def _maybe_stream(result: dict, req):
        """Adapt a one-shot result to the streaming wire shape when the
        client asked to stream but the serving path is one-shot."""
        if not req.stream:
            return result

        def lines():
            for i, toks in enumerate(result["tokens"]):
                yield {"row": i, "tokens": toks[: result["lengths"][i]]}
            yield {"done": True, "lengths": result["lengths"]}

        return lines()

    def _metered_stream(self, gen):
        self.metrics.task_started("inference")

        def wrapped():
            try:
                yield from gen
            finally:
                self.metrics.task_finished("inference")

        return wrapped()

    def _get_decoder(self, model_id: str, model, variables, mtime=None,
                     mesh=None):
        """The continuous-batching decoder for a finished checkpoint, or None
        when the model can't be slab-decoded (no per-row positions support)
        or batching is disabled. Invalidated when the checkpoint changes
        (``mtime`` is the caller's _load_serving freshness key — passed
        through so a serving-cache eviction between the load and this call
        can't mis-key the decoder). With ``mesh`` (Config.serving_mesh) the
        decoder runs SPMD: params and KV slab sharded over the mesh."""
        if not self.cfg.serving_batcher:
            return None
        module = getattr(model, "module", None)
        if not _decodes_tokens(module):
            return None
        with self._lock:
            cached = self._decoders.get(model_id)
            # a closed decoder (init failed on-device, unrecoverable loop
            # fault) is dead weight: rebuild instead of 503ing every request
            if (cached is not None and cached[1] == mtime
                    and not cached[0].closed):
                return cached[0]
        t0 = time.monotonic()
        with tracing.get_tracer().span("ps.serving.decoder", service="ps",
                                       job=model_id) as span:
            decoder = self._new_decoder(model_id, module, variables, mesh)
            if span is not None:
                span.attrs.update(slots=decoder.slots,
                                  pages=decoder.arena_pages,
                                  arena_bytes=decoder.arena_bytes)
        # where this replica's start went so far: the tree's restore and
        # hold (once: a decoder rebuilt on a cached tree spent neither)
        with self._lock:
            spent = self._serving_startup.pop(model_id, {})
            decoder.stats.param_leaves_narrowed = self._serving_narrowed.get(
                model_id, 0)
        spent["decoder"] = time.monotonic() - t0
        for phase, seconds in spent.items():
            decoder.stats.startup(phase, seconds)
        stale = []
        with self._lock:
            # double-checked: a racing thread may have built one meanwhile —
            # theirs may already carry traffic, ours is guaranteed unused
            current = self._decoders.get(model_id)
            if (current is not None and current[1] == mtime
                    and not current[0].closed):
                stale.append(decoder)
                decoder = current[0]
            else:
                if current is not None:
                    stale.append(current[0])
                self._decoders[model_id] = (decoder, mtime)
                while len(self._decoders) > DECODER_CACHE_SIZE:
                    # dicts iterate in insertion order: evict the oldest entry
                    oldest = next(iter(self._decoders))
                    stale.append(self._decoders.pop(oldest)[0])
        for d in stale:
            try:
                # graceful: in-flight requests on a displaced decoder finish;
                # only new submissions are refused
                d.retire()
            except Exception:
                log.exception("retiring stale decoder failed")
        return decoder

    def _new_decoder(self, model_id: str, module, variables, mesh):
        """A decoder for ``module`` over ``variables`` as the process config
        asks: the paged engine where it can serve, else the slot engine."""
        from ..serving import BatchingDecoder, PagedBatchingDecoder

        quantize = self.cfg.serving_quantize
        if quantize not in ("", "int8"):
            log.warning("KUBEML_SERVING_QUANTIZE=%r not recognized "
                        "(valid: int8) — serving unquantized", quantize)
            quantize = ""
        common = dict(
            slots=self.cfg.serving_slots,
            chunk_steps=self.cfg.serving_chunk_steps, name=model_id,
            quantize=quantize,
            int8_matmul=self.cfg.int8_matmul,
            pipeline_depth=self.cfg.serving_pipeline,
            queue_limit=self.cfg.serving_queue_limit,
            shed_policy=self.cfg.serving_shed_policy,
            compile_storm_per_min=self.cfg.compile_storm_per_min)
        # paged engine (KUBEML_SERVING_PAGED, default on) for capable
        # models on an unmeshed device: paged KV arena + block allocator,
        # page-budget admission, shared-prefix reuse. Meshed serving and
        # models without a paged decode path (MoE-interleaved) keep the
        # dense slot engine.
        from ..models.generation import supports_paged_decode

        if (self.cfg.serving_paged and mesh is None
                and supports_paged_decode(module)):
            paged_kw = dict(page_tokens=self.cfg.serving_page_tokens,
                            pages=self.cfg.serving_pages,
                            prefix_cache=self.cfg.serving_prefix_cache,
                            paged_attn=self.cfg.paged_attn,
                            kv_quant=self.cfg.kv_quant,
                            spec_min_accept=self.cfg.spec_min_accept,
                            prefill_chunk_tokens=self.cfg.prefill_chunk_tokens,
                            pool_audit_interval=self.cfg.pool_audit_interval)
            spec_kw = self._spec_decoder_args(module)
            try:
                decoder = PagedBatchingDecoder(module, variables,
                                               **paged_kw, **spec_kw,
                                               **common)
            except Exception as e:
                # the degrade-to-plain contract covers constructor-time
                # rejections too (exit layer out of range, incompatible
                # draft model, bad k): serving the checkpoint beats
                # serving a 500 on every request
                if not spec_kw:
                    raise
                log.warning("speculative-decoding config rejected (%s); "
                            "serving %s without speculation", e, model_id)
                decoder = PagedBatchingDecoder(module, variables,
                                               **paged_kw, **common)
        else:
            decoder = BatchingDecoder(module, variables, mesh=mesh, **common)
        return decoder

    def _spec_decoder_args(self, module) -> dict:
        """Speculative-decoding constructor args for a paged decoder, from
        the process config (KUBEML_SERVING_SPEC=draft|self|off). A broken
        spec configuration (unknown mode, missing/unloadable/incompatible
        draft model) DEGRADES to plain decode with a warning — serving the
        checkpoint beats serving a 500."""
        spec = (self.cfg.serving_spec or "off").lower()
        if spec in ("", "off"):
            return {}
        if spec not in ("draft", "self"):
            log.warning("KUBEML_SERVING_SPEC=%r not recognized (valid: "
                        "off, draft, self) — serving without speculation",
                        spec)
            return {}
        out = dict(spec=spec, spec_k=self.cfg.spec_k,
                   spec_adaptive=self.cfg.spec_adaptive)
        if spec == "self":
            out["spec_exit_layer"] = self.cfg.spec_exit_layer
            return out
        draft_id = self.cfg.spec_draft_model
        if not draft_id:
            log.warning("KUBEML_SERVING_SPEC=draft needs "
                        "KUBEML_SPEC_DRAFT_MODEL (a finished job id); "
                        "serving without speculation")
            return {}
        try:
            # the draft checkpoint rides the same serving loader as the
            # target: final-int8 preferred under int8 serving, so the
            # drafter streams quantized weights too
            from ..models.generation import supports_paged_decode

            dmodel, dvars, _, dmesh = self._load_serving(draft_id)
            dmod = getattr(dmodel, "module", None)
            if dmod is None or dmesh is not None \
                    or not supports_paged_decode(dmod):
                raise KubeMLError(
                    f"draft model {draft_id!r} cannot draft (no paged "
                    f"decode path, or meshed)", 400)
            out.update(draft_module=dmod, draft_variables=dvars)
            return out
        except Exception as e:
            log.warning("loading the draft model %r failed (%s); serving "
                        "without speculation", draft_id, e)
            return {}

    def _infer_from_socket(self, model_id: str, record, data) -> Optional[list]:
        """Serve a live standalone job from its runner's tensor socket; None
        when unavailable (socket off/absent, or no epoch published yet) —
        the caller then falls back to the runner's HTTP /infer."""
        import jax.numpy as jnp

        if not self.cfg.tensor_sockets:
            return None
        sock = self.cfg.job_socket_path(model_id)
        if not sock.exists():
            return None
        from ..native.bindings import TensorClient
        from ..native.weights import (FetchCache, fetch_variables,
                                      read_version)

        with self._lock:
            cached = self._socket_cache.get(model_id)
        with TensorClient(str(sock), timeout=10) as client:
            version = read_version(client)
            if version is None:
                # nothing published yet, OR the runner is mid-publish (seqlock
                # sentinel): serve the previous epoch from cache if we have it
                # rather than falling back to the HTTP payload round-trip
                if cached is None:
                    return None
            elif cached is None or cached[2] != version:
                # delta fetch: the FetchCache keeps last epoch's leaves, so
                # only leaves whose manifest version moved cross the socket
                fetch_cache = cached[3] if cached is not None else FetchCache()
                variables, version = fetch_variables(client, cache=fetch_cache)
                if variables is None:
                    return None
                model = self.registry.load(record.task.parameters.function_name)
                cached = (model, variables, version, fetch_cache)
                with self._lock:
                    self._socket_cache[model_id] = cached
        model, variables = cached[0], cached[1]
        self.metrics.task_started("inference")
        try:
            x = model.preprocess(jnp.asarray(np.asarray(data)))
            return np.asarray(model.infer(variables, x)).tolist()
        finally:
            self.metrics.task_finished("inference")

    def _infer_from_wire(self, model_id: str, record, data) -> Optional[list]:
        """Serve a live standalone job by pulling its weights over the HTTP
        binary seam (``GET /weights`` — engine/dataplane wire format) and
        running the model locally. Returns None when the runner has nothing
        published (the caller then falls back to the JSON /infer
        round-trip). A repeat pull while we are current costs one 204; a
        one-epoch-stale cache costs the delta payload, not the tree."""
        import jax.numpy as jnp

        from ..engine import dataplane
        from ..engine.dataplane import BaseVersionMismatch, DeltaDecoder
        from ..utils import traced_http as requests

        with self._lock:
            wire_lock = self._wire_locks.setdefault(model_id, threading.Lock())
            cached = self._wire_cache.get(model_id)
        # the GET runs OUTSIDE the per-model lock: only decode + cache-swap
        # needs serializing, and holding the lock across a network round
        # trip (60s read timeout; the steady-state 204 check included)
        # would cap the model's ENTIRE serving path at one request per
        # runner response — every ThreadingHTTPServer thread queueing
        # behind one slow /weights answer
        since_v = cached[2].version if cached is not None else None
        url = f"{record.url}/weights"
        since = f"?since={since_v}" if since_v is not None else ""
        r = requests.get(url + since, timeout=requests.timeouts(60),
                         retryable=True)
        if r.status_code == 404:
            return None  # nothing published yet
        if r.status_code >= 400:
            from ..api.errors import error_from_envelope

            raise error_from_envelope(r.content, r.status_code)
        if r.status_code == 204:
            # only reachable with a cached decoder: ``since`` is sent iff
            # the decoder has a version, i.e. it decoded into the cache
            # before, and ``cached`` is our own pre-GET snapshot (a racing
            # thread advancing the cache meanwhile just makes this serve
            # one version stale — still an internally consistent tree)
            model, variables = cached[0], cached[1]
        else:
            target = int(r.headers.get(dataplane.VERSION_HEADER, "0"))
            # load the model BEFORE decoding: decode() advances the SHARED
            # cached decoder in place (atomically — state lands only on
            # success), so anything that can raise after it would leave the
            # decoder ahead of the cached variables and every later
            # ?since= would 204 into silently stale serves
            model = self.registry.load(record.task.parameters.function_name)
            with wire_lock:
                # re-read under the lock: another thread may have decoded
                # while our GET was in flight — its payload and ours carry
                # the same delta, and double-applying a delta into the
                # stateful decoder would corrupt the chain
                with self._lock:
                    cached = self._wire_cache.get(model_id)
                decoder = cached[2] if cached is not None else DeltaDecoder()
                if cached is not None and decoder.version == target:
                    model, variables = cached[0], cached[1]
                else:
                    try:
                        variables, _version = decoder.decode(r.content)
                    except BaseVersionMismatch:
                        # the runner no longer serves a delta against our
                        # version (it only keeps one step): full snapshot,
                        # fresh chain (rare resync — worth the lock)
                        decoder = DeltaDecoder()
                        r = requests.get(url, timeout=requests.timeouts(60),
                                         retryable=True)
                        if r.status_code >= 400:
                            return None
                        variables, _version = decoder.decode(r.content)
                    with self._lock:
                        self._wire_cache[model_id] = (model, variables,
                                                      decoder)
        self.metrics.task_started("inference")
        try:
            x = model.preprocess(jnp.asarray(np.asarray(data)))
            return np.asarray(model.infer(variables, x)).tolist()
        finally:
            self.metrics.task_finished("inference")

    @staticmethod
    def _densified(variables):
        """Dense view of possibly-int8 serving variables for the paths that
        consume a plain tree (classifier /infer, the one-shot generate
        fallback) — the batcher consumes QuantizedTensor leaves natively,
        and slices a table held by padded rows inside its programs."""
        from ..serving.quant import (dequantize_tree, is_quantized_tree,
                                     unpadded)

        variables = unpadded(variables)
        if is_quantized_tree(variables):
            import jax.numpy as jnp

            return dequantize_tree(variables, jnp.float32)
        return variables

    def _serving_telemetry(self) -> dict:
        """{model_id: telemetry} across the resident decoders (the /metrics
        serving source; VERDICT r4 weak-4 — the serving runtime gets the
        same gauge discipline as training)."""
        with self._lock:
            decoders = {mid: d for mid, (d, _) in self._decoders.items()}
        out = {}
        for mid, d in decoders.items():
            try:
                out[mid] = d.telemetry()
            except Exception:
                log.debug("telemetry for %s failed", mid, exc_info=True)
        return out

    # --- graceful serving drain / boot replay (ISSUE 20) ---

    def drain_serving(self, grace: Optional[float] = None) -> dict:
        """``POST /serving/drain`` (and the SIGTERM seam): drain every
        resident decoder — new admissions 429, live rows get up to
        ``grace`` seconds (KUBEML_DRAIN_GRACE), stragglers snapshot into
        portable KMS1 frames. With KUBEML_SNAP_DIR set the frames land
        there (one ``<model>-<request>.kms`` each) for the next boot's
        :meth:`restore_serving` to replay; without it the frames are
        dropped (the waiters already got their retryable 503 + partial
        tokens either way). Decoders without a drain seam (the dense
        engine) just retire."""
        import os

        with self._lock:
            decoders = {mid: d for mid, (d, _) in self._decoders.items()}
        snap_dir = self.cfg.snap_dir
        out = {"models": [], "snapshots": 0, "written": []}
        for mid, d in decoders.items():
            try:
                if hasattr(d, "drain"):
                    frames = d.drain(self.cfg.drain_grace if grace is None
                                     else grace)
                else:
                    d.retire()
                    frames = []
            except Exception:
                log.exception("draining decoder %s failed", mid)
                continue
            out["models"].append(mid)
            out["snapshots"] += len(frames)
            if not (snap_dir and frames):
                continue
            from ..serving import kvsnap

            os.makedirs(snap_dir, exist_ok=True)
            for frame in frames:
                try:
                    rid = (kvsnap.peek_header(frame).get("request_id")
                           or f"r{len(out['written'])}")
                    safe = "".join(c if c.isalnum() or c in "-_" else "_"
                                   for c in f"{mid}-{rid}")
                    path = os.path.join(snap_dir,
                                        safe + kvsnap.SNAP_SUFFIX)
                    with open(path, "wb") as f:
                        f.write(frame)
                    out["written"].append(path)
                except Exception:
                    log.exception("writing snapshot for %s failed", mid)
        return out

    def restore_serving(self) -> dict:
        """Boot-time replay: scan KUBEML_SNAP_DIR for ``.kms`` frames, route
        each to its model's decoder by the KMS1 header, and re-admit it via
        ``submit_snapshot`` — the generation continues mid-stream in this
        process (greedy continuation bit-identical to the uninterrupted
        run). A replayed file is deleted after admission; failures leave
        the file in place and are reported, not raised (a corrupt frame
        must not wedge boot)."""
        import os

        snap_dir = self.cfg.snap_dir
        out = {"restored": [], "failed": []}
        if not snap_dir or not os.path.isdir(snap_dir):
            return out
        from ..serving import kvsnap

        for fname in sorted(os.listdir(snap_dir)):
            if not fname.endswith(kvsnap.SNAP_SUFFIX):
                continue
            path = os.path.join(snap_dir, fname)
            try:
                with open(path, "rb") as f:
                    frame = f.read()
                mid = str(kvsnap.peek_header(frame).get("model") or "")
                model, variables, mtime, mesh = self._load_serving(mid)
                decoder = self._get_decoder(mid, model, variables, mtime,
                                            mesh)
                if decoder is None or not hasattr(decoder,
                                                  "submit_snapshot"):
                    raise KubeMLError(
                        f"model {mid!r} has no snapshot-capable decoder",
                        409)
                entry = decoder.submit_snapshot(frame)
                rec = {"model": mid, "request_id": entry.request_id,
                       "file": fname, "entry": entry, "decoder": decoder}
                with self._lock:
                    self._restored.append(rec)
                out["restored"].append({"model": mid,
                                        "request_id": entry.request_id})
                os.unlink(path)
            except Exception as e:
                log.warning("snapshot replay failed for %s: %s", fname, e)
                out["failed"].append({"file": fname, "error": str(e)})
        return out

    def restored_snapshot(self) -> list:
        """``GET /serving/restored``: replayed requests + their live state
        (done flag, emitted token count, and the full tokens once done) —
        the cross-process drain demo's ground truth."""
        with self._lock:
            recs = list(self._restored)
        out = []
        for rec in recs:
            entry = rec["entry"]
            done = entry.done_evt.is_set() and entry.error is None
            row = {"model": rec["model"], "request_id": rec["request_id"],
                   "file": rec["file"], "done": done,
                   "error": str(entry.error) if entry.error else None,
                   "lengths": [len(r.out) for r in entry.rows]}
            if done:
                res = entry.result()
                row["tokens"] = [t[:n] for t, n in zip(res["tokens"],
                                                       res["lengths"])]
            out.append(row)
        return out

    def _serving_sharded_store(self):
        # cached: _final_source sits on the hot path of every /infer and
        # /generate, and the store's __init__ mkdirs its root
        store = getattr(self, "_sharded_ckpt_store", None)
        if store is None:
            from ..storage.sharded_checkpoint import ShardedCheckpointStore

            store = ShardedCheckpointStore(root=self._ckpt_store.root)
            self._sharded_ckpt_store = store
        return store

    def _final_source(self, model_id: str):
        """(kind, tag, mtime_ns) of the checkpoint to serve — ``"flat"``
        (single-replica export) or ``"sharded"`` (gather-free manifest +
        per-process slices, the SPMD engine's sharded_checkpoints export) —
        or (None, None, None). With ``KUBEML_SERVING_QUANTIZE=int8`` a
        pre-quantized ``final-int8`` export (serving.quant.
        quantize_final_checkpoint) is PREFERRED: it restores int8 straight
        onto the serving mesh with no dense transient. A malformed/unknown
        id is a 404, never a 500."""
        from ..api.errors import CheckpointNotFoundError, StorageError

        def resolve(tag):
            flat = sharded = None
            try:
                flat = self._ckpt_store.export_path(
                    model_id, tag=tag).stat().st_mtime_ns
            except (CheckpointNotFoundError, StorageError, OSError):
                pass
            try:
                sharded = self._serving_sharded_store().manifest_path(
                    model_id, tag).stat().st_mtime_ns
            except (StorageError, OSError):
                pass
            if flat is None and sharded is None:
                return None
            if sharded is None or (flat is not None and flat >= sharded):
                return ("flat", tag, flat)
            return ("sharded", tag, sharded)

        dense = resolve(FINAL_TAG)
        if self.cfg.serving_quantize == "int8":
            from ..serving.quant import INT8_TAG

            int8 = resolve(INT8_TAG)
            # prefer the quantized export only while it is at least as
            # fresh as the dense final — a retrain under the same id must
            # not be shadowed forever by a stale final-int8
            if int8 is not None and (dense is None or int8[2] >= dense[2]):
                return int8
            if int8 is not None:
                log.debug("%s: final-int8 is older than the dense final — "
                          "serving dense (re-run `checkpoint quantize`)",
                          model_id)
        if dense is None:
            return None, None, None
        return dense

    def _serving_mesh_for(self, model):
        """The configured serving mesh (Config.serving_mesh, e.g. "tp=2"),
        or None for single-device serving. The mesh makes the finished-model
        decode path one SPMD program: params follow the module's partitioning
        annotations, the batcher's KV slab is head-sharded (serving/batcher),
        and sharded checkpoints restore straight onto it."""
        try:
            axes = self.cfg.serving_mesh_axes()
        except ValueError:
            log.exception("invalid KUBEML_SERVING_MESH; single-device serving")
            return None
        if not axes:
            return None
        import jax

        from ..parallel.mesh import make_mesh

        if any(int(v) < 1 for v in axes.values()):
            log.warning("serving mesh %s has a non-positive axis — "
                        "falling back to single-device serving", axes)
            return None
        n = 1
        for v in axes.values():
            n *= int(v)
        devices = jax.devices()
        if n > len(devices):
            log.warning("serving mesh %s needs %d devices, have %d — "
                        "falling back to single-device serving",
                        axes, n, len(devices))
            return None
        try:
            return make_mesh(shape=axes, devices=devices[:n])
        except ValueError:
            log.exception("serving mesh %s rejected — single-device serving",
                          axes)
            return None

    def _build_serving(self, model_id: str, kind: str, tag: str,
                       mtime) -> tuple:
        """(model, variables, mtime, mesh) from the final checkpoint: the
        files read and remapped (a ``ps.serving.restore`` span: ``leaves``
        and ``bytes`` as stored), then held in the served type
        (``ps.serving.hold``), both children of the request's server span.
        Their seconds wait in ``_serving_startup`` for the decoder's stats:
        they are timed whether the tracer is on or not."""
        import jax

        t0 = time.monotonic()
        with tracing.get_tracer().span("ps.serving.restore", service="ps",
                                       job=model_id, kind=kind) as span:
            model, variables, mesh, placed = self._restore_serving(
                model_id, kind, tag)
            if span is not None:
                span.attrs.update(leaves=len(jax.tree.leaves(variables)),
                                  bytes=_tree_bytes(variables))
        t1 = time.monotonic()
        narrowed = 0
        if not placed:
            variables, narrowed = self._held(
                variables, getattr(model, "module", None), mesh)
        with self._lock:
            self._serving_startup[model_id] = {
                "restore": t1 - t0, "hold": time.monotonic() - t1}
            self._serving_narrowed[model_id] = narrowed
        return (model, variables, mtime, mesh)

    def _restore_serving(self, model_id: str, kind: str, tag: str) -> tuple:
        """(model, variables, mesh, placed) from the final checkpoint. The
        model's ``serving_remap`` re-layouts training-shaped checkpoints
        (e.g. pipeline-stacked stages) into the serving module's layout; a
        sharded final restores per-slice straight onto the serving mesh —
        no host materializes the full tree (VERDICT r4 next-1), and such a
        tree is ``placed``: it keeps the type it was restored in. A
        ``final-int8`` export restores its int8 values/scales directly
        (storage markers -> QuantizedTensor tree; serving-layout already,
        so the remap never re-applies)."""
        from ..api.errors import CheckpointNotFoundError
        from ..serving.quant import from_storage_tree, is_quantized_storage

        if kind == "flat":
            try:
                ck = self._ckpt_store.restore(model_id, tag=tag)
            except CheckpointNotFoundError:
                raise JobNotFoundError(model_id)
            fn_name = ck.meta.get("request", {}).get("function_name", "")
            model = self.registry.load(fn_name)
            variables = ck.variables
            if is_quantized_storage(variables):
                variables = from_storage_tree(variables)
            remap = model.serving_remap()
            if remap is not None and ck.meta.get("layout") != "serving":
                from ..storage.sharded_checkpoint import apply_remap_host

                variables = apply_remap_host(variables, remap)
            return (model, variables, self._serving_mesh_for(model), False)
        store = self._serving_sharded_store()
        try:
            manifest = store.read_manifest(model_id, tag)
        except CheckpointNotFoundError:
            raise JobNotFoundError(model_id)
        fn_name = (manifest.get("meta", {}).get("request", {})
                   .get("function_name", ""))
        model = self.registry.load(fn_name)
        quantized = any(p.rsplit("/", 1)[-1].startswith("__q8_")
                        for p in manifest["leaves"])
        remap = (None if (quantized
                          or manifest.get("meta", {}).get("layout") == "serving")
                 else model.serving_remap())
        mesh = self._serving_mesh_for(model)
        shardings = None
        if mesh is not None:
            try:
                if quantized:
                    from ..serving.batcher import storage_shardings

                    shardings = storage_shardings(
                        manifest["leaves"], model.module, mesh)
                else:
                    from ..serving.batcher import _param_shardings

                    shardings = _param_shardings(model.module, mesh)
            except Exception:
                # not a token-in LM (or no annotations): restore to host and
                # serve single-device — the mesh only helps decode-capable
                # models anyway
                log.debug("deriving serving shardings for %s failed; "
                          "restoring to host", model_id, exc_info=True)
                mesh = None
        ck = store.restore(model_id, tag, shardings=shardings, remap=remap)
        variables = ck.variables
        if quantized:
            variables = from_storage_tree(variables)
        return (model, variables, mesh, mesh is not None)

    def _held(self, variables, module, mesh=None) -> tuple:
        """(the served tree as it is held on the device, the leaves the
        rule narrowed). With ``Config.serving_param_dtype`` set every
        floating leaf is cast to it. Empty, the module is asked what it
        does with each leaf (``serving.quant.held_as``: its forward
        traced abstractly): **a leaf whose every use is a cast to one and
        the same narrower floating type is held in that type**, the same
        bits the programs would make of it every step and every admit;
        every other leaf stays as the checkpoint has it. Type, then
        layout, by the same trace: **a table of which the programs only
        gather whole rows (the token lookup) is held with a row contiguous
        on the lanes** (``serving.quant.rows_on_lanes``: nothing but the
        placement where the device stores the leaf by rows of itself; a
        ``PaddedRows`` of whole lane rows where it does not, which the
        engines' programs slice to the table's own width before
        ``module.apply``). The rule is skipped, and the tree kept whole,
        for a tree that holds quantized leaves, for a process that
        quantizes at serve time (the quantizer must see the checkpoint's
        own values), for a module that is no token-in LM and where the
        trace fails. A tree already placed on a serving mesh never comes
        here: its shardings were derived for the leaves as restored; and a
        tree that the engine of ``mesh`` will place is cast here and no
        table of it padded: the mesh lays its shards out.
        A ``ps.serving.hold`` span: the types ``from`` and ``to``, ``bytes``
        as held, ``narrowed`` and ``narrowed_bytes`` (the leaves the rule
        cast, and their bytes as held), ``kept`` (the floating leaves still
        in the type they came in), ``row_tables`` (the tables the rule
        named) and ``padded_bytes`` (what padding their rows added, 0 where
        the device stores them by rows as they are), and what the casts and
        a padding compiled on this thread, which is the hold's and not an
        engine program's."""
        import jax

        from ..serving import quant

        want = (self.cfg.serving_param_dtype or "").lower()
        if want not in ("", "bfloat16", "float32"):
            log.warning("KUBEML_SERVING_PARAM_DTYPE=%r not recognized "
                        "(valid: bfloat16, float32) - serving the "
                        "checkpoint's own type", want)
            want = ""
        clock = tracing.compile_clock()
        before = clock.read()
        with tracing.get_tracer().span("ps.serving.hold",
                                       service="ps") as span:
            restored = variables
            types, rows = [], []
            if want:
                variables = quant.cast_tree(variables, want)
            elif (self.cfg.serving_quantize != "int8"
                  and not quant.is_quantized_tree(variables)
                  and _decodes_tokens(module)):
                try:
                    types, rows = quant.held_as(module, variables)
                except Exception:
                    log.debug("tracing the serving forward failed; holding "
                              "the tree as restored", exc_info=True)
                else:
                    variables = quant.cast_leaves(
                        variables, types, rows if mesh is None else None)
            cast = [i for i, to in enumerate(types) if to is not None]
            if span is not None:
                held = jax.tree.leaves(variables)
                span.attrs["from"] = _float_types(restored)
                span.attrs.update(
                    to=_float_types(variables), bytes=_tree_bytes(variables),
                    narrowed=len(cast),
                    narrowed_bytes=sum(int(held[i].nbytes) for i in cast),
                    row_tables=sum(rows),
                    padded_bytes=quant.padded_bytes(variables),
                    kept=sum(was.dtype == now.dtype for was, now in zip(
                        jax.tree.leaves(restored), held)
                        if quant.is_floating(now)),
                    **clock.since(before))
        return variables, len(cast)

    def _load_serving(self, model_id: str):
        """(model, variables, mtime, serving mesh) for a FINISHED job from
        its exported final checkpoint (flat or sharded), via the
        mtime-validated serving cache. Shared by /infer and /generate."""
        kind, tag, mtime = self._final_source(model_id)
        with self._lock:
            cached = self._serving_cache.get(model_id)
            if cached is not None and cached[2] != mtime:
                cached = None  # checkpoint deleted or replaced since caching
                self._serving_cache.pop(model_id, None)
        if mtime is None:
            raise JobNotFoundError(model_id)
        if cached is None:
            cached = self._build_serving(model_id, kind, tag, mtime)
            with self._lock:
                self._serving_cache[model_id] = cached
                while len(self._serving_cache) > SERVING_CACHE_SIZE:
                    evicted = next(iter(self._serving_cache))
                    self._serving_cache.pop(evicted)
                    self._serving_startup.pop(evicted, None)
                    self._serving_narrowed.pop(evicted, None)
        return cached

    def _infer_from_checkpoint(self, model_id: str, data) -> list:
        import jax.numpy as jnp

        model, variables, _, _ = self._load_serving(model_id)
        variables = self._densified(variables)
        self.metrics.task_started("inference")
        try:
            # same device-side input pipeline as training/live serving: a model
            # whose preprocess dequantizes (KubeModel.preprocess) must see
            # identical inputs whether the job is live (KAvgTrainer.infer) or
            # served from its final checkpoint here
            x = model.preprocess(jnp.asarray(np.asarray(data)))
            return np.asarray(model.infer(variables, x)).tolist()
        finally:
            self.metrics.task_finished("inference")
