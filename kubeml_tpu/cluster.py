"""Cluster wiring — boot the full control plane in one process.

The reference deploys five services as Kubernetes pods (Helm chart,
reference: ml/charts/kubeml/) and also supports an all-goroutines debug boot
(reference: ml/tests/integration.go:14-36 + DEBUG_ENV). On a TPU VM the
all-in-one-process form is the *primary* deployment — the chips are local, so
scattering the control plane over pods would only add hops. ``LocalCluster``
wires storage + PS + scheduler + controller in-process (method calls, zero
serialization) while still exposing every reference HTTP surface for remote
clients and the CLI.
"""

from __future__ import annotations

import logging
from typing import Optional

from .api.config import Config, enable_compilation_cache, get_config
from .controller.controller import Controller
from .functions.registry import FunctionRegistry
from .ps.parameter_server import ParameterServer
from .ps.transport import PSAPI
from .scheduler.scheduler import Scheduler
from .scheduler.transport import SchedulerAPI
from .storage.history import HistoryStore
from .storage.service import StorageService
from .storage.store import ShardStore

log = logging.getLogger("kubeml.cluster")


class LocalCluster:
    """All services in one process, shared stores, in-proc control flow."""

    def __init__(self, config: Optional[Config] = None, devices=None, serve_http: bool = True):
        self.cfg = config or get_config()
        self.cfg.ensure_dirs()
        self.serve_http = serve_http

        # multi-host: the control plane lives on process 0 (the leader); the
        # PS announces jobs to follower processes over the host channel
        # (engine.follower) so every host joins the training collectives
        self.dist = None
        import jax

        if jax.process_count() > 1:
            from .parallel.distributed import get_dist_context

            self.dist = get_dist_context()
            if not self.dist.is_leader:
                raise RuntimeError(
                    "LocalCluster must run on process 0; follower processes "
                    "run kubeml_tpu.engine.follower.run_follower"
                )

        self.store = ShardStore(config=self.cfg)
        self.history_store = HistoryStore(config=self.cfg)
        self.registry = FunctionRegistry(config=self.cfg)
        self.ps = ParameterServer(
            registry=self.registry,
            store=self.store,
            history_store=self.history_store,
            config=self.cfg,
            devices=devices,
            dist=self.dist,
        )
        self.scheduler = Scheduler(self.ps, config=self.cfg)
        self.ps.bind_scheduler(self.scheduler)
        # multi-tenant preemption controller (KUBEML_PREEMPT_MONITOR): watches
        # the serving overload signals and checkpoint-and-yields the lowest-
        # priority training job; preempted jobs park here until pressure
        # clears, then requeue with resume=True
        self.preemption = None
        if self.cfg.preempt_monitor:
            from .scheduler.preemption import PreemptionController

            self.preemption = PreemptionController(
                self.scheduler, self.ps, config=self.cfg)
            self.scheduler.preemption = self.preemption
        self.controller = Controller(
            self.scheduler,
            self.ps,
            store=self.store,
            history_store=self.history_store,
            registry=self.registry,
            config=self.cfg,
        )
        self.storage_service: Optional[StorageService] = None
        self.scheduler_api: Optional[SchedulerAPI] = None
        self.ps_api: Optional[PSAPI] = None

    def start(self, recover: bool = True) -> "LocalCluster":
        enable_compilation_cache()
        self.scheduler.start()
        # serving SLO observability: sample the registry into the embedded
        # time-series store and evaluate the SLO engine on each tick
        self.ps.start_telemetry()
        if self.preemption is not None:
            self.preemption.start()
            log.info("preemption controller running (queue>=%d, 429/s>=%g, "
                     "p99>=%gs; grace %gs)", self.cfg.preempt_queue_depth,
                     self.cfg.preempt_overload_rate, self.cfg.preempt_p99,
                     self.cfg.preempt_grace)
        if self.serve_http:
            self.controller.start()
            self.storage_service = StorageService(store=self.store, config=self.cfg).start()
            self.scheduler_api = SchedulerAPI(self.scheduler, config=self.cfg).start()
            self.ps_api = PSAPI(self.ps, config=self.cfg).start()
            log.info("kubeml-tpu cluster up: controller at %s", self.controller.url)
        if recover:
            # crash recovery (deployment supervision): jobs journaled by a
            # previous life resubmit with resume=True — a supervised restart
            # continues interrupted work from its newest checkpoint without
            # operator action. No-op on a clean boot (empty journal).
            try:
                n = self.ps._journal.recover_into(self.scheduler)
                if n:
                    log.info("recovered %d interrupted job(s) from the journal", n)
            except Exception:
                log.exception("journal recovery failed (non-fatal)")
        return self

    def stop(self) -> None:
        if self.preemption is not None:
            self.preemption.stop()
        self.ps.stop_telemetry()
        self.ps.shutdown_standalone_jobs()
        # stop threaded jobs BEFORE the shutdown announcement: a running
        # multi-host job holds the dist lock for its whole duration, and its
        # followers only learn about the stop through the job's own per-round
        # broadcast — announcing first would wait out every remaining epoch
        self.ps.stop_running_jobs()
        self.ps.announce_shutdown()  # release follower processes (multi-host)
        self.scheduler.stop()
        if self.serve_http:
            for svc in (self.controller, self.storage_service, self.scheduler_api, self.ps_api):
                if svc is not None:
                    svc.stop()

    @property
    def controller_url(self) -> str:
        return self.controller.url

    def __enter__(self) -> "LocalCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
