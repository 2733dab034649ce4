"""TrainJob — the per-job training engine.

The TPU-native counterpart of the reference's core runtime
(reference: ml/pkg/train/job.go:156-265): drives the epoch loop — init, per-epoch
train rounds, elastic parallelism re-evaluation, periodic validation, goal-accuracy
early stop, metrics push, history persistence — but where the reference fans out N
HTTP function invocations and merges weights through Redis, this job feeds sync
rounds to the in-process :class:`KAvgTrainer` whose averaging is an on-chip
collective.

Decoupled from the control plane via two callbacks so it runs identically
in-process (tests), threaded under the PS, or standalone:

* ``on_epoch_end(JobState) -> new_parallelism`` — the scheduler hook
  (reference: job.go:196-215 asking the scheduler for next-epoch parallelism);
* ``on_metrics(MetricUpdate)`` — the PS metrics push (train/util.go:20-50).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np

from ..api.errors import KubeMLError, MergeError
from ..api.types import History, JobState, MetricUpdate, TrainRequest
from ..data.dataset import KubeDataset
from ..data.loader import RoundLoader, validation_loader
from ..data.sharding import plan_epoch
from ..runtime.model import KubeModel
from ..storage.checkpoint import FINAL_TAG, CheckpointStore
from ..storage.history import HistoryStore
from ..storage.store import ShardStore
from ..utils.tracing import get_tracer
from .failures import FailureInjector, WorkerHealth
from .kavg import KAvgTrainer, RoundPrefetcher

log = logging.getLogger("kubeml.job")


class TrainJob:
    def __init__(
        self,
        job_id: str,
        request: TrainRequest,
        model: KubeModel,
        store: Optional[ShardStore] = None,
        history_store: Optional[HistoryStore] = None,
        checkpoint_store: Optional[CheckpointStore] = None,
        on_epoch_end: Optional[Callable[[JobState], int]] = None,
        on_metrics: Optional[Callable[[MetricUpdate], None]] = None,
        devices=None,
        seed: int = 0,
        chaos: Optional[FailureInjector] = None,
        health_threshold: int = 3,
        dist=None,
        on_epoch_weights: Optional[Callable[[dict, int], None]] = None,
    ):
        self.job_id = job_id
        self.request = request
        self.model = model
        self.store = store or ShardStore()
        self.history_store = history_store or HistoryStore()
        self._checkpoint_store = checkpoint_store
        self.on_epoch_end = on_epoch_end
        self.on_metrics = on_metrics
        # per-epoch reference-weights hook (standalone runners publish into
        # their tensor socket so the PS serves live /infer; one device->host
        # model copy per epoch — negligible against an epoch of training)
        self.on_epoch_weights = on_epoch_weights
        self.seed = seed

        # multi-controller context: every process runs this same job in
        # lockstep; control decisions (stop, elastic parallelism) are made on
        # the leader and broadcast so the collective programs never diverge
        # (parallel.distributed.DistContext; SURVEY §5 distributed backend)
        if dist is None and jax.process_count() > 1:
            from ..parallel.distributed import get_dist_context

            dist = get_dist_context()
        self.dist = dist
        self._leader = dist is None or dist.is_leader
        if dist is not None and dist.size > 1 and chaos is not None:
            # a CUSTOM injector object only exists in this process; the
            # option-derived injector below is deterministic from the job id,
            # so chaos_prob works multi-host (every process draws identical
            # masks in lockstep — no broadcast needed)
            raise ValueError("custom chaos injectors are single-process "
                             "only; use options.chaos_prob in multi-host mode")

        self.parallelism = request.options.default_parallelism
        self._pending_notes: list = []
        if dist is not None and dist.size > 1:
            # the worker axis must split evenly across host processes
            requested = self.parallelism
            self.parallelism = max(
                dist.size, (requested // dist.size) * dist.size
            )
            if self.parallelism != requested:
                note = (f"requested parallelism {requested} rounded to "
                        f"{self.parallelism} (must be a multiple of the "
                        f"{dist.size} host processes)")
                log.warning("%s: %s", job_id, note)
                self._pending_notes.append(note)
        self.trainer = KAvgTrainer(
            model, precision=request.options.precision, devices=devices,
            donate=request.options.donate, mesh_shape=request.options.mesh_shape,
            dist=dist,
        )
        # fault injection + health-based re-meshing (SURVEY §5/§7). The seed
        # derives from the JOB ID, not the per-process seed arg: in multi-host
        # mode every process must draw bit-identical masks in lockstep
        if chaos is None and request.options.chaos_prob > 0.0:
            import zlib

            chaos = FailureInjector(prob=request.options.chaos_prob,
                                    seed=zlib.crc32(job_id.encode()) & 0x7FFFFFFF)
        self.chaos = chaos
        self.health = WorkerHealth(threshold=health_threshold)
        self.tracer = get_tracer()
        # per-epoch latency-histogram feeds (reset in _train_epoch, pushed
        # with the epoch's MetricUpdate)
        self._last_round_times: list = []
        self._last_merge_s = -1.0
        # statistical-efficiency signals of the epoch's rounds (trainer
        # round program, KUBEML_ROUND_STATS): device arrays accumulated
        # lazily per round, fetched ONCE at the epoch-end loss sync
        self._epoch_round_stats: list = []
        self._last_divergence: list = []
        self._last_spread: list = []
        self._last_round_skew = -1.0

        self.history = History(id=job_id, task={"request": request.to_dict()})
        self.history.notes.extend(self._pending_notes)
        self.stop_event = threading.Event()
        # checkpoint-and-yield (multi-tenant preemption): preempt() rides the
        # stop machinery — every round/epoch boundary and dist broadcast that
        # honors stop_event honors preemption too — but the exit differs: a
        # preempted job writes a resume checkpoint instead of a final export,
        # and reports the `preempted` terminal status so the scheduler
        # requeues it with resume=True when pressure clears
        self.preempt_event = threading.Event()
        self.preempt_requested_at: Optional[float] = None
        # progress stamp for the PS heartbeat monitor (function guardrails):
        # a job whose user code hangs inside a traced program goes stale here
        # and is failed by the monitor instead of wedging its thread forever.
        # heartbeat_cold doubles the monitor's allowance while the first
        # round's XLA compile runs (minutes on chip — ADVICE r4: a cold
        # compile must not read as a hang); cleared once the first round lands
        self.heartbeat = time.time()
        self.heartbeat_cold = True
        self.exit_error: Optional[str] = None
        self._stacked_vars = None
        self._final_variables = None
        # leader-held host copy of the newest checkpointed weights, so /infer
        # can answer DURING multi-host training (serving the live global array
        # would need a collective the followers aren't at); (variables, epoch)
        self._latest_snapshot: Optional[tuple] = None
        # in-flight async checkpoint write (at most one; see _save_checkpoint)
        self._ckpt_thread: Optional[threading.Thread] = None

    # --- public control (reference: train/api.go /stop) ---

    def stop(self) -> None:
        self.stop_event.set()

    def preempt(self) -> None:
        """Checkpoint-and-yield: exit at the next round boundary, write a
        resume checkpoint, report the ``preempted`` status. Idempotent."""
        if self.preempt_requested_at is None:
            self.preempt_requested_at = time.time()
        self.preempt_event.set()
        self.stop_event.set()

    @property
    def preempted(self) -> bool:
        return self.preempt_event.is_set()

    @property
    def checkpoint_store(self) -> CheckpointStore:
        if self._checkpoint_store is None:
            self._checkpoint_store = CheckpointStore()
        return self._checkpoint_store

    @property
    def state(self) -> JobState:
        return JobState(parallelism=self.parallelism)

    # --- main loop (reference: job.go:156-265) ---

    def train(self) -> History:
        req = self.request
        opts = req.options
        try:
            dataset: KubeDataset = self.model.dataset
            dataset._attach(self.store)
            handle = dataset.handle

            # init: build + broadcast initial variables (job.go:268-291 init fn)
            rng = jax.random.PRNGKey(self.seed)
            dataset.set_mode(True)
            sample_x, _ = handle.load_subset_range("train", 0, 1)
            sample_x, _ = dataset.transform(np.asarray(sample_x), None)
            sample_x = sample_x[: req.batch_size]
            self._stacked_vars = self.trainer.init_variables(
                rng, sample_x, self.parallelism
            )

            # resume (TPU-native addition; the reference cannot — SURVEY §5):
            # restore the latest checkpointed reference model + recorded history
            # and continue from the following epoch
            start_epoch = 0
            if opts.resume:
                start_epoch = self._restore_latest()

            val_acc = 0.0
            acc_pct = None
            epochs_run = 0
            for epoch in range(start_epoch, req.epochs):
                if self._sync_stop():
                    log.info("%s: stop requested, exiting at epoch %d", self.job_id, epoch)
                    break
                t0 = time.time()
                used_parallelism = self.parallelism
                with self.tracer.span("job.epoch", service="worker",
                                      job=self.job_id, epoch=epoch,
                                      parallelism=self.parallelism):
                    train_loss = self._train_epoch(epoch, handle, dataset)
                elapsed = time.time() - t0
                if self.stop_event.is_set() and np.isnan(train_loss):
                    break  # stopped mid-epoch before any round completed
                # fast-yield gate, SINGLE-HOST only: the blocks below contain
                # collectives (validation, the elastic broadcast, checkpoint
                # snapshots), and in dist mode preempt_event is leader-local —
                # a one-sided skip would strand the followers; dist yields at
                # the granularity the stop broadcast already provides
                yielding = self.preempt_event.is_set() and (
                    self.dist is None or self.dist.size == 1)

                # health-based re-mesh (SURVEY §7 "partial failure inside
                # collectives"): persistently dead workers shrink the mesh at
                # the epoch boundary — the collective can't drop them mid-round
                if not opts.static_parallelism:
                    healthy_p = self.health.suggest_parallelism(self.parallelism)
                    if self.dist is not None and self.dist.size > 1:
                        # worker axis must stay a host-count multiple (same
                        # invariant the constructor and the elastic branch
                        # enforce); health state is lockstep-identical on
                        # every process, so each computes the same rounding
                        healthy_p = max(
                            self.dist.size,
                            (healthy_p // self.dist.size) * self.dist.size,
                        )
                    if healthy_p < self.parallelism:
                        log.warning(
                            "%s: %d persistently failed worker(s); re-meshing %d -> %d",
                            self.job_id, self.parallelism - healthy_p,
                            self.parallelism, healthy_p,
                        )
                        self._stacked_vars = self.trainer.resize(
                            self._stacked_vars, self.parallelism, healthy_p
                        )
                        self.parallelism = healthy_p
                        self.health.reset()  # indices renumber after the re-mesh

                # elastic re-evaluation (job.go:196-215): ask the scheduler with
                # this epoch's elapsed time unless parallelism is static. The
                # leader asks (its elapsed time stands for the job) and the
                # answer is broadcast so every process re-meshes identically.
                # Skipped when preempting: the answer is unused (the loop
                # exits) and the scheduler round-trip would delay the yield.
                # Lockstep-safe: the round loop's _sync_stop broadcast means
                # every process agrees on the stop flag by this point.
                if not opts.static_parallelism and not yielding and (
                    self.on_epoch_end is not None or self.dist is not None
                ):
                    new_p = None
                    if self._leader and self.on_epoch_end is not None:
                        new_p = self.on_epoch_end(
                            JobState(parallelism=self.parallelism, elapsed_time=elapsed)
                        )
                    if self.dist is not None:
                        _, p = self.dist.broadcast_flags(parallelism=new_p or 0)
                        new_p = p or None
                        if new_p and self.dist.size > 1:
                            asked = new_p
                            new_p = max(
                                self.dist.size,
                                (asked // self.dist.size) * self.dist.size,
                            )
                            if new_p != asked:
                                note = (f"epoch {epoch + 1}: scheduler "
                                        f"parallelism {asked} rounded to "
                                        f"{new_p} (multiple of "
                                        f"{self.dist.size} host processes)")
                                log.warning("%s: %s", self.job_id, note)
                                self.history.notes.append(note)
                    if new_p and new_p != self.parallelism:
                        log.info(
                            "%s: parallelism %d -> %d", self.job_id, self.parallelism, new_p
                        )
                        self._stacked_vars = self.trainer.resize(
                            self._stacked_vars, self.parallelism, new_p
                        )
                        self.parallelism = new_p
                        # worker indices renumber on any resize: stale
                        # consecutive-failure counts must not transfer
                        self.health.reset()

                # periodic validation (job.go:223-243) — skipped mid-yield: a
                # preempting job must release the devices, not run an eval sweep
                val_loss = None
                acc_pct = None
                if (opts.validate_every > 0 and not yielding
                        and (epoch + 1) % opts.validate_every == 0):
                    val_acc, val_loss = self._validate(dataset, handle)
                    acc_pct = val_acc * 100.0

                epochs_run += 1
                self.history.append_epoch(
                    train_loss=train_loss,
                    parallelism=used_parallelism,
                    duration=elapsed,
                    validation_loss=val_loss,
                    accuracy=acc_pct,
                    # with round stats ON every epoch appends a value — an
                    # unmeasured epoch (all-NaN rounds, or a single round
                    # for skew) records NaN so the signal lists stay
                    # index-aligned with train_loss/parallelism; with stats
                    # OFF the lists stay empty entirely (None = no append)
                    worker_divergence=self._epoch_signal(
                        self._last_divergence),
                    loss_spread=self._epoch_signal(self._last_spread),
                    round_skew=(self._last_round_skew
                                if self._last_round_skew >= 0
                                else self._epoch_signal(())),
                )
                if self._leader:
                    self._push_metrics(train_loss, val_loss, acc_pct, elapsed,
                                       used_parallelism, epoch + 1)
                if (opts.checkpoint_every > 0 and not yielding
                        and (epoch + 1) % opts.checkpoint_every == 0):
                    # preempting: redundant with the synchronous yield
                    # checkpoint written at exit (same epoch, same weights)
                    self._save_checkpoint(epoch)
                if self.on_epoch_weights is not None and self.dist is None:
                    try:
                        self.on_epoch_weights(
                            self.trainer.reference_variables(self._stacked_vars),
                            epoch,
                        )
                    except Exception:
                        log.exception("%s: epoch weights publish failed "
                                      "(non-fatal)", self.job_id)
                log.info(
                    "%s: epoch %d/%d loss=%.4f acc=%s parallelism=%d %.2fs",
                    self.job_id, epoch + 1, req.epochs, train_loss,
                    f"{acc_pct:.2f}%" if acc_pct is not None else "-",
                    used_parallelism, elapsed,
                )

                # goal-accuracy early stop (job.go:49-54, 233-243)
                if acc_pct is not None and acc_pct >= opts.goal_accuracy:
                    log.info(
                        "%s: goal accuracy %.2f%% reached (%.2f%%)",
                        self.job_id, opts.goal_accuracy, acc_pct,
                    )
                    break

            # final validation if the last epoch didn't run one (job.go:247-255);
            # validate_every == 0 means the user opted out of validation entirely,
            # and a resume that had nothing left to train must not append extra
            # entries onto the restored (already-aligned) history
            if (
                opts.validate_every > 0
                and acc_pct is None
                and epochs_run > 0
                and not self.stop_event.is_set()
            ):
                val_acc, val_loss = self._validate(dataset, handle)
                self.history.validation_loss.append(float(val_loss))
                self.history.accuracy.append(float(val_acc * 100.0))

            self._join_checkpoint()  # epoch writes land before the final export
            # device->host snapshot of the final model: a COLLECTIVE in dist
            # mode (every process must join the extraction — even the leader
            # eagerly indexing shard 0 of a global array would hang waiting
            # for the others); only the leader persists it below
            self._final_variables = self._snapshot_reference()
            # final model export (the reference deletes all weights at job end,
            # util.go:211-244 — here a finished job stays inferable/exportable).
            # A no-op resume skips the rewrite unless no final export exists yet
            # (crash after the last epoch checkpoint but before the final save).
            # A PREEMPTED job writes a resume checkpoint instead: it is parked,
            # not done — a FINAL export would make the id serve mid-training
            # weights as "the model" and slow the yield with a second write.
            if self.preempt_event.is_set():
                self._save_yield_checkpoint()
            elif self._leader and opts.save_model and (
                epochs_run > 0 or FINAL_TAG not in self.checkpoint_store.tags(self.job_id)
            ):
                self.checkpoint_store.save(
                    self.job_id,
                    self._final_variables,
                    epoch=len(self.history.train_loss),
                    tag=FINAL_TAG,
                    meta={"request": req.to_dict(), "history": self._history_lists()},
                )
        except KubeMLError as e:
            self.exit_error = e.message
            raise
        except Exception as e:
            self.exit_error = str(e)
            raise KubeMLError(f"job {self.job_id} failed: {e}") from e
        finally:
            # persist the history unconditionally, like the deferred save+finish
            # (job.go:161-170) — a failed job records its error so pollers can
            # see the outcome; tensor GC is implicit (device buffers die with us)
            self._join_checkpoint()  # no orphan writer past job end
            if self.exit_error is not None and isinstance(self.history.task, dict):
                self.history.task["error"] = self.exit_error
            if self._leader:
                self.history_store.save(self.history)
        return self.history

    # --- internals ---

    def _sync_stop(self) -> bool:
        """Stop decision every process agrees on: the leader's stop_event is
        broadcast (COLLECTIVE in dist mode) so no process leaves the lockstep
        round/epoch loop while others still issue collectives."""
        stop = self.stop_event.is_set()
        if self.dist is not None:
            stop, _ = self.dist.broadcast_flags(stop=stop)
            if stop:
                self.stop_event.set()
        return stop

    def _train_epoch(self, epoch: int, handle, dataset: KubeDataset) -> float:
        req = self.request
        dataset.set_mode(True)
        plan = plan_epoch(
            num_docs=handle.num_subsets("train"),
            n_workers=self.parallelism,
            batch_size=req.batch_size,
            k=req.options.k,
            subset_size=handle.subset_size,
            num_samples=handle.num_samples("train"),
        )
        loader = RoundLoader(handle, "train", plan, transform=dataset.transform,
                             worker_rows=self.trainer.local_rows(self.parallelism))
        rng = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch + 1)
        losses = []
        skipped = 0
        # latency-histogram feeds, reset per epoch (pushed with MetricUpdate)
        self._last_round_times = []
        self._last_merge_s = -1.0
        self._epoch_round_stats = []
        self._last_divergence = []
        self._last_spread = []
        self._last_round_skew = -1.0
        # prefetched staging (engine/kavg.RoundPrefetcher): each round's
        # slabs are device_put KUBEML_DATAPLANE_PREFETCH rounds ahead
        # (default 1 = double buffering), so the host->HBM transfer of round
        # i+1 overlaps round i's compute (stage_round never blocks;
        # parallelism is fixed within an epoch so the ahead-staging target
        # sharding is always right)
        for rb, rb_staged in RoundPrefetcher(self.trainer, loader,
                                             self.parallelism):
            if self._sync_stop():
                break
            worker_mask = None
            if self.chaos is not None:
                worker_mask = self.chaos.mask(self.parallelism)
                newly_dead = self.health.update(worker_mask)
                if worker_mask.min() == 0.0:
                    log.info("%s: round %d injected failures on workers %s",
                             self.job_id, rb.round_index,
                             np.flatnonzero(worker_mask == 0.0).tolist())
                for w in newly_dead:
                    log.warning("%s: worker %d persistently failed", self.job_id, w)
                # the host knows both masks: when chaos leaves no healthy
                # data-bearing worker, skip the round here (weights keep their
                # pre-round value) instead of running a no-participant merge —
                # so a NaN loss from the device always means real divergence.
                # data-bearing comes from PLAN math, not rb.mask: in dist mode
                # each host materializes only its worker-rows block, and the
                # skip decision must be identical on every process
                data_bearing = loader.plan.data_bearing(rb.round_index)
                if float((worker_mask * data_bearing).sum()) == 0.0:
                    skipped += 1
                    log.warning("%s: round %d skipped — no healthy data-bearing worker",
                                self.job_id, rb.round_index)
                    continue
            t_round = time.time()
            # byte attribution: the slab this round staged host->HBM rides
            # the span so `kubeml profile` can classify rounds
            # compute-bound vs transfer-bound (utils.profiler)
            slab_bytes = int(sum(getattr(a, "nbytes", 0)
                                 for a in (rb.x, rb.y, rb.mask)))
            with self.tracer.span("job.round", service="worker",
                                  job=self.job_id, epoch=epoch,
                                  round=rb.round_index, bytes=slab_bytes):
                loss = self._run_round(rb, rng, worker_mask, epoch, staged=rb_staged)
            if loss is None:  # stop requested during retry backoff
                break
            # histogram feed (ps/metrics.py): per-round host wall time — the
            # function/update-latency analog of the reference's per-invocation
            # timing (dispatch is async; sync stalls land on the epoch fetch)
            self._last_round_times.append(time.time() - t_round)
            # [loss spread, weight divergence] of the dispatched round —
            # still a device array; fetched with the epoch-end loss sync
            if self.trainer.last_round_stats is not None:
                self._epoch_round_stats.append(self.trainer.last_round_stats)
            self.heartbeat = time.time()  # round dispatched: job is alive
            self.heartbeat_cold = False   # cold-start compile is behind us
            if not losses:
                # first round dispatched: background-precompile the next
                # topology-legal scale-up level while this epoch trains, so an
                # elastic grow pays a compile-cache read instead of a stall
                self._precompile_next_level(rb, epoch)
            losses.append(loss)
        if not losses:
            if self.stop_event.is_set():
                return float("nan")  # graceful stop before any round completed
            if skipped:
                # every round lost all data-bearing workers: no progress at
                # all — a hard error like the reference's zero responders
                raise MergeError(
                    f"job {self.job_id}: all {skipped} rounds this epoch had "
                    f"no healthy data-bearing worker"
                )
            raise KubeMLError(f"job {self.job_id}: epoch produced no rounds")
        if skipped:
            log.warning("%s: %d round(s) skipped this epoch (no effective "
                        "participants)", self.job_id, skipped)
        # one blocking host read per epoch, not per round (keeps rounds async);
        # a NaN here is real divergence and stays visible in the history.
        # This fetch is also where ASYNC device-side faults surface (JAX
        # dispatch is lazy): by now the round retry can no longer help — the
        # weights were reassigned to the poisoned outputs — so translate the
        # fault into an actionable error instead of a bare RPC traceback.
        try:
            t_merge = time.time()
            mean_loss = float(np.mean([float(l) for l in losses]))
            # the K-AVG merge is fused on-chip into the round program; this
            # blocking fetch is where the host waits on it, so its wall time
            # is the observable merge cost (kubeml_job_merge_seconds)
            self._last_merge_s = time.time() - t_merge
            self.tracer.record("job.merge", self._last_merge_s,
                               service="worker", job=self.job_id, epoch=epoch)
            self._finalize_round_stats()
            return mean_loss
        except KubeMLError:
            raise
        except Exception as e:
            from .failures import is_transient_accelerator_error

            if is_transient_accelerator_error(e):
                raise KubeMLError(
                    f"job {self.job_id}: transient accelerator fault surfaced at "
                    f"epoch-end loss fetch (round outputs already consumed; "
                    f"in-round retry cannot recover async faults) — resubmit "
                    f"with resume=true to restart from the last checkpoint: {e}"
                ) from e
            raise

    def _run_round(self, rb, rng, worker_mask, epoch: int, staged=None):
        """One staged sync round, retried on transient accelerator faults.

        ``staged`` carries slabs already ahead-staged by the epoch loop's
        double buffer; retries always re-stage from the host arrays. A
        fleet's preemptions and transport resets can drop a program
        mid-round; retrying re-stages and re-runs the round — safe because a
        failed round never published averaged weights. Semantic errors
        (KubeMLError/MergeError) propagate immediately.

        Coverage boundary: JAX dispatch is async, so this retry covers faults
        that raise *synchronously* (compile-RPC drops, staging failures).
        A device-side fault in an already-dispatched round surfaces later, at
        the epoch-end loss fetch, after the variables were reassigned to the
        poisoned outputs — unrecoverable in-round by design (the buffer is
        donated); that path is translated into a resume-from-checkpoint error
        in ``_train_epoch``. The ``alive`` check below guards the related
        donation hazard within this round."""
        from .failures import is_transient_accelerator_error

        req = self.request
        # no retry in multi-host mode: one process retrying while the others
        # proceed would deadlock the collective — a fault fails the job and
        # recovery is resume-from-checkpoint
        attempts = 1 if (self.dist is not None and self.dist.size > 1) else 3
        for attempt in range(attempts):
            try:
                # async-stage the slabs (bf16 host cast / quantized uint8 +
                # device_put): the transfer rides the DMA engine while the
                # previous round's compute is still in flight
                if staged is not None and attempt == 0:
                    sx, sy, sm = staged
                else:
                    sx, sy, sm = self.trainer.stage_round(
                        rb.x, rb.y, rb.mask, self.parallelism
                    )
                self._stacked_vars, loss = self.trainer.sync_round(
                    self._stacked_vars,
                    sx,
                    sy,
                    sm,
                    jax.random.fold_in(rng, rb.round_index),
                    lr=req.lr,
                    epoch=epoch,
                    worker_mask=worker_mask,
                )
                return loss
            except KubeMLError:
                raise
            except Exception as e:
                # the variables buffer is donated into sync_round: if the
                # failed execution already consumed it there is nothing left
                # to retry with — only retry while every leaf is still alive
                alive = all(
                    not getattr(leaf, "is_deleted", lambda: False)()
                    for leaf in jax.tree.leaves(self._stacked_vars)
                )
                if (attempt == attempts - 1 or not alive
                        or not is_transient_accelerator_error(e)):
                    raise
                log.warning(
                    "%s: transient accelerator error on round %d (attempt %d/%d), "
                    "retrying: %s", self.job_id, rb.round_index, attempt + 1,
                    attempts, e,
                )
                # interruptible backoff: a stop request mustn't wait out the
                # sleep — and must end as a graceful stop (None), not as a
                # job failure carrying the transient error
                if self.stop_event.wait(1.0 + attempt):
                    return None

    def _epoch_signal(self, values):
        """Epoch aggregate of a per-round signal list for the History
        record: the mean when measured, NaN when instrumentation is on but
        this epoch produced nothing (keeps the lists index-aligned with
        train_loss), None (no append) when round stats are off."""
        if values:
            return float(np.mean(values))
        return float("nan") if self.trainer.round_stats else None

    def _finalize_round_stats(self) -> None:
        """Fetch the epoch's accumulated round stats to the host (we're at
        the epoch-end sync anyway — the one blocking read per epoch) and
        derive the per-epoch signals: finite per-round divergence/spread
        lists for the PS histograms, and the round-time skew ratio
        max/median (the straggler signal; -1 with fewer than 2 rounds)."""
        self._last_divergence = []
        self._last_spread = []
        for s in self._epoch_round_stats:
            arr = np.asarray(s)
            spread, div = float(arr[0]), float(arr[1])
            # NaN marks a no-participant round — nothing to record
            if np.isfinite(spread):
                self._last_spread.append(spread)
            if np.isfinite(div):
                self._last_divergence.append(div)
        self._epoch_round_stats = []
        self._last_round_skew = -1.0
        # skew is part of the round-stats instrumentation (the docs promise
        # empty/-1 signals with KUBEML_ROUND_STATS=0), so it honors the
        # same switch even though its input is the always-measured times
        if self.trainer.round_stats and len(self._last_round_times) >= 2:
            med = float(np.median(self._last_round_times))
            if med > 0:
                self._last_round_skew = float(
                    max(self._last_round_times) / med)

    def _precompile_next_level(self, rb, epoch: int) -> None:
        """Kick a background AOT compile of sync_round at the next scale-up
        level (the ladder the scheduler walks, scheduler/policy.py). Round 1's
        unbounded elastic scenario timed out on synchronous recompiles at
        every new level; this moves that cost off the training path."""
        opts = self.request.options
        if opts.static_parallelism:
            return
        try:
            from ..api.config import get_config
            from ..scheduler.policy import next_power_down, next_power_up

            cfg = get_config()
            cap = cfg.max_parallelism or max(8, len(jax.devices()))
            cap = next_power_down(max(1, cap) + 1)  # scheduler's legal ceiling
            if self.dist is not None and self.dist.size > 1:
                cap = (cap // self.dist.size) * self.dist.size
            next_p = next_power_up(self.parallelism, cap)
            if next_p == self.parallelism:
                return
            # staged dtypes: what stage_round will actually feed at next_p
            x_dtype = rb.x.dtype
            if self.request.options.precision == "bf16" and x_dtype == np.float32:
                import jax.numpy as jnp

                x_dtype = jnp.bfloat16
            plan_next = plan_epoch(
                num_docs=self.model.dataset.handle.num_subsets("train"),
                n_workers=next_p,
                batch_size=self.request.batch_size,
                k=opts.k,
                subset_size=self.model.dataset.handle.subset_size,
                num_samples=self.model.dataset.handle.num_samples("train"),
            )
            self.trainer.precompile_async(
                self._stacked_vars, next_p, plan_next.steps_per_round,
                (plan_next.batch_size,) + tuple(rb.x.shape[3:]), x_dtype,
                (plan_next.batch_size,) + tuple(rb.y.shape[3:]), rb.y.dtype,
                lr=self.request.lr, epoch=epoch,
            )
        except Exception:
            log.debug("next-level precompile setup failed (non-fatal)",
                      exc_info=True)

    def _validate(self, dataset: KubeDataset, handle):
        # epoch-end validation runs no training rounds: stamp per evaluated
        # round (the loader is streamed through a stamping generator) so a
        # sweep longer than the function timeout never reads as a hang — one
        # hung eval round still trips the monitor
        self.heartbeat = time.time()
        dataset.set_mode(False)
        loader = validation_loader(
            handle, self.parallelism, self.request.batch_size,
            transform=dataset.transform,
            worker_rows=self.trainer.local_rows(self.parallelism),
        )

        def stamping(rounds):
            for rb in rounds:
                self.heartbeat = time.time()
                yield rb

        with self.tracer.span("job.validate", service="worker",
                              job=self.job_id):
            acc, loss = self.trainer.evaluate_rounds(self._stacked_vars,
                                                     stamping(loader))
        dataset.set_mode(True)
        return acc, loss

    def _history_lists(self) -> dict:
        h = self.history
        return {
            "train_loss": list(h.train_loss),
            "validation_loss": list(h.validation_loss),
            "accuracy": list(h.accuracy),
            "parallelism": list(h.parallelism),
            "epoch_duration": list(h.epoch_duration),
            "worker_divergence": list(h.worker_divergence),
            "loss_spread": list(h.loss_spread),
            "round_skew": list(h.round_skew),
            "notes": list(h.notes),
        }

    def _join_checkpoint(self) -> None:
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None

    def _snapshot_reference(self):
        """Device->host copy of the reference model. COLLECTIVE in dist mode:
        every process must call it at the same point (the extraction is a
        computation over a non-fully-addressable array)."""
        if self.dist is not None and self.dist.size > 1:
            return self.trainer.replicated_reference(self._stacked_vars, self.parallelism)
        return self.trainer.reference_variables(self._stacked_vars)

    def _save_checkpoint(self, epoch: int) -> None:
        self.heartbeat = time.time()  # checkpoint phase: no rounds stamping
        try:
            with self.tracer.span("job.checkpoint", service="worker",
                                  job=self.job_id, epoch=epoch):
                # the device->host copy is synchronous (it must snapshot THIS
                # epoch's weights — and is a collective all processes join in
                # dist mode), but the npz write + retention prune run on a
                # background thread so the next epoch trains meanwhile; at
                # most one write is in flight (epoch ordering preserved).
                # Only the leader persists the snapshot.
                self._join_checkpoint()
                variables = self._snapshot_reference()
                if not self._leader:
                    return
                # mid-training serving snapshot (tuple assignment is atomic
                # under the GIL — the HTTP thread reads it)
                self._latest_snapshot = (variables, epoch)
                meta = {"request": self.request.to_dict(),
                        "history": self._history_lists()}

                def write():
                    try:
                        self.checkpoint_store.save(
                            self.job_id, variables, epoch=epoch, meta=meta
                        )
                        self.checkpoint_store.prune_epochs(
                            self.job_id, self.request.options.checkpoint_keep
                        )
                    except Exception:
                        log.exception("%s: async checkpoint write failed (non-fatal)",
                                      self.job_id)

                self._ckpt_thread = threading.Thread(
                    target=write, name=f"ckpt-{self.job_id}", daemon=True
                )
                self._ckpt_thread.start()
        except Exception:
            log.exception("%s: checkpoint save failed (non-fatal)", self.job_id)

    def _save_yield_checkpoint(self) -> None:
        """Yield checkpoint for a preempted job: the CURRENT reference weights
        tagged with the last completed epoch — resume then restarts the
        following epoch, identical semantics to a checkpoint_every save (a
        pre-existing checkpoint at that epoch is refreshed with the extra
        mid-epoch progress). Synchronous by design: the devices are released
        only after the checkpoint is durably published, and the store's
        tmp+rename publish is atomic, so even a hard kill mid-yield leaves
        either the old or the new checkpoint — never a torn one."""
        if not self._leader:
            return
        completed = len(self.history.train_loss)
        if completed <= 0:
            return  # nothing completed yet: resume restarts from scratch/prior
        self.heartbeat = time.time()
        try:
            with self.tracer.span("job.yield_checkpoint", service="worker",
                                  job=self.job_id, epoch=completed - 1):
                self.checkpoint_store.save(
                    self.job_id, self._final_variables, epoch=completed - 1,
                    meta={"request": self.request.to_dict(),
                          "history": self._history_lists()},
                )
        except Exception:
            log.exception("%s: yield checkpoint failed (resume falls back to "
                          "the previous checkpoint)", self.job_id)

    def _restore_latest(self) -> int:
        """Restore the newest checkpoint (selection shared with the SPMD
        engine, engine/resume.py). Returns the epoch to resume from (0 =
        nothing to restore).

        Multi-host: the LEADER selects the checkpoint and broadcasts the
        choice, then every process loads that exact tag from its own store
        (checkpoints are written on the leader, so multi-host resume requires
        the checkpoint store on a shared filesystem). A follower selecting
        independently could pick a different epoch — or nothing — and diverge
        the collective programs; a follower missing the chosen file fails
        loudly here instead."""
        from .resume import extend_history, select_resume_checkpoint

        if self.dist is not None and self.dist.size > 1:
            sel = None
            if self._leader:
                best = select_resume_checkpoint(self.checkpoint_store, self.job_id)
                if best is not None:
                    sel = {"epoch": best[0], "tag": best[1].tag}
            sel = self.dist.broadcast_obj(sel)
            if sel is None:
                return 0
            ck = self.checkpoint_store.restore(self.job_id, tag=sel["tag"])
            start_epoch = int(sel["epoch"])
        else:
            best = select_resume_checkpoint(self.checkpoint_store, self.job_id)
            if best is None:
                return 0
            start_epoch, ck = best
        self._stacked_vars = self.trainer.place_reference(ck.variables, self.parallelism)
        extend_history(self.history, ck)
        log.info("%s: resumed from checkpoint %s (epoch %d)", self.job_id, ck.tag, start_epoch)
        return start_epoch

    def _push_metrics(self, train_loss, val_loss, acc_pct, elapsed,
                      parallelism, epochs_done: int = -1) -> None:
        if self.on_metrics is None:
            return
        try:
            self.on_metrics(
                MetricUpdate(
                    job_id=self.job_id,
                    train_loss=float(train_loss),
                    validation_loss=float(val_loss) if val_loss is not None else 0.0,
                    accuracy=float(acc_pct) if acc_pct is not None else 0.0,
                    parallelism=parallelism,
                    epoch=int(epochs_done),
                    epoch_duration=float(elapsed),
                    round_seconds=[float(t) for t in self._last_round_times],
                    merge_seconds=float(self._last_merge_s),
                    round_divergence=[float(v) for v in self._last_divergence],
                    round_loss_spread=[float(v) for v in self._last_spread],
                    round_skew_ratio=float(self._last_round_skew),
                )
            )
        except Exception:
            log.exception("%s: metrics push failed (non-fatal)", self.job_id)

    # --- results ---

    @property
    def final_variables(self):
        """The trained reference model (fixes the reference's 'weights die with
        the job' gap — SURVEY §5 checkpoint/resume)."""
        return self._final_variables

    def generate(self, req) -> dict:
        """Serve a GenerateRequest from the live model (KV-cache decode,
        models.generation). Variables resolve like infer: worker-0 slab on a
        single host, the newest checkpoint snapshot multi-host."""
        import jax

        from ..models.generation import generate_from_request

        if self._stacked_vars is None and self._final_variables is None:
            raise KubeMLError(f"job {self.job_id} has no model yet", 400)
        if self._final_variables is not None:
            variables = self._final_variables
        elif self.dist is not None and self.dist.size > 1:
            snap = self._latest_snapshot or self._restore_serving_snapshot()
            if snap is None:
                raise KubeMLError(
                    f"job {self.job_id} is training multi-host and has no "
                    f"checkpoint yet; generation needs one", 409)
            variables = snap[0]
        else:
            variables = jax.tree.map(lambda v: v[0], self._stacked_vars)
        return generate_from_request(self.model.module, variables, req)

    def infer(self, x: np.ndarray):
        if self._stacked_vars is None:
            raise KubeMLError(f"job {self.job_id} has no model yet", 400)
        if self.dist is not None and self.dist.size > 1:
            # serving from the live global array would need a collective the
            # follower processes are not at (they are inside the training
            # loop), so multi-host jobs serve from the LATEST CHECKPOINTED
            # weights instead — the answer trails training by up to
            # checkpoint_every epochs (the reference's PS serves whatever the
            # model id resolves to mid-training, ml/pkg/scheduler/api.go:119-162,
            # which is equally stale between merges)
            if self._final_variables is not None:
                return self.trainer.infer_from_host(self._final_variables, x)
            snap = self._latest_snapshot
            if snap is None:
                snap = self._restore_serving_snapshot()
            if snap is None:
                every = self.request.options.checkpoint_every
                detail = (
                    f"retry after the first checkpoint (checkpoint_every={every})"
                    if every > 0 else
                    "it runs without checkpoints (checkpoint_every=0), so "
                    "inference is available once it finishes"
                )
                raise KubeMLError(
                    f"job {self.job_id} is training multi-host and has no "
                    f"checkpoint yet; {detail}", 409,
                )
            return self.trainer.infer_from_host(snap[0], x)
        return self.trainer.infer(self._stacked_vars, x)

    def _restore_serving_snapshot(self):
        """Fallback for mid-training serving after a runner restart: pull the
        newest epoch checkpoint off disk (leader-written)."""
        if not self._leader:
            return None
        try:
            from .resume import select_resume_checkpoint

            best = select_resume_checkpoint(self.checkpoint_store, self.job_id)
            if best is None:
                return None
            _, ck = best
            # ck.epoch is the epoch the weights were saved at (select's first
            # element is the RESUME epoch, one past it). Never clobber a
            # snapshot the training thread published while we read the disk —
            # it is at least as fresh as anything on disk.
            if self._latest_snapshot is None:
                self._latest_snapshot = (ck.variables, ck.epoch)
            return self._latest_snapshot
        except Exception:
            log.exception("%s: serving-snapshot restore failed", self.job_id)
            return None
