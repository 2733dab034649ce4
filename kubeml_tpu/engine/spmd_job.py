"""SPMDJob — control-plane job driving the SPMD (multi-axis mesh) engine.

The K-AVG job (engine/job.py) is the reference-parity path: elastic data
parallelism with local SGD. This job is the TPU-native extension for models
that need the full mesh — transformers/LLMs sharded over dp/tp/sp/ep — made
reachable through the same control plane: ``kubeml train --engine spmd
--mesh tp=2,sp=2`` deploys the same kind of function file, and datasets are
token-id arrays ``[N, L]`` in the same shard store.

Differences from the K-AVG job, by design:

* parallelism is the data-parallel axis of the mesh: elastic re-meshing
  between epochs resizes ``dp`` (more/fewer devices) while the model axes
  (tp/sp/ep) stay fixed — the scheduler round-trip is the same epoch-end hook
  the K-AVG job uses, and ``JobState.parallelism`` reports devices in use;
* the objective is next-token LM loss (kubeml_tpu.parallel.trainer.lm_loss)
  unless the model overrides ``per_sample_loss`` is irrelevant here — language
  modeling trains on the tokens themselves, labels in the store are ignored;
* validation reports eval loss AND next-token top-1 accuracy;
  ``goal_accuracy`` early-stops on that accuracy (%), and the SPMD-specific
  ``goal_loss`` early-stops on eval loss (a perplexity target P is
  ``goal_loss = ln(P)``).

The user's ``build()`` may read ``self.mesh`` (set by this job before the
module is built) to construct a mesh-aware module, e.g.
``CausalTransformer(mesh=self.mesh, sp_impl="ulysses")``.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import jax

import numpy as np

from ..api.errors import KubeMLError
from ..api.types import History, JobState, MetricUpdate, TrainRequest
from ..parallel.mesh import make_mesh, mesh_shape_for
from ..parallel.trainer import SPMDTrainer
from ..storage.checkpoint import FINAL_TAG, CheckpointStore
from ..storage.history import HistoryStore
from ..storage.store import ShardStore
from ..utils.tracing import get_tracer

log = logging.getLogger("kubeml.spmdjob")


def spmd_elastic_device_count(new_p: int, n_devices: int, model: int,
                              size: int = 1) -> int:
    """Legal device count for an elastic SPMD level: multiples of
    ``model * size`` so every host contributes equally AND each host's share
    is a multiple of the model-axis product — dp-major mesh order then keeps
    every tp/sp/ep group inside one host, so their per-step collectives stay
    on ICI. (NOT lcm(model, size): lcm(2,2)=2 would let a tp pair straddle
    hosts and ride DCN every matmul.)"""
    base = max(1, model) * max(1, size)
    return max(base, (min(new_p, n_devices) // base) * base)


class SPMDJob:
    """Same lifecycle surface as TrainJob (train/stop/state/infer) over the
    SPMD engine."""

    def __init__(
        self,
        job_id: str,
        request: TrainRequest,
        model,
        store: Optional[ShardStore] = None,
        history_store: Optional[HistoryStore] = None,
        checkpoint_store: Optional[CheckpointStore] = None,
        on_epoch_end=None,  # scheduler hook driving elastic dp re-meshing
        on_metrics=None,
        devices=None,
        seed: int = 0,
        dist=None,
    ):
        # multi-controller context: every process runs this same job over one
        # GLOBAL mesh; each host feeds the full batch (XLA takes the local
        # shards), control decisions are leader-broadcast, and parameter
        # placement goes through jitted programs (a host cannot device_put
        # onto chips it does not address). Stop requests take effect at epoch
        # boundaries in dist mode (a mid-epoch break on one process would
        # strand the others in a collective).
        if dist is None and jax.process_count() > 1:
            from ..parallel.distributed import get_dist_context

            dist = get_dist_context()
        self.dist = dist
        self._leader = dist is None or dist.is_leader
        self.job_id = job_id
        self.request = request
        self.model = model
        self.store = store or ShardStore()
        self.history_store = history_store or HistoryStore()
        self._checkpoint_store = checkpoint_store
        self.on_epoch_end = on_epoch_end
        self.on_metrics = on_metrics
        self.seed = seed
        self.tracer = get_tracer()

        self._all_devices = list(devices if devices is not None else jax.devices())
        shape = mesh_shape_for(len(self._all_devices),
                               **(request.options.mesh_shape or {}))
        # model axes are fixed for the job's life; elasticity moves dp only
        self._model_axes = {ax: s for ax, s in shape.items() if ax != "dp"}
        self.mesh = make_mesh(shape=shape, devices=self._all_devices)
        # the user's build() may read self.mesh to construct a mesh-aware module
        model.mesh = self.mesh
        self.trainer = self._make_trainer(self.mesh)

        self.history = History(id=job_id, task={"request": request.to_dict()})
        self.stop_event = threading.Event()
        # checkpoint-and-yield (multi-tenant preemption): preempt() rides the
        # stop machinery — same boundaries, same dist broadcast — but the
        # exit writes a resume checkpoint instead of the final export and
        # reports the `preempted` terminal status
        self.preempt_event = threading.Event()
        self.preempt_requested_at: Optional[float] = None
        # progress stamp for the PS heartbeat monitor (function guardrails).
        # heartbeat_cold doubles the monitor's allowance while the first
        # step's XLA compile runs (minutes on chip); cleared after it lands
        self.heartbeat = time.time()
        self.heartbeat_cold = True
        self.exit_error: Optional[str] = None
        self._dataset_handle = None
        # live inference and a donating train step must not touch the same
        # buffers concurrently (donation invalidates the inputs)
        self._step_lock = threading.Lock()
        # cached jitted identities for dist-mode placement/gather per mesh
        self._identity_cache: dict = {}

    def _make_trainer(self, mesh) -> SPMDTrainer:
        return SPMDTrainer(
            self.model.module,
            mesh,
            optimizer=self.model.configure_optimizers(),
            precision=self.request.options.precision,
            donate=self.request.options.donate,
            # the KubeModel device-side input pipeline (runtime/model.py
            # preprocess) applies under this engine too, not just K-AVG
            input_transform=self.model.preprocess,
        )

    # --- TrainJob surface ---

    def stop(self) -> None:
        self.stop_event.set()

    def preempt(self) -> None:
        """Checkpoint-and-yield: exit at the next step/epoch boundary, write
        a resume checkpoint, report the ``preempted`` status. Idempotent."""
        if self.preempt_requested_at is None:
            self.preempt_requested_at = time.time()
        self.preempt_event.set()
        self.stop_event.set()

    @property
    def preempted(self) -> bool:
        return self.preempt_event.is_set()

    @property
    def state(self) -> JobState:
        return JobState(parallelism=self.mesh.devices.size)

    @property
    def checkpoint_store(self) -> CheckpointStore:
        if self._checkpoint_store is None:
            self._checkpoint_store = CheckpointStore()
        return self._checkpoint_store

    # --- data ---

    @property
    def _handle(self):
        if self._dataset_handle is None:
            self._dataset_handle = self.store.get(self.request.dataset)
        return self._dataset_handle

    def _token_batches(self, split: str, batch: int):
        """Global [batch, L] token slabs; remainder rows beyond a dp-divisible
        batch are dropped (SPMD batches must tile the dp axis)."""
        n = self._handle.num_samples(split)
        x = self._handle.raw(split, "data")
        dp = int(self.mesh.shape.get("dp", 1))
        batch = max(dp, (batch // dp) * dp)
        for a in range(0, n - batch + 1, batch):
            yield np.ascontiguousarray(x[a : a + batch]).astype(np.int32)

    # --- main loop ---

    def train(self) -> History:
        req = self.request
        opts = req.options
        try:
            first = next(self._token_batches("train", req.batch_size), None)
            if first is None:
                raise KubeMLError(
                    f"dataset {req.dataset!r} has fewer than one dp-divisible "
                    f"batch of {req.batch_size}"
                )
            rng = jax.random.PRNGKey(self.seed)
            self.trainer.init(rng, first)
            log.info("%s: SPMD job on mesh %s", self.job_id, dict(self.mesh.shape))

            start_epoch = 0
            if opts.resume:
                start_epoch = self._restore_latest()

            dist_multi = self.dist is not None and self.dist.size > 1
            for epoch in range(start_epoch, req.epochs):
                stop = self.stop_event.is_set()
                if dist_multi:
                    # leader's stop broadcast so no process leaves the
                    # lockstep loop while others still issue collectives
                    stop, _ = self.dist.broadcast_flags(stop=stop)
                    if stop:
                        self.stop_event.set()
                if stop:
                    break
                t0 = time.time()
                losses = []
                with self.tracer.span("job.epoch", service="worker",
                                      job=self.job_id, epoch=epoch,
                                      engine="spmd"):
                    for i, batch in enumerate(self._token_batches("train", req.batch_size)):
                        if self.stop_event.is_set() and not dist_multi:
                            # dist mode defers stop to the epoch boundary —
                            # a one-sided mid-epoch break would strand the
                            # other processes in a collective
                            break
                        step_rng = jax.random.fold_in(rng, epoch * 100003 + i)
                        with self._step_lock:
                            losses.append(self.trainer.train_step(batch, step_rng))
                        self.heartbeat = time.time()
                        self.heartbeat_cold = False  # first compile is done
                if not losses:
                    break  # stopped mid-epoch
                train_loss = float(np.mean([float(l) for l in losses]))
                elapsed = time.time() - t0

                used_devices = self.mesh.devices.size

                # validation is skipped mid-yield — SINGLE-HOST only: in dist
                # mode preempt_event may be set on the leader alone mid-epoch
                # (stop broadcasts at the loop top), and validation is a
                # collective, so a one-sided skip would strand the followers
                val_loss = None
                acc_pct = None
                skip_val = self.preempt_event.is_set() and not dist_multi
                if (opts.validate_every > 0 and not skip_val
                        and (epoch + 1) % opts.validate_every == 0):
                    val_loss, token_acc = self._validate()
                    if token_acc is not None:
                        acc_pct = token_acc * 100.0

                self.history.append_epoch(
                    train_loss=train_loss,
                    parallelism=used_devices,
                    duration=elapsed,
                    validation_loss=val_loss,
                    accuracy=acc_pct,
                )
                if self._leader:
                    self._push_metrics(train_loss, val_loss, acc_pct, elapsed,
                                       used_devices, epoch + 1)
                log.info("%s: epoch %d/%d loss=%.4f val=%s acc=%s %.2fs",
                         self.job_id, epoch + 1, req.epochs, train_loss,
                         f"{val_loss:.4f}" if val_loss is not None else "-",
                         f"{acc_pct:.2f}%" if acc_pct is not None else "-",
                         elapsed)
                if opts.checkpoint_every > 0 and (epoch + 1) % opts.checkpoint_every == 0:
                    self._save_checkpoint(epoch)

                # goal metrics (K-AVG parity job.go:49-54 + the SPMD-native
                # eval-loss goal: a perplexity target P is goal_loss = ln P)
                if acc_pct is not None and acc_pct >= opts.goal_accuracy:
                    log.info("%s: goal accuracy %.2f%% reached (%.2f%%)",
                             self.job_id, opts.goal_accuracy, acc_pct)
                    break
                if (opts.goal_loss > 0.0 and val_loss is not None
                        and val_loss <= opts.goal_loss):
                    log.info("%s: goal eval loss %.4f reached (%.4f)",
                             self.job_id, opts.goal_loss, val_loss)
                    break

                # elastic dp re-meshing between epochs (the same scheduler
                # hook the K-AVG job uses; parallelism = devices in use).
                # The leader asks; the answer is broadcast so every process
                # re-meshes identically.
                if not opts.static_parallelism and (
                    self.on_epoch_end is not None or dist_multi
                ):
                    new_p = None
                    if self._leader and self.on_epoch_end is not None:
                        new_p = self.on_epoch_end(
                            JobState(parallelism=used_devices, elapsed_time=elapsed)
                        )
                    if dist_multi:
                        _, p = self.dist.broadcast_flags(parallelism=new_p or 0)
                        new_p = p or None
                    if new_p:
                        self._maybe_remesh(new_p, rng, first)

            # the save branches below contain COLLECTIVES (gathers, sharded
            # barriers): in dist mode every process must take the same one,
            # and mid-epoch preempt_event is leader-local — broadcast the
            # leader's decision first
            preempted = self.preempt_event.is_set()
            if dist_multi:
                preempted = bool(self.dist.broadcast_obj(
                    preempted if self._leader else None))
                if preempted:
                    self.preempt_event.set()
            if preempted:
                # checkpoint-and-yield: persist the current params as the
                # newest epoch checkpoint (resume restarts the next epoch);
                # the final export belongs to a COMPLETED job only
                if self.history.train_loss:
                    self._save_checkpoint(len(self.history.train_loss) - 1)
            elif opts.save_model and self.history.train_loss:
                if opts.sharded_checkpoints:
                    # gather-free FINAL export: the rationale for sharded
                    # checkpoints ("no host ever materializes a full leaf")
                    # must hold for the model the job LEAVES BEHIND too —
                    # the PS serves it by restoring straight onto a serving
                    # mesh (VERDICT r4 next-1: trains-big must serve-big).
                    # FINAL records the completed-epoch count as its epoch
                    # (the next start index — resume semantics match
                    # engine/resume.py and _restore_sharded)
                    self._save_checkpoint_sharded(
                        len(self.history.train_loss), tag=FINAL_TAG)
                else:
                    final = self._host_params()  # collective in dist mode
                    if self._leader:
                        self.checkpoint_store.save(
                            self.job_id, final,
                            epoch=len(self.history.train_loss), tag=FINAL_TAG,
                            meta={"request": req.to_dict(),
                                  "history": self._history_lists()},
                        )
        except KubeMLError as e:
            self.exit_error = e.message
            raise
        except Exception as e:
            self.exit_error = str(e)
            raise KubeMLError(f"job {self.job_id} failed: {e}") from e
        finally:
            if self.exit_error is not None and isinstance(self.history.task, dict):
                self.history.task["error"] = self.exit_error
            if self._leader:
                self.history_store.save(self.history)
        return self.history

    # --- internals ---

    def _restore_latest(self) -> int:
        """Restore the newest checkpoint into the sharded params (selection
        shared with the K-AVG engine, engine/resume.py). Optimizer state
        restarts — consistent with K-AVG's per-sync optimizer reset."""
        import flax.core.meta as meta

        from .resume import extend_history, select_resume_checkpoint

        if self.request.options.sharded_checkpoints:
            start = self._restore_sharded()
            if start >= 0:
                return start
            # fall through: a job may upgrade to sharded checkpoints while
            # resuming from an older flat checkpoint
        if self.dist is not None and self.dist.size > 1:
            # leader selects; every process loads the SAME tag from its own
            # (shared-filesystem) store — independent selection could diverge
            # the collective programs (same protocol as the K-AVG job)
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
        unboxed = meta.unbox(self.trainer.params)
        shardings = jax.tree.map(lambda x: x.sharding, unboxed)
        placed = self._place(ck.variables, shardings)
        self.trainer.params = meta.replace_boxed(self.trainer.params, placed)
        extend_history(self.history, ck)
        log.info("%s: resumed from checkpoint %s (epoch %d)", self.job_id,
                 ck.tag, start_epoch)
        return start_epoch

    def _validate(self):
        """Mean (eval loss, next-token accuracy) over the test split."""
        # validation runs no train steps: stamp per eval batch so a sweep
        # longer than the function timeout never reads as a hang (a single
        # eval BATCH hung inside a traced program still trips the monitor)
        self.heartbeat = time.time()
        losses, accs = [], []
        with self.tracer.span("job.validate", service="worker",
                              job=self.job_id, engine="spmd"):
            for batch in self._token_batches("test", self.request.batch_size):
                l, a = self.trainer.eval_metrics(batch)  # enters the mesh itself
                self.heartbeat = time.time()
                losses.append(l)
                accs.append(a)
        if not losses:
            return None, None
        return float(np.mean(losses)), float(np.mean(accs))

    def _remesh_devices(self, new_p: int):
        """Pick the device block for an elastic level. Multi-process: every
        host must contribute equally (a process with no devices in the mesh
        could not legally join the computation) AND each host's share must be
        a multiple of the model-axis product — dp-major mesh order then keeps
        every tp/sp/ep group inside one host, so their per-step collectives
        stay on ICI (base = model * n_processes, NOT lcm: lcm(2,2)=2 would
        let a tp pair straddle hosts and ride DCN every matmul)."""
        model = max(1, int(np.prod(list(self._model_axes.values()))))
        size = self.dist.size if (self.dist is not None and self.dist.size > 1) else 1
        devices_new = spmd_elastic_device_count(
            new_p, len(self._all_devices), model, size
        )
        if size == 1:
            return devices_new, self._all_devices[:devices_new], model
        per = devices_new // size
        chosen = []
        for pr in range(size):
            local = [d for d in self._all_devices if d.process_index == pr]
            chosen.extend(local[:per])
        return devices_new, chosen, model

    def _jit_identity(self, purpose: str, shardings):
        """Cached jitted identity per (mesh, purpose): a fresh lambda each
        call would retrace + recompile the placement/gather program on every
        checkpoint/remesh — the synchronous-compile class round 2 removed."""
        key = (self.mesh, purpose)
        fn = self._identity_cache.get(key)
        if fn is None:
            fn = jax.jit(lambda v: v, out_shardings=shardings)
            self._identity_cache[key] = fn
        return fn

    def _place(self, host_tree, shardings):
        """Place identical host values onto sharded devices. Multi-process a
        raw device_put cannot target non-addressable chips — placement runs
        through a jitted identity with out_shardings instead."""
        if self.dist is not None and self.dist.size > 1:
            with jax.set_mesh(self.mesh):
                return self._jit_identity("place", shardings)(host_tree)
        return jax.device_put(host_tree, shardings)

    def _maybe_remesh(self, new_p: int, rng, sample_batch) -> None:
        """Elastic dp resize between epochs: keep the model axes, change the
        device count. The params host-bounce onto the new mesh (the same
        replicate-then-place move the K-AVG multi-host resize makes) and the
        optimizer state restarts — consistent with K-AVG's per-sync optimizer
        reset (reference semantics network.py:121-128). The step recompiles
        per mesh shape; the persistent XLA cache makes revisited levels a
        read. COLLECTIVE in dist mode (host-params gather + jitted placement)."""
        devices_new, chosen, model = self._remesh_devices(new_p)
        if devices_new == self.mesh.devices.size:
            return
        dp_new = devices_new // model
        log.info("%s: elastic re-mesh %d -> %d devices (dp=%d, model axes %s)",
                 self.job_id, self.mesh.devices.size, devices_new, dp_new,
                 self._model_axes or "{}")
        host = self._host_params()
        shape = dict(self._model_axes, dp=dp_new)
        self.mesh = make_mesh(shape=shape, devices=chosen)
        # rebuild the module against the new mesh: a stale capture (sp
        # shard_map, pipeline sharding constraints) would issue collectives
        # sized for the old device set
        self.model.rebind_mesh(self.mesh)
        with self._step_lock:
            self.trainer = self._make_trainer(self.mesh)
            self.trainer.init(rng, sample_batch)  # shardings + fresh opt state
            import flax.core.meta as meta

            unboxed = meta.unbox(self.trainer.params)
            shardings = jax.tree.map(lambda x: x.sharding, unboxed)
            placed = self._place(host, shardings)
            self.trainer.params = meta.replace_boxed(self.trainer.params, placed)

    def _host_params(self):
        """Host copy of the params. COLLECTIVE in dist mode: every process
        must call it at the same point (replicated gather through jit — a
        host fetch of a non-fully-addressable array would hang)."""
        import flax.linen as nn
        from jax.sharding import NamedSharding, PartitionSpec as P

        unboxed = nn.meta.unbox(self.trainer.params)
        if self.dist is not None and self.dist.size > 1:
            replicated = NamedSharding(self.mesh, P())
            rep_shardings = jax.tree.map(lambda _: replicated, unboxed)
            with jax.set_mesh(self.mesh):
                unboxed = self._jit_identity("gather", rep_shardings)(unboxed)
        return jax.tree.map(np.asarray, unboxed)

    def _history_lists(self) -> dict:
        h = self.history
        return {
            "train_loss": list(h.train_loss),
            "validation_loss": list(h.validation_loss),
            "accuracy": list(h.accuracy),
            "parallelism": list(h.parallelism),
            "epoch_duration": list(h.epoch_duration),
        }

    def _save_checkpoint(self, epoch: int) -> None:
        self.heartbeat = time.time()  # checkpoint phase: no steps stamping
        if self.request.options.sharded_checkpoints:
            self._save_checkpoint_sharded(epoch)
            return
        # the gather is COLLECTIVE in dist mode and must stay OUTSIDE the
        # non-fatal guard: swallowing a one-sided fault here would let this
        # process run ahead while its peers sit in the gather — the hang the
        # follower's failure semantics exist to prevent. Only the disk write
        # is non-fatal.
        with self.tracer.span("job.checkpoint", service="worker",
                              job=self.job_id, epoch=epoch):
            variables = self._host_params()
            if not self._leader:
                return
            try:
                self.checkpoint_store.save(
                    self.job_id, variables, epoch=epoch,
                    meta={"request": self.request.to_dict(),
                          "history": self._history_lists()},
                )
                self.checkpoint_store.prune_epochs(
                    self.job_id, self.request.options.checkpoint_keep
                )
            except Exception:
                log.exception("%s: checkpoint save failed (non-fatal)", self.job_id)

    def _sharded_store(self):
        from ..storage.sharded_checkpoint import ShardedCheckpointStore

        return ShardedCheckpointStore(root=self.checkpoint_store.root)

    def _save_checkpoint_sharded(self, epoch: int,
                                 tag: Optional[str] = None) -> None:
        """Gather-free checkpoint: every process writes only the leaf slices
        its devices own (storage.sharded_checkpoint). COLLECTIVE in dist mode
        (the pre-manifest barrier); faults are fatal for the same one-sided
        reasons as the gather above. ``tag`` defaults to the epoch tag; the
        end-of-job export passes FINAL_TAG."""
        import flax.linen as nn

        with self.tracer.span("job.checkpoint", service="worker",
                              job=self.job_id, epoch=epoch,
                              sharded=True):
            barrier = (self.dist.barrier
                       if self.dist is not None and self.dist.size > 1 else None)
            self._sharded_store().save(
                self.job_id, nn.meta.unbox(self.trainer.params),
                epoch=epoch, tag=tag or f"ep{epoch:05d}",
                meta={"request": self.request.to_dict(),
                      "history": self._history_lists()},
                barrier=(lambda t: barrier(f"{t}/{epoch}"))
                if barrier is not None else None,
            )

    def _restore_sharded(self) -> int:
        """Resume from the newest SHARDED checkpoint onto the CURRENT mesh
        (which may have a different dp level than the writer's): each process
        reads only the slices its own devices need. Returns the start epoch,
        or -1 when no sharded checkpoint exists."""
        import flax.core.meta as meta

        from .resume import extend_history

        store = self._sharded_store()
        tags = store.tags(self.job_id)
        if not tags:
            return -1
        # mirror engine/resume.select_resume_checkpoint: an epoch tag epN
        # resumes at N+1; the FINAL export records its completed-epoch count
        # (already the next start index). The furthest start wins — naive
        # tags[-1] would pick 'final' lexicographically and double-advance
        # the start epoch, silently skipping an epoch of requested training.
        candidates = []  # (start_epoch, tag)
        ep_tags = sorted(t for t in tags if t.startswith("ep"))
        if ep_tags:
            last = ep_tags[-1]
            candidates.append(
                (int(store.read_manifest(self.job_id, last)["epoch"]) + 1,
                 last))
        if FINAL_TAG in tags:
            candidates.append(
                (int(store.read_manifest(self.job_id, FINAL_TAG)["epoch"]),
                 FINAL_TAG))
        if not candidates:
            return -1
        start, tag = max(candidates)
        unboxed = meta.unbox(self.trainer.params)
        shardings = jax.tree.map(lambda x: x.sharding, unboxed)
        ck = store.restore(self.job_id, tag, shardings=shardings)
        self.trainer.params = meta.replace_boxed(self.trainer.params, ck.variables)
        extend_history(self.history, ck)
        log.info("%s: resumed from sharded checkpoint %s (epoch %d)",
                 self.job_id, tag, start)
        return start

    def _push_metrics(self, train_loss, val_loss, acc_pct, elapsed,
                      parallelism, epochs_done: int = -1) -> None:
        if self.on_metrics is None:
            return
        try:
            overflow = -1.0
            last = getattr(self.trainer, "last_moe_overflow", None)
            if last is not None:
                overflow = float(last)  # -1 sentinel for dense models
            self.on_metrics(MetricUpdate(
                job_id=self.job_id, train_loss=float(train_loss),
                validation_loss=float(val_loss) if val_loss is not None else 0.0,
                accuracy=float(acc_pct) if acc_pct is not None else 0.0,
                parallelism=parallelism,
                epoch=int(epochs_done),
                epoch_duration=float(elapsed),
                moe_overflow=overflow,
            ))
        except Exception:
            log.exception("%s: metrics push failed (non-fatal)", self.job_id)

    def infer(self, x: np.ndarray):
        """Greedy next-token ids for each position of the given token batch."""
        if self.trainer.params is None:
            raise KubeMLError(f"job {self.job_id} has no model yet", 400)
        if self.dist is not None and self.dist.size > 1:
            # serving mid-training would need a collective the followers are
            # not at; the finished model serves from the final checkpoint
            raise KubeMLError(
                f"job {self.job_id} is training multi-host; inference is "
                f"served from its checkpoint after it finishes", 409
            )
        import jax.numpy as jnp

        with self._step_lock, jax.set_mesh(self.mesh):
            tokens = self.model.preprocess(jnp.asarray(np.asarray(x), jnp.int32))
            logits = self.model.module.apply(self.trainer.params, tokens, train=False)
            return np.asarray(jnp.argmax(logits, axis=-1))

    def generate(self, req) -> dict:
        """Serve a GenerateRequest from the live model (KV-cache decode,
        models.generation). Single-host only, same as infer."""
        if self.trainer.params is None:
            raise KubeMLError(f"job {self.job_id} has no model yet", 400)
        if self.dist is not None and self.dist.size > 1:
            raise KubeMLError(
                f"job {self.job_id} is training multi-host; generation is "
                f"served from its checkpoint after it finishes", 409
            )
        import jax

        from ..models.generation import generate_from_request

        with self._step_lock, jax.set_mesh(self.mesh):
            return generate_from_request(self.model.module,
                                         self.trainer.params, req)
