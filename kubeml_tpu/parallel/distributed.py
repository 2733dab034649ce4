"""Multi-host / multi-slice distributed setup.

The reference scales by adding serverless function invocations behind one
Redis; its "distributed backend" is HTTP + Redis blobs (SURVEY §2.4: no
NCCL/MPI). The TPU-native equivalent is JAX's multi-controller runtime: every
TPU-VM host runs the same program, ``jax.distributed`` wires the processes,
and collectives ride ICI within a slice and DCN across slices. This module
owns that wiring:

* :func:`init_distributed` — idempotent ``jax.distributed.initialize`` with
  env-driven defaults (``KUBEML_COORDINATOR``, ``KUBEML_NUM_PROCESSES``,
  ``KUBEML_PROCESS_ID``; on Cloud TPU all three auto-detect).
* :func:`global_mesh` — a mesh over ALL global devices. On multi-slice
  topologies the data-parallel axis is laid out across slices (DCN) and the
  model axes (tp/sp/ep/pp) stay within a slice (ICI), the scaling-book
  hybrid-mesh recipe, via ``mesh_utils.create_hybrid_device_mesh``; on a
  single slice / single host it degrades to the plain local mesh.
* :func:`local_batch_slice` — which rows of a global batch this process feeds
  (hosts feed only their addressable shard of a globally-sharded array).
* :class:`DistContext` — leader/follower coordination for multi-controller
  training: every process runs the same jitted programs in the same order;
  dynamic control decisions (stop, elastic parallelism, job announcements) are
  made on process 0 and broadcast over the host channel so the programs never
  diverge. The TPU-native counterpart of the reference's PS→job-pod HTTP
  control flow (reference: ml/pkg/ps/job_pod.go:96-217, train/api.go:69-96).
* :func:`worker_device_count` / :func:`local_worker_rows` — pure layout math
  for the K-AVG worker axis across processes (unit-testable without devices).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from .mesh import AXIS_ORDER, make_mesh, mesh_shape_for

log = logging.getLogger("kubeml.distributed")

_initialized = False


def _pod_hosts(env) -> int:
    """How many worker hosts the Cloud TPU environment names (0 when it
    names none): the comma-separated host lists libtpu itself reads, or a
    multislice coordinator (always more than one host)."""
    if env.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return 2
    for var in ("TPU_WORKER_HOSTNAMES", "TPU_PROCESS_ADDRESSES"):
        hosts = [h for h in env.get(var, "").split(",") if h.strip()]
        if hosts:
            return len(hosts)
    return 0


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize the multi-controller runtime; returns True when distributed.

    Single-process (no coordinator configured, one process) is a no-op —
    the same binary serves laptop CPU, one TPU VM, and a multi-host pod.
    """
    global _initialized
    if _initialized:
        return jax.process_count() > 1
    coordinator_address = coordinator_address or os.environ.get("KUBEML_COORDINATOR")
    if num_processes is None:
        env = os.environ.get("KUBEML_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("KUBEML_PROCESS_ID")
        process_id = int(env) if env else None
    if coordinator_address is None and num_processes in (None, 1):
        # no explicit config: on a Cloud TPU pod — an environment that names
        # MORE THAN ONE worker host — the no-arg initialize() auto-detects
        # the process group from the TPU metadata, and a failure there is
        # fatal (N hosts each booting their own single-process cluster is
        # not a degraded pod, it is N wrong clusters). A single TPU VM
        # exports the same variables with one host in them; it, laptops and
        # CI stay single-process.
        if _pod_hosts(os.environ) > 1:
            jax.distributed.initialize()
            _initialized = True
            log.info("distributed (auto-detected TPU pod): process %d/%d",
                     jax.process_index(), jax.process_count())
            return jax.process_count() > 1
        log.info("single-process mode (no KUBEML_COORDINATOR)")
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    log.info("distributed: process %d/%d, %d local + %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())
    return True


def num_slices() -> int:
    """Number of ICI-connected slices among the global devices (1 when the
    backend does not report slice topology, e.g. CPU)."""
    slices = {getattr(d, "slice_index", 0) for d in jax.devices()}
    return max(1, len(slices))


def hybrid_mesh_shapes(
    shape: Dict[str, int], n_slices: int, n_devices: int, dcn_axis: str = "dp"
) -> Tuple[Tuple[str, ...], list, list]:
    """Pure layout math for the DCN-aware hybrid mesh: (axis names,
    per-slice ICI shape, across-slice DCN shape). Factored out of
    :func:`global_mesh` so multi-slice layouts are testable without multi-slice
    hardware (CPU reports one slice)."""
    if dcn_axis not in shape:
        raise ValueError(
            f"dcn_axis {dcn_axis!r} missing from mesh shape {shape}; on a "
            f"{n_slices}-slice topology one axis must span the slices"
        )
    per_slice = n_devices // n_slices
    model = int(np.prod([s for ax, s in shape.items() if ax != dcn_axis]))
    if per_slice % model != 0:
        raise ValueError(
            f"model axes use {model} devices which does not divide the "
            f"{per_slice}-device slice; keep tp/sp/ep/pp within one slice"
        )
    if shape[dcn_axis] % n_slices != 0:
        raise ValueError(
            f"{dcn_axis}={shape[dcn_axis]} must be divisible by the "
            f"{n_slices} slices it spans"
        )
    names = tuple(ax for ax in AXIS_ORDER if ax in shape)
    ici_shape = [shape[ax] // n_slices if ax == dcn_axis else shape[ax] for ax in names]
    dcn_shape = [n_slices if ax == dcn_axis else 1 for ax in names]
    return names, ici_shape, dcn_shape


def global_mesh(
    shape: Optional[Dict[str, int]] = None,
    dcn_axis: str = "dp",
    **axes: int,
) -> Mesh:
    """Mesh over all global devices with DCN-aware layout.

    Model axes (tp/sp/ep/pp) must fit within one slice — their collectives are
    in the steady-state critical path and belong on ICI. The ``dcn_axis``
    (default ``dp``: gradient/weight averaging once per step or per K steps)
    spans slices. Falls back to a plain mesh on single-slice/CPU topologies.
    """
    devices = jax.devices()
    n_slices = num_slices()
    if shape is None:
        shape = mesh_shape_for(len(devices), **axes)
    if n_slices == 1:
        return make_mesh(shape=shape, devices=devices)

    from jax.experimental import mesh_utils

    names, ici_shape, dcn_shape = hybrid_mesh_shapes(
        shape, n_slices, len(devices), dcn_axis
    )
    grid = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=devices
    )
    return Mesh(grid, names)


def worker_device_count(n_workers: int, n_devices: int, n_procs: int = 1) -> int:
    """Devices the 1-D ``worker`` mesh should span.

    Single-process: the largest ``d <= n_devices`` dividing ``n_workers``
    (workers pack onto chips when N > devices). Multi-process: additionally
    ``d`` must be a multiple of ``n_procs`` so every process contributes an
    equal block of the worker axis — a process with no addressable shard could
    not legally join the computation. Requires ``n_workers % n_procs == 0``
    (the scheduler only proposes such levels in distributed mode)."""
    if n_procs > 1:
        if n_workers % n_procs != 0:
            raise ValueError(
                f"n_workers={n_workers} must be a multiple of the "
                f"{n_procs} host processes"
            )
        d = min(n_workers, (n_devices // n_procs) * n_procs)
        while d > n_procs and (n_workers % d != 0 or d % n_procs != 0):
            d -= n_procs
        return max(d, n_procs)
    d = min(n_workers, n_devices)
    while d > 1 and n_workers % d != 0:
        d -= 1
    return d


def pick_worker_devices(
    n_workers: int, devices: List[jax.Device], n_procs: int = 1
) -> List[jax.Device]:
    """The device block for the worker mesh, process-major so contiguous
    worker rows land on one process (each process feeds only its rows)."""
    d = worker_device_count(n_workers, len(devices), n_procs)
    if n_procs <= 1:
        return devices[:d]
    per = d // n_procs
    chosen: List[jax.Device] = []
    for p in range(n_procs):
        local = [dv for dv in devices if dv.process_index == p]
        if len(local) < per:
            raise ValueError(
                f"process {p} has {len(local)} devices, need {per} for the "
                f"worker mesh"
            )
        chosen.extend(local[:per])
    return chosen


def local_worker_rows(n_workers: int, rank: int, size: int) -> Tuple[int, int]:
    """[start, end) rows of the ``[N, ...]`` worker axis this process feeds.

    With the process-major device block from :func:`pick_worker_devices`,
    worker rows split into ``size`` equal contiguous blocks."""
    if size <= 1:
        return 0, n_workers
    if n_workers % size != 0:
        raise ValueError(
            f"n_workers={n_workers} must be a multiple of {size} processes"
        )
    per = n_workers // size
    return rank * per, (rank + 1) * per


class DistContext:
    """Host-channel coordination between the leader (process 0) and followers.

    Decisions travel through the jax.distributed coordination service's
    key-value store — a pure HOST channel. They deliberately do NOT use device
    collectives (``multihost_utils.broadcast_one_to_all``): with JAX's async
    dispatch a host-issued broadcast program can hit the wire while a training
    step's collectives from a *different* device subset are still in flight,
    and the two interleave on the same transport (observed as gloo frame-size
    mismatches on CPU). A host-side KV read can never race device traffic.

    In multi-process mode every process must call each method at the same
    point in its program (the leader writes sequence-numbered keys, followers
    read them in order). Single-process instances short-circuit, so the same
    engine code path runs in tests and the driver's multichip dry-run without
    a process group.

    Use :func:`get_dist_context` — the sequence counter must be shared
    process-wide, so ad-hoc instances would desynchronize the key stream."""

    def __init__(self):
        import threading

        self.rank = jax.process_index()
        self.size = jax.process_count()
        self._seq = 0
        self._lock = threading.Lock()
        self._client = None
        if self.size > 1:
            from jax._src import distributed as _jdist

            self._client = _jdist.global_state.client
            if self._client is None:
                raise RuntimeError(
                    "jax.distributed is multi-process but has no coordination "
                    "client; call init_distributed() first"
                )

    @property
    def is_leader(self) -> bool:
        return self.rank == 0

    # leader-side lazy deletion window for broadcast keys: key N-LAG is
    # deleted when key N is written, bounding coordinator memory on long runs.
    # Followers consume keys in order and only lag the leader by host-loop
    # skew within an epoch (both sides run the same program sequence and
    # resynchronize at every blocking loss fetch), orders of magnitude less
    # than this window.
    BCAST_GC_LAG = 8192

    def _next_key(self) -> Tuple[str, int]:
        with self._lock:
            seq = self._seq
            self._seq += 1
        return f"kubeml/bcast/{seq}", seq

    def broadcast_obj(self, obj=None, poll_ms: int = 10_000):
        """Broadcast a JSON-serializable object from the leader. Followers
        block until the leader publishes the next sequence-numbered key (no
        deadline — the leader may legitimately be idle between jobs)."""
        if self.size == 1:
            return obj
        key, seq = self._next_key()
        if self.is_leader:
            self._client.key_value_set(key, json.dumps(obj))
            if seq >= self.BCAST_GC_LAG:
                try:
                    self._client.key_value_delete(
                        f"kubeml/bcast/{seq - self.BCAST_GC_LAG}"
                    )
                except Exception:  # GC is best-effort
                    pass
            return obj
        while True:
            try:
                return json.loads(self._client.blocking_key_value_get(key, poll_ms))
            except Exception as e:  # jaxlib raises a generic RuntimeError
                if "DEADLINE_EXCEEDED" in str(e):
                    continue  # leader not there yet; keep waiting
                raise

    def broadcast_flags(self, stop: bool = False, parallelism: int = 0) -> Tuple[bool, int]:
        """Per-round/per-epoch control decisions; followers' arguments are
        ignored (rank 0's values win)."""
        out = self.broadcast_obj({"s": int(stop), "p": int(parallelism)})
        return bool(out["s"]), int(out["p"])

    # --- point-to-point KV (job-start acknowledgements) ---

    def put(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value)

    def barrier(self, tag: str, timeout_s: float = 600.0) -> None:
        """Host-channel barrier: returns once every process has announced
        ``tag``. Keys carry a per-call sequence number (aligned across
        processes by the same same-order-calls discipline the broadcast
        stream relies on), so a REUSED tag — e.g. a resumed job rewriting the
        same epoch checkpoint — can't satisfy a later barrier with a stale
        announcement. Raises on timeout — a barrier that silently gives up
        would let the leader publish a manifest over missing shards."""
        if self.size == 1:
            return
        with self._lock:
            seq = self._barrier_seq = getattr(self, "_barrier_seq", -1) + 1
        self.put(f"kubeml/barrier/{seq}/{tag}/{self.rank}", "1")
        for r in range(self.size):
            if self.get(f"kubeml/barrier/{seq}/{tag}/{r}", timeout_s) is None:
                raise TimeoutError(
                    f"barrier {tag!r}: rank {r} missing after {timeout_s}s")

    def get(self, key: str, timeout_s: float = 120.0) -> Optional[str]:
        """Blocking KV read with a real deadline; None on timeout."""
        import time as _time

        deadline = _time.monotonic() + timeout_s
        while True:
            remaining_ms = int(max(0.1, deadline - _time.monotonic()) * 1000)
            try:
                return self._client.blocking_key_value_get(
                    key, min(remaining_ms, 10_000)
                )
            except Exception as e:
                if "DEADLINE_EXCEEDED" not in str(e):
                    raise
                if _time.monotonic() >= deadline:
                    return None


_dist_context: Optional[DistContext] = None


def get_dist_context() -> DistContext:
    """The process-wide DistContext singleton (see DistContext docstring for
    why per-call instances would desynchronize the broadcast key stream)."""
    global _dist_context
    if _dist_context is None:
        _dist_context = DistContext()
    return _dist_context


def local_batch_slice(global_batch: int) -> Tuple[int, int]:
    """[start, end) rows of the global batch this process should feed — hosts
    materialize only their shard (reference counterpart: each function loads
    only its contiguous doc range, python/kubeml/kubeml/util.py:46-56).

    The global batch must divide evenly: silently dropping remainder rows
    would leave shards of a globally-sharded array unmaterialized."""
    n = max(1, jax.process_count())
    if global_batch % n != 0:
        raise ValueError(
            f"global batch {global_batch} must be divisible by the "
            f"{n} host processes"
        )
    per = global_batch // n
    start = jax.process_index() * per
    return start, start + per
