"""Distributed (sharded) checkpointing — per-process shard files + manifest.

The flat ``CheckpointStore`` (storage/checkpoint.py) persists ONE replica of
the pytree, which forces a replicate-and-gather onto a single host first
(``SPMDJob._host_params``). Fine at 124M params; a wall for the
multi-billion-param models the SPMD engine otherwise supports (64k-context
training is demonstrated). This store removes the gather (VERDICT r3 next-4):

* **save**: every process writes exactly the leaf SLICES its devices own
  (``jax.Array.addressable_shards``), deduplicated by ``replica_id == 0`` so
  replicated leaves are written once across the fleet. No host ever
  materializes a full leaf, let alone the full tree.
* **layout**: ``<root>/<job>/<tag>.shards/shard-<p>.npz`` (slice data, keyed
  by leaf path + slice index) + ``manifest.json`` (global shapes/dtypes, the
  slice table, epoch/meta). The manifest is written LAST by the leader after
  a barrier — its presence marks the checkpoint complete, which is the same
  atomic-publish discipline the flat store gets from ``os.replace``.
* **restore onto any mesh**: each leaf is rebuilt with
  ``jax.make_array_from_callback`` against the TARGET sharding — every
  process reads only the byte ranges its own devices need, assembling them
  from whichever stored slices overlap (the stored and target meshes may
  tile the leaf completely differently, e.g. resume on a different dp
  level). Requires the shard dir on a shared filesystem, the same assumption
  the multi-host resume path already makes (engine/spmd_job.py).

bfloat16 uses the same uint16 bit-pattern trick as the flat store.
"""

from __future__ import annotations

import json
import os
import shutil
import time
import uuid
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import ml_dtypes
import numpy as np

from ..api.config import Config, get_config
from ..api.errors import CheckpointNotFoundError, StorageError
from .checkpoint import _BITCAST, _BITCAST_BACK, _flatten, _unflatten

MANIFEST = "manifest.json"
SHARD_DIR_SUFFIX = ".shards"


def _slice_key(path: str, start: Tuple[int, ...]) -> str:
    return f"{path}@{','.join(map(str, start))}"


@dataclass
class ShardedCheckpoint:
    """A restored sharded checkpoint (variables may be jax or numpy leaves)."""

    job_id: str
    tag: str
    variables: Dict[str, Any]
    epoch: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)


class ShardedCheckpointStore:
    """Filesystem store for mesh-sharded checkpoints.

    Layout::

        <root>/<job_id>/ep00003.shards/manifest.json
        <root>/<job_id>/ep00003.shards/shard-0.npz
        <root>/<job_id>/ep00003.shards/shard-1.npz
    """

    def __init__(self, root: Optional[Path] = None, config: Optional[Config] = None):
        cfg = config or get_config()
        self.root = Path(root) if root is not None else cfg.checkpoints_dir
        self.root.mkdir(parents=True, exist_ok=True)

    def _dir(self, job_id: str, tag: str) -> Path:
        if not job_id or "/" in job_id or job_id.startswith("."):
            raise StorageError(f"invalid job id {job_id!r}")
        if not tag or "/" in tag or tag.startswith("."):
            raise StorageError(f"invalid checkpoint tag {tag!r}")
        return self.root / job_id / f"{tag}{SHARD_DIR_SUFFIX}"

    # --- write ---

    def save(
        self,
        job_id: str,
        variables: Dict[str, Any],
        *,
        epoch: int = 0,
        tag: str,
        meta: Optional[Dict[str, Any]] = None,
        barrier: Optional[Callable[[str], None]] = None,
    ) -> Path:
        """Write this process's addressable slices of a sharded pytree.

        COLLECTIVE across processes: every process must call with the same
        (job_id, tag) and its own view of the same global arrays. ``barrier``
        (e.g. a DistContext sync) is awaited before the leader publishes the
        manifest; single-process callers may omit it. Leaves may be jax
        Arrays (sharded or not) or numpy arrays (treated as fully
        replicated)."""
        import jax

        proc = jax.process_index()
        pairs = _flatten_jax(variables)
        d = self._dir(job_id, tag)
        d.mkdir(parents=True, exist_ok=True)

        blobs: Dict[str, np.ndarray] = {}
        slice_table: Dict[str, Dict[str, Any]] = {}
        for path, leaf in pairs:
            dt = str(leaf.dtype)
            entry = {"shape": list(np.shape(leaf)), "dtype": dt, "slices": []}
            slice_table[path] = entry
            for start, data, owner in _owned_slices(leaf, proc):
                entry["slices"].append(
                    {"start": list(start), "shape": list(data.shape),
                     "shard": owner})
                if owner == proc:
                    arr = np.asarray(data)
                    if dt in _BITCAST:
                        arr = arr.view(_BITCAST[dt])
                    blobs[_slice_key(path, start)] = arr

        # Re-saving over an existing tag must never tear the PREVIOUS
        # checkpoint (ADVICE r4): the manifest's presence marks a sharded
        # checkpoint complete, and replacing shard-<p>.npz files while the
        # old manifest stays published would let a crash mid-rewrite (or a
        # concurrent restore) silently assemble a mix of old and new slice
        # data. Discipline: (1) STAGE every process's new shard under a tmp
        # name — any failure here leaves the old checkpoint fully
        # restorable; (2) unpublish the old manifest; (3) rename the staged
        # shards into place; (4) republish. A crash inside (2)-(4) reads as
        # "checkpoint absent" (no manifest), never as mixed data — the
        # multi-file analogue of the flat store's os.replace atomicity.
        shard_path = d / f"shard-{proc}.npz"
        tmp = d / f".shard-{proc}.{uuid.uuid4().hex}.npz"
        t0 = time.perf_counter()
        try:
            np.savez(tmp, **blobs)
            if barrier is not None:  # every process has staged its bytes
                barrier(f"ckpt-staged/{job_id}/{tag}")
            if proc == 0:
                (d / MANIFEST).unlink(missing_ok=True)
            if barrier is not None:  # no shard lands under a live manifest
                barrier(f"ckpt-clear/{job_id}/{tag}")
            os.replace(tmp, shard_path)
        except Exception:
            tmp.unlink(missing_ok=True)
            raise
        # data-plane accounting: this process's checkpoint bytes + achieved
        # write bandwidth (utils.profiler; barrier waits ride in the wall
        # time deliberately — they ARE the observable save cost)
        from ..utils import profiler

        profiler.record_io(
            "ckpt.save", sum(b.nbytes for b in blobs.values()),
            time.perf_counter() - t0, job=job_id, tag=tag)

        if barrier is not None:
            barrier(f"ckpt/{job_id}/{tag}")
        if proc == 0:
            manifest = {
                "job_id": job_id,
                "tag": tag,
                "epoch": int(epoch),
                "saved_at": time.time(),
                "processes": int(jax.process_count()),
                "meta": meta or {},
                "leaves": slice_table,
            }
            tmpm = d / f".manifest.{uuid.uuid4().hex}"
            tmpm.write_text(json.dumps(manifest))
            os.replace(tmpm, d / MANIFEST)
        return d

    # --- read ---

    def exists(self, job_id: str, tag: str) -> bool:
        return (self._dir(job_id, tag) / MANIFEST).exists()

    def manifest_path(self, job_id: str, tag: str) -> Path:
        """The manifest file (the checkpoint's completion marker — its mtime
        is the PS serving cache's freshness key, like the flat store's
        export_path)."""
        return self._dir(job_id, tag) / MANIFEST

    def tags(self, job_id: str) -> List[str]:
        jd = self.root / job_id
        if not jd.exists():
            return []
        return sorted(
            p.name[: -len(SHARD_DIR_SUFFIX)]
            for p in jd.glob(f"*{SHARD_DIR_SUFFIX}")
            if (p / MANIFEST).exists()
        )

    def list_jobs(self) -> List[str]:
        if not self.root.exists():
            return []
        return sorted(
            d.name for d in self.root.iterdir()
            if d.is_dir() and any(d.glob(f"*{SHARD_DIR_SUFFIX}/{MANIFEST}"))
        )

    def read_manifest(self, job_id: str, tag: str) -> Dict[str, Any]:
        p = self._dir(job_id, tag) / MANIFEST
        if not p.exists():
            raise CheckpointNotFoundError(f"{job_id}/{tag} (sharded)")
        return json.loads(p.read_text())

    def restore(
        self,
        job_id: str,
        tag: str,
        shardings: Optional[Dict[str, Any]] = None,
        remap: Optional[Callable] = None,
    ) -> ShardedCheckpoint:
        """Rebuild the pytree.

        With ``shardings`` (a pytree of NamedSharding matching the saved —
        or remapped — tree): leaves come back as jax Arrays on the TARGET
        mesh, each process reading only the stored slices overlapping its
        own devices' shards — the stored mesh shape is irrelevant. Without:
        full numpy leaves (single-host serving/inspection path).

        ``remap`` re-layouts the tree AT RESTORE TIME without materializing
        the stored layout first: a callable ``stored_path -> None | [(
        target_path, index_prefix)]``. ``None`` keeps the leaf as-is; a list
        fans a stored leaf out into target leaves, each the stored leaf
        indexed by ``index_prefix`` on its leading axes (e.g. a pipeline
        job's ``params/stages/layer_j`` leaves, STACKED on the ``pp`` axis,
        become the flat model's per-block ``params/block_i`` leaves — each
        target reads only the byte ranges of its own stage slice, so serving
        a pp-trained checkpoint never gathers the stacked tree;
        models.gpt_pipeline.flat_serving_remap builds this plan)."""
        import jax

        t_restore = time.perf_counter()
        d = self._dir(job_id, tag)
        mpath = d / MANIFEST
        if not mpath.exists():
            raise CheckpointNotFoundError(f"{job_id}/{tag} (sharded)")
        before = mpath.stat()
        manifest = json.loads(mpath.read_text())
        readers = _ShardReaders(d)
        flat_specs = manifest["leaves"]
        # Pin every shard file NOW and verify the manifest is unchanged
        # after: open handles keep the original inodes alive (POSIX), so a
        # concurrent re-save that renames new shards over these names cannot
        # change what this restore reads. A re-save that got in first
        # unpublishes the manifest before any rename (save() step 2), so an
        # unchanged manifest after the opens proves the handles are the
        # manifest's own generation — never a mix of old and new slices.
        shard_ids = sorted({sl["shard"] for spec in flat_specs.values()
                            for sl in spec["slices"]})
        for sid in shard_ids:
            readers.get(sid)
        try:
            after = mpath.stat()
        except OSError:
            after = None
        if (after is None or after.st_ino != before.st_ino
                or after.st_mtime_ns != before.st_mtime_ns):
            readers.close()
            raise StorageError(
                f"checkpoint {job_id}/{tag} was replaced while a restore was "
                f"starting; retry the restore")
        # target plan: path -> (stored path, leading-axis index prefix)
        plan: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        for p in flat_specs:
            fan = remap(p) if remap is not None else None
            if fan is None:
                plan[p] = (p, ())
            else:
                for tgt, pre in fan:
                    plan[tgt] = (p, tuple(int(i) for i in pre))

        def sub_assemble(src, spec, pre, index, out_shape):
            full = tuple(slice(i, i + 1) for i in pre) + tuple(index)
            return _assemble(readers, src, spec, full).reshape(out_shape)

        try:
            if shardings is None:
                pairs = {}
                for tgt, (src, pre) in plan.items():
                    spec = flat_specs[src]
                    if not pre:
                        pairs[tgt] = _assemble(readers, src, spec, None)
                    else:
                        shape = tuple(spec["shape"])[len(pre):]
                        idx = tuple(slice(0, s) for s in shape)
                        pairs[tgt] = sub_assemble(src, spec, pre, idx, shape)
            else:
                flat_sh = dict(_flatten_any(shardings))
                missing = set(plan) - set(flat_sh)
                if missing:
                    raise StorageError(
                        f"restore shardings missing leaves: {sorted(missing)[:4]}")
                pairs = {}
                for tgt, (src, pre) in plan.items():
                    spec = flat_specs[src]
                    target = flat_sh[tgt]
                    dtype = _stored_dtype(spec["dtype"])
                    shape = tuple(spec["shape"])[len(pre):]

                    def cb(index, src=src, spec=spec, pre=pre, shape=shape):
                        out = tuple(
                            (s.stop if s.stop is not None else dim)
                            - (s.start if s.start is not None else 0)
                            for s, dim in zip(index, shape))
                        return sub_assemble(src, spec, pre, index, out)

                    pairs[tgt] = jax.make_array_from_callback(
                        shape, target, cb, dtype=dtype)
        finally:
            readers.close()
        from ..utils import profiler

        profiler.record_io(
            "ckpt.restore",
            sum(getattr(a, "nbytes", 0) for a in pairs.values()),
            time.perf_counter() - t_restore, job=job_id, tag=tag)
        return ShardedCheckpoint(
            job_id=manifest.get("job_id", job_id),
            tag=manifest.get("tag", tag),
            variables=_unflatten(pairs),
            epoch=int(manifest.get("epoch", 0)),
            meta=manifest.get("meta", {}),
        )

    def delete(self, job_id: str, tag: str) -> None:
        d = self._dir(job_id, tag)
        if not d.exists():
            raise CheckpointNotFoundError(f"{job_id}/{tag} (sharded)")
        shutil.rmtree(d)


def apply_remap_host(variables: Dict[str, Any], remap) -> Dict[str, Any]:
    """Apply a restore-time remap plan (see ``restore``'s ``remap``) to an
    in-memory host pytree — the FLAT-checkpoint counterpart: a pp-trained
    job saved through the flat store still re-layouts to its serving shape
    (stacked stage leaves sliced per target block; small models, host copies
    are fine here)."""
    out: Dict[str, Any] = {}
    for path, leaf in _flatten_any(variables):
        fan = remap(path)
        if fan is None:
            out[path] = leaf
            continue
        for tgt, pre in fan:
            sub = leaf
            for i in pre:
                sub = sub[int(i)]
            out[tgt] = sub
    return _unflatten(out)


# --- internals ---


def _flatten_jax(tree: Any) -> List[Tuple[str, Any]]:
    """Like checkpoint._flatten but keeps jax Arrays un-copied."""
    out: List[Tuple[str, Any]] = []
    if not isinstance(tree, dict):
        raise StorageError("checkpoint root must be a dict pytree")

    def rec(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                if "/" in str(k):
                    raise StorageError(f"checkpoint key {k!r} may not contain '/'")
                rec(node[k], f"{prefix}{k}/")
            return
        out.append((prefix[:-1], node))

    rec(tree, "")
    return out


def _flatten_any(tree: Any) -> List[Tuple[str, Any]]:
    out: List[Tuple[str, Any]] = []

    def rec(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                rec(node[k], f"{prefix}{k}/")
            return
        out.append((prefix[:-1], node))

    rec(tree, "")
    return out


def _stored_dtype(dt: str):
    if dt in _BITCAST_BACK:
        return _BITCAST_BACK[dt]
    return np.dtype(dt)


def _owned_slices(leaf, proc: int):
    """Yield (start, data, owner_process) for every UNIQUE slice of ``leaf``.

    jax Arrays: one entry per distinct shard index, owned by the process
    holding its replica-0 device (every process computes the same table; only
    the owner materializes data). numpy/unsharded leaves: a single slice
    owned by process 0."""
    import jax

    if isinstance(leaf, jax.Array) and hasattr(leaf, "sharding"):
        seen = {}
        # global shard table: device -> index; replica 0 of each distinct
        # index owns the write. addressable_shards only covers local devices,
        # so walk the full device->index map for the OWNER decision and pull
        # data from local shards.
        index_map = leaf.sharding.devices_indices_map(leaf.shape)
        for device, index in index_map.items():
            start = tuple(
                (0 if s.start is None else int(s.start)) for s in index)
            if start in seen:
                continue
            seen[start] = device.process_index
        local = {tuple((0 if s.start is None else int(s.start))
                       for s in sh.index): sh
                 for sh in leaf.addressable_shards}
        for start, owner in seen.items():
            if owner == proc:
                sh = local.get(start)
                if sh is None:
                    # owner computed from the device map must be local;
                    # defensive: skip rather than write garbage
                    raise StorageError(
                        f"shard at {start} mapped to process {proc} but is "
                        f"not addressable")
                yield start, np.asarray(sh.data), owner
            else:
                yield start, _Shape(leaf.shape, start, index_map, leaf), owner
        return
    arr = np.asarray(leaf)
    yield (0,) * arr.ndim, (arr if proc == 0 else _FakeShaped(arr)), 0


class _Shape:
    """Shape-only stand-in for a slice another process owns (save() needs
    its shape for the manifest, never its bytes)."""

    def __init__(self, global_shape, start, index_map, leaf):
        # find the index tuple for this start to compute the slice shape
        for index in index_map.values():
            s = tuple((0 if sl.start is None else int(sl.start)) for sl in index)
            if s == start:
                self.shape = tuple(
                    (dim if sl.stop is None else int(sl.stop)) -
                    (0 if sl.start is None else int(sl.start))
                    for sl, dim in zip(index, global_shape))
                return
        raise StorageError(f"no index for start {start}")


class _FakeShaped:
    def __init__(self, arr):
        self.shape = arr.shape


class _ShardReaders:
    """Lazy npz handles over every shard file in a checkpoint dir."""

    def __init__(self, d: Path):
        self.dir = d
        self._handles: Dict[int, Any] = {}

    def get(self, shard: int):
        h = self._handles.get(shard)
        if h is None:
            p = self.dir / f"shard-{shard}.npz"
            if not p.exists():
                raise StorageError(f"missing shard file {p}")
            h = np.load(p)
            self._handles[shard] = h
        return h

    def close(self):
        for h in self._handles.values():
            h.close()


def _assemble(readers: _ShardReaders, path: str, spec: Dict[str, Any],
              index) -> np.ndarray:
    """Materialize ``leaf[index]`` (or the whole leaf when index is None)
    from whichever stored slices overlap it."""
    shape = tuple(spec["shape"])
    dtype = _stored_dtype(spec["dtype"])
    if index is None:
        index = tuple(slice(0, s) for s in shape)
    req_start = tuple(0 if s.start is None else int(s.start) for s in index)
    req_stop = tuple(dim if s.stop is None else int(s.stop)
                     for s, dim in zip(index, shape))
    out_shape = tuple(b - a for a, b in zip(req_start, req_stop))
    out = np.empty(out_shape, dtype=dtype)
    filled = 0
    for sl in spec["slices"]:
        s_start = tuple(sl["start"])
        s_shape = tuple(sl["shape"])
        s_stop = tuple(a + n for a, n in zip(s_start, s_shape))
        lo = tuple(max(a, b) for a, b in zip(req_start, s_start))
        hi = tuple(min(a, b) for a, b in zip(req_stop, s_stop))
        if any(l >= h for l, h in zip(lo, hi)):
            continue  # no overlap
        data = readers.get(sl["shard"])[_slice_key(path, s_start)]
        if spec["dtype"] in _BITCAST_BACK:
            data = data.view(_BITCAST_BACK[spec["dtype"]])
        src = tuple(slice(l - a, h - a) for l, h, a in zip(lo, hi, s_start))
        dst = tuple(slice(l - a, h - a) for l, h, a in zip(lo, hi, req_start))
        out[dst] = data[src]
        filled += int(np.prod([h - l for l, h in zip(lo, hi)]))
    if filled < int(np.prod(out_shape)):
        raise StorageError(
            f"stored slices do not cover leaf {path!r} range "
            f"{req_start}..{req_stop}")
    return out
