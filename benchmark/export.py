"""Hand seeded weights to the program as a finished job's final checkpoint.

The layout is the program's sharded checkpoint store's, as its module
documents it (``<checkpoints>/<job>/final.shards/manifest.json`` plus
``shard-<n>.npz``; a slice is keyed ``<leaf path>@<start,...>``), which the
parameter server serves a finished job from. It is written here, in several
shard files, because a file over about 1 GB cannot be written on the
driver's machine and the store's own single-process save writes one file per
process."""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

SHARD_BYTES = 400 << 20  # well under the ~1 GB a file may have


def write_final(checkpoints_dir: Path, job_id: str, leaves,
                function_name: str) -> dict:
    """``leaves`` yields (path, float32 numpy array). Returns what was
    written: files, bytes, the largest file."""
    d = Path(checkpoints_dir) / job_id / "final.shards"
    d.mkdir(parents=True, exist_ok=True)
    table, blobs, held, shard, sizes = {}, {}, 0, 0, []

    def flush():
        nonlocal blobs, held, shard
        if blobs:
            path = d / f"shard-{shard}.npz"
            np.savez(path, **blobs)
            sizes.append(path.stat().st_size)
            blobs, held, shard = {}, 0, shard + 1

    for path, arr in leaves:
        if held and held + arr.nbytes > SHARD_BYTES:
            flush()
        start = (0,) * arr.ndim
        table[path] = {"shape": list(arr.shape), "dtype": str(arr.dtype),
                       "slices": [{"start": list(start),
                                   "shape": list(arr.shape), "shard": shard}]}
        blobs[f"{path}@{','.join(map(str, start))}"] = arr
        held += arr.nbytes
    flush()
    manifest = {"job_id": job_id, "tag": "final", "epoch": 1,
                "saved_at": time.time(), "processes": len(sizes),
                "meta": {"request": {"function_name": function_name}},
                "leaves": table}
    # the manifest last: its presence marks the checkpoint complete
    (d / "manifest.json").write_text(json.dumps(manifest))
    return {"files": len(sizes), "bytes": int(sum(sizes)),
            "largest_file_bytes": int(max(sizes))}
