#!/usr/bin/env python
"""Seed real datasets into a running kubeml-tpu cluster (or a data root).

The counterpart of the reference's one-command dataset bootstrap
(reference: ml/hack/upload_mnist.sh, upload_cifar10.sh, upload_cifar100.sh —
CLI invocations that multipart-upload the four split files). Three sources:

* ``digits``  — scikit-learn's REAL handwritten-digits corpus (1,797 8x8
  scans), available offline; this is the real-data convergence target in
  environments without network egress.
* ``mnist``   — from a local ``mnist.npz`` (the standard Keras archive with
  x_train/y_train/x_test/y_test) or a directory of the four IDX files
  (train-images-idx3-ubyte etc., optionally .gz).
* ``cifar10`` — from a local ``cifar-10-python.tar.gz`` (the standard
  batches.meta/data_batch_N pickle tarball).

Upload goes through the controller's HTTP multipart route (the reference's
`kubeml dataset create` path) when --url is given, else straight into the
shard store at --data-root.

    python scripts/seed_datasets.py digits --url http://127.0.0.1:9090
    python scripts/seed_datasets.py mnist --file ~/mnist.npz --name mnist
    python scripts/seed_datasets.py cifar10 --file ~/cifar-10-python.tar.gz
"""

from __future__ import annotations

import argparse
import gzip
import io
import pickle
import struct
import sys
import tarfile
from pathlib import Path

import numpy as np

# runnable as `python scripts/seed_datasets.py` from anywhere
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def load_digits_real():
    # the ONE split definition, shared with the tests' digits-real scenario so
    # seeded clusters and test-created datasets always partition identically
    from kubeml_tpu.data.digits import load_digits_real as _load

    return _load()


def _read_idx(path: Path) -> np.ndarray:
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    magic, = struct.unpack(">I", raw[:4])
    ndim = magic & 0xFF
    dims = struct.unpack(f">{ndim}I", raw[4 : 4 + 4 * ndim])
    return np.frombuffer(raw, np.uint8, offset=4 + 4 * ndim).reshape(dims)


def load_mnist(src: Path):
    if src.is_file():  # mnist.npz (Keras layout)
        with np.load(src) as z:
            xtr, ytr = z["x_train"], z["y_train"]
            xte, yte = z["x_test"], z["y_test"]
    else:  # directory of IDX files
        def find(stem):
            for suffix in ("", ".gz"):
                p = src / f"{stem}{suffix}"
                if p.exists():
                    return p
            raise FileNotFoundError(f"{stem}[.gz] not in {src}")

        xtr = _read_idx(find("train-images-idx3-ubyte"))
        ytr = _read_idx(find("train-labels-idx1-ubyte"))
        xte = _read_idx(find("t10k-images-idx3-ubyte"))
        yte = _read_idx(find("t10k-labels-idx1-ubyte"))
    return (xtr.astype(np.uint8)[..., None], ytr.astype(np.int64),
            xte.astype(np.uint8)[..., None], yte.astype(np.int64))


def load_cifar10(tar_path: Path):
    def batch(tf, name):
        with tf.extractfile(f"cifar-10-batches-py/{name}") as f:
            d = pickle.load(io.BytesIO(f.read()), encoding="bytes")
        x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        return x.astype(np.uint8), np.asarray(d[b"labels"], np.int64)

    with tarfile.open(tar_path) as tf:
        parts = [batch(tf, f"data_batch_{i}") for i in range(1, 6)]
        xtr = np.concatenate([p[0] for p in parts])
        ytr = np.concatenate([p[1] for p in parts])
        xte, yte = batch(tf, "test_batch")
    return xtr, ytr, xte, yte


def upload_http(url: str, name: str, splits) -> None:
    import requests

    def npy(a):
        b = io.BytesIO()
        np.save(b, a)
        return b.getvalue()

    xtr, ytr, xte, yte = splits
    files = {"x-train": npy(xtr), "y-train": npy(ytr),
             "x-test": npy(xte), "y-test": npy(yte)}
    r = requests.post(f"{url}/dataset/{name}", files=files, timeout=600)
    r.raise_for_status()
    print(r.json())


def upload_direct(data_root: str, name: str, splits) -> None:
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.storage.store import ShardStore

    store = ShardStore(config=Config(data_root=Path(data_root)))
    summary = store.create(name, *splits)
    print(summary.to_dict() if hasattr(summary, "to_dict") else summary)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("dataset", choices=["digits", "mnist", "cifar10"])
    p.add_argument("--file", type=Path, default=None,
                   help="source archive/dir (mnist.npz, IDX dir, or cifar tar)")
    p.add_argument("--name", default=None, help="dataset name (default: source name)")
    p.add_argument("--url", default=None, help="controller URL (HTTP upload)")
    p.add_argument("--data-root", default=None, help="write into this store directly")
    args = p.parse_args(argv)

    if args.dataset == "digits":
        splits = load_digits_real()
        name = args.name or "digits-real"
    elif args.dataset == "mnist":
        if args.file is None:
            sys.exit("mnist needs --file (mnist.npz or an IDX directory); this "
                     "environment has no network egress to fetch it")
        splits = load_mnist(args.file)
        name = args.name or "mnist"
    else:
        if args.file is None:
            sys.exit("cifar10 needs --file cifar-10-python.tar.gz; this "
                     "environment has no network egress to fetch it")
        splits = load_cifar10(args.file)
        name = args.name or "cifar10"

    print(f"{name}: train {splits[0].shape} test {splits[2].shape}")
    if args.url:
        upload_http(args.url, name, splits)
    elif args.data_root:
        upload_direct(args.data_root, name, splits)
    else:
        sys.exit("pass --url (running cluster) or --data-root (direct)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
