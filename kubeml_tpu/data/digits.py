"""The handwritten-digits corpus shipped with scikit-learn, split for training."""

from __future__ import annotations

import numpy as np


def load_digits_real():
    """The REAL handwritten-digits dataset shipped with scikit-learn (1,797
    8x8 scans of the UCI optical-digits corpus) — the in-environment real-data
    convergence target (no network egress here; MNIST/CIFAR arrive via
    ``scripts/seed_datasets.py mnist|cifar10`` when their files are present).
    Deterministic 80/20 split (every 5th sample is test). This is THE single
    definition — ``scripts/seed_datasets.py digits`` seeds exactly this split,
    so seeded clusters and test-created datasets always match."""
    from sklearn.datasets import load_digits

    d = load_digits()
    x = d.images.astype(np.uint8)[..., None]  # [1797, 8, 8, 1], 0..16
    y = d.target.astype(np.int64)
    test = np.arange(len(x)) % 5 == 0
    return x[~test], y[~test], x[test], y[test]
