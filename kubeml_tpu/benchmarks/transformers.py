"""Transformer training headline benchmark: samples/sec + MFU on one chip.

The round-1 perf story had CNN throughput only; transformers are where MXU
utilization is actually provable (dense [B*L, D] x [D, 4D] contractions vs the
small convs of CIFAR models). This measures the ViT-Tiny and BERT-base
training targets (BASELINE.md targets #3/#4) through the same K-AVG engine
the platform trains them with, and reports MFU from the compiled executable's
own FLOP count (kubeml_tpu.benchmarks.mfu — no analytic guessing).

    python -m kubeml_tpu.benchmarks.transformers                # both models
    python -m kubeml_tpu.benchmarks.transformers --model bert-base --steps 10

Prints one JSON line per model:
    {"metric": "...-train-throughput", "value": samples/sec, "mfu": ...}
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def _bench_kavg(module, name: str, sample, labels, *, k: int, steps_cap: int,
                reps: int = 3) -> dict:
    from ..engine.kavg import KAvgTrainer
    from .harness import make_synthetic_model
    from .mfu import mfu_from, peak_flops, roofline_mfu

    model = make_synthetic_model(module, f"bench-{name}")
    trainer = KAvgTrainer(model, precision="bf16")
    n = 1  # single-chip headline; multi-chip scaling is the multihost story
    x = np.broadcast_to(sample, (n, k, *sample.shape)).copy()
    y = np.broadcast_to(labels, (n, k, *labels.shape)).copy()
    mask = np.ones(y.shape[:3], np.float32)

    rng = jax.random.PRNGKey(0)
    variables = trainer.init_variables(rng, sample, n)
    sx, sy, sm = trainer.stage_round(x, y, mask, n)
    variables, loss = trainer.sync_round(variables, sx, sy, sm, rng, lr=1e-3)
    jax.block_until_ready(loss)

    batch = sample.shape[0]
    samples_per_round = n * k * batch
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(steps_cap):
            variables, loss = trainer.sync_round(
                variables, sx, sy, sm, jax.random.fold_in(rng, i), lr=1e-3
            )
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        best = max(best, steps_cap * samples_per_round / dt)

    # MFU from the compiled program's own cost analysis (1-step count x k —
    # XLA counts a lax.scan body once regardless of trip count), plus the
    # roofline CEILING the program's arithmetic intensity allows: measured
    # MFU near the ceiling = bandwidth-bound (the lever is intensity, e.g.
    # batch); far below = compute-side headroom (VERDICT r2 #3 asks which)
    costs = trainer.round_costs(variables, sx, sy, sm, lr=1e-3)
    flops = costs["flops"]
    rounds_per_sec = best / samples_per_round
    mfu = mfu_from(flops, rounds_per_sec)
    ceiling = roofline_mfu(flops, costs["bytes_hbm"])
    return {
        "metric": f"{name}-train-throughput",
        "value": round(best, 1),
        "unit": "samples/sec",
        "batch": batch,
        "k": k,
        "flops_per_round": flops,
        "bytes_per_round": costs["bytes_hbm"],
        "bytes_prefusion": costs["bytes_accessed"],
        "peak_flops": peak_flops(),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "roofline_mfu_ceiling": round(ceiling, 4) if ceiling is not None else None,
        "loss": round(float(loss), 4),
    }


def bench_vit(steps: int = 10, batch: int = 256) -> dict:
    from ..models.vit import ViTTiny

    r = np.random.default_rng(0)
    sample = r.normal(size=(batch, 32, 32, 3)).astype(np.float32)
    labels = r.integers(0, 100, size=(batch,)).astype(np.int64)
    return _bench_kavg(ViTTiny(num_classes=100, dtype=jnp.bfloat16),
                       "vit-tiny-cifar100", sample, labels, k=8, steps_cap=steps)


def bench_bert(steps: int = 5, batch: int = 32, seq: int = 128) -> dict:
    from ..models.bert import BertBase

    r = np.random.default_rng(0)
    sample = r.integers(1, 30000, size=(batch, seq)).astype(np.int32)
    labels = r.integers(0, 2, size=(batch,)).astype(np.int64)
    return _bench_kavg(BertBase(num_classes=2, dtype=jnp.bfloat16),
                       "bert-base-sst2", sample, labels, k=4, steps_cap=steps)


def bench_moe(steps: int = 8, batch: int = 16, seq: int = 512) -> dict:
    """MoE LM training MFU on one chip (VERDICT r4: chip-bench an MoE
    config): GPT-2-small skeleton with routed experts every other block,
    trained through the SPMD engine; reports MFU, the post-fusion roofline
    ceiling, and the expert-capacity overflow rate."""
    from jax.sharding import PartitionSpec as P

    from ..models.gpt import CausalTransformer
    from ..parallel.mesh import make_mesh
    from ..parallel.trainer import SPMDTrainer
    from .mfu import compiled_costs, mfu_from, peak_flops, roofline_mfu

    mesh = make_mesh(devices=jax.devices()[:1])
    module = CausalTransformer(vocab_size=32000, max_len=seq, embed_dim=768,
                               depth=12, num_heads=12, moe_every=2,
                               num_experts=8, top_k=2, mesh=mesh,
                               dtype=jnp.bfloat16)
    trainer = SPMDTrainer(module, mesh, precision="bf16", batch_spec=P("dp"))
    r = np.random.default_rng(0)
    tokens = r.integers(1, 32000, size=(batch, seq)).astype(np.int32)
    rng = jax.random.PRNGKey(0)
    trainer.init(rng, tokens)
    jax.block_until_ready(trainer.train_step(tokens, rng))  # compile
    best = 0.0
    for rep in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            loss = trainer.train_step(tokens, jax.random.fold_in(rng, i))
        jax.block_until_ready(loss)
        best = max(best, steps * batch * seq / (time.perf_counter() - t0))
    costs = compiled_costs(trainer._step_fn, trainer.params, trainer.opt_state,
                           jnp.asarray(tokens), rng)
    flops = costs["flops"]
    steps_per_sec = best / (batch * seq)
    mfu = mfu_from(flops, steps_per_sec)
    ceiling = roofline_mfu(flops, costs["bytes_hbm"])
    return {
        "metric": "gpt-moe-train-throughput",
        "value": round(best, 1),
        "unit": "tokens/sec",
        "batch": batch,
        "seq": seq,
        "num_experts": 8,
        "top_k": 2,
        "moe_every": 2,
        "flops_per_step": flops,
        "peak_flops": peak_flops(),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "roofline_mfu_ceiling": round(ceiling, 4) if ceiling is not None else None,
        "moe_overflow": round(float(trainer.last_moe_overflow), 4),
        "loss": round(float(loss), 4),
    }


def sweep_bert(steps: int = 5, batches=(32, 64, 128, 256)) -> List[dict]:
    """The MFU lever sweep (VERDICT r2 #3: BERT-base sat at 30% — is the
    ceiling per-core batch?): per-chip batch doubles until HBM pushes back.
    Each row carries measured MFU AND its roofline ceiling, so the output
    separates 'bandwidth-bound, ceiling reached' from 'compute-side gaps'."""
    rows = []
    for b in batches:
        try:
            row = bench_bert(steps=steps, batch=b)
        except Exception as e:  # e.g. HBM OOM at the top of the sweep
            row = {"metric": "bert-base-sst2-train-throughput", "batch": b,
                   "error": f"{type(e).__name__}: {e}"}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e):
                break  # batches grow monotonically; bigger ones are doomed too
            continue
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="transformer training headline benchmark")
    p.add_argument("--model", choices=["vit-tiny", "bert-base", "gpt-moe", "all"],
                   default="all")
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--sweep", action="store_true",
                   help="BERT per-chip batch sweep with roofline ceilings")
    p.add_argument("--batch", type=int, default=None)
    args = p.parse_args(argv)

    if args.sweep:
        if args.model != "all" or args.batch is not None:
            p.error("--sweep runs the BERT batch grid and is incompatible "
                    "with --model/--batch")
        sweep_bert(args.steps or 5)
        return 0
    results: List[dict] = []
    if args.model in ("vit-tiny", "all"):
        results.append(bench_vit(args.steps or 10, batch=args.batch or 256))
        print(json.dumps(results[-1]))
    if args.model in ("bert-base", "all"):
        results.append(bench_bert(args.steps or 5, batch=args.batch or 32))
        print(json.dumps(results[-1]))
    if args.model in ("gpt-moe", "all"):
        results.append(bench_moe(args.steps or 8, batch=args.batch or 16))
        print(json.dumps(results[-1]))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
