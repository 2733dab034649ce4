"""Long-context LM training benchmark — tokens/sec vs sequence length.

The reference has no long-context story at all (SURVEY §5: sequence length is
never a concept). This benchmark measures the TPU-native one end-to-end: the
causal-transformer flagship under the SPMD engine with rematerialized blocks
(``jax.checkpoint``) and the Pallas flash-attention kernel auto-dispatched at
KV length >= 1024 — measured 1.2-21x faster than XLA's fused attention inside
the rematerialized training step (round-3 table in BASELINE.md; the full
measurement story lives in kubeml_tpu/ops/attention.py). Fixed token budget
per step so throughput is comparable across sequence lengths.

    python -m kubeml_tpu.benchmarks.longcontext                 # 1k..16k sweep
    python -m kubeml_tpu.benchmarks.longcontext --seq-lens 4096 --steps 10

Prints one JSON line per (seq_len, dtype): tokens/sec plus the config. On a
multi-device host the batch shards over dp; sequence parallelism (sp) is
exercised separately by the dryrun/tests — this benchmark is the single-chip
long-context envelope.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def run_point(seq_len: int, tokens_per_step: int, steps: int, dtype_name: str,
              depth: int = 8, embed_dim: int = 512, num_heads: int = 8,
              vocab: int = 32000, logits_chunk: int | None = None) -> dict:
    from ..models.gpt import CausalTransformer
    from ..parallel.mesh import make_mesh
    from ..parallel.trainer import SPMDTrainer

    batch = max(1, tokens_per_step // seq_len)
    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    mesh = make_mesh(dp=len(jax.devices()))
    module = CausalTransformer(
        vocab_size=vocab, max_len=seq_len, embed_dim=embed_dim, depth=depth,
        num_heads=num_heads, mesh=mesh, remat=True, dtype=dtype,
    )
    if logits_chunk is None and seq_len > 32768:
        # past 32k the [B, L, vocab] logits are the HBM wall (measured:
        # 64k x 32k vocab = 8.4 GB f32 fails to fit with its backward copy,
        # while 32k runs unchunked — the recorded 32k row stays reproducible);
        # stream the lm_head + loss instead (parallel.trainer.chunked_lm_loss)
        logits_chunk = 8192
    trainer = SPMDTrainer(module, mesh, precision="bf16",
                          logits_chunk=logits_chunk)
    r = np.random.default_rng(0)
    global_batch = batch * mesh.shape["dp"]
    tokens = r.integers(1, vocab, size=(global_batch, seq_len)).astype(np.int32)

    rng = jax.random.PRNGKey(0)
    trainer.init(rng, tokens)
    loss = trainer.train_step(tokens, rng)  # warmup/compile
    jax.block_until_ready(loss)

    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            loss = trainer.train_step(tokens, jax.random.fold_in(rng, i))
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        best = max(best, steps * global_batch * seq_len / dt)
    row = {
        "metric": "gpt-longcontext-train-throughput",
        "seq_len": seq_len,
        "global_batch": global_batch,
        "depth": depth,
        "embed_dim": embed_dim,
        "dtype": dtype_name,
        "value": round(best, 1),
        "unit": "tokens/sec",
        "loss": round(float(loss), 4),
    }
    if logits_chunk is not None:  # provenance: the loss path differs
        row["logits_chunk"] = logits_chunk
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="long-context LM training benchmark")
    p.add_argument("--seq-lens", type=int, nargs="*",
                   default=[1024, 2048, 4096, 8192, 16384])
    p.add_argument("--tokens-per-step", type=int, default=16384,
                   help="fixed token budget per step (batch = budget // seq_len)")
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--dtype", choices=["bf16", "f32"], default="bf16",
                   help="model computation dtype (bf16 = mixed precision)")
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--embed-dim", type=int, default=512)
    args = p.parse_args(argv)

    results: List[dict] = []
    for L in args.seq_lens:
        res = run_point(L, args.tokens_per_step, args.steps, args.dtype,
                        depth=args.depth, embed_dim=args.embed_dim)
        print(json.dumps(res))
        results.append(res)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
