"""bf16 vs int8-dequant vs int8-NATIVE decode throughput.

Measures the continuous batcher's raw decode rate at batch 1-16 across
THREE weight modes on the GPT-2 classes, same process, interleaved (the
dev chip's deliverable rate swings between minutes — each batch point
measures all modes back-to-back so the comparison is same-regime):

* ``bf16``        — dense bf16 weights (the baseline stream)
* ``int8``        — int8 weights, dequantized to a dense tree inside the
                    step program (the round-5 path; +4-11% at batch 1)
* ``int8_native`` — int8 weights contracted directly by quantized_dot
                    (KUBEML_INT8_MATMUL; ops/int8_matmul.py) — the mode
                    the 2x byte cut is supposed to show up in tokens/sec
                    through (VERDICT r5 next-1)

plus the teacher-forced quality delta and the per-step weight-byte
accounting. One JSON line per batch with all three rates side by side.

    python -m kubeml_tpu.benchmarks.quant_bench --batches 1,8,16
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

PROMPT_LEN = 32
VOCAB = 32000


def _served(max_len: int, model: str = "small"):
    from ..models.gpt import CausalTransformer, GPTSmall

    if model == "large":
        # GPT-2-large class (~774M): weight traffic ~1.5 GB/step bf16 — the
        # regime where decode IS HBM-bound on a v5e and the int8 cut shows
        # (GPT-2-small's 124M streams only ~200 GB/s at measured step rate,
        # a quarter of HBM: per-op overhead dominates and int8 buys ~0)
        module = CausalTransformer(vocab_size=VOCAB, max_len=max_len,
                                   embed_dim=1280, depth=36, num_heads=20,
                                   dtype=jnp.bfloat16)
    else:
        module = GPTSmall(vocab_size=VOCAB, max_len=max_len,
                          dtype=jnp.bfloat16)
    r = np.random.default_rng(0)
    prompt = jnp.asarray(r.integers(1, VOCAB, size=(1, PROMPT_LEN)), jnp.int32)
    variables = module.init(jax.random.PRNGKey(0), prompt)
    # the BASELINE must actually stream bf16 weights: params init as f32
    # (compute dtype != storage dtype, models/gpt.py), and an f32 baseline
    # would overstate the int8 win as ~4x instead of the claimed ~2x
    import flax.linen as nn

    variables = jax.tree.map(
        lambda l: (l.astype(jnp.bfloat16)
                   if jnp.issubdtype(l.dtype, jnp.floating) else l),
        nn.meta.unbox(variables))
    return module, variables


def decode_rate(module, variables, *, batch: int, new_tokens: int,
                quantize: str, int8_matmul: bool = False, reps: int = 3,
                chunk_steps: int = 16) -> dict:
    """Sustained decode tokens/sec through the batcher at a fixed batch:
    B requests fill B slots, the engine advances them in lockstep; the rep
    clock starts after warmup (compiles amortized out). Small chunks
    measure the DISPATCH pipeline, not the device — pass
    a large ``chunk_steps`` (e.g. new_tokens/2) to amortize the per-program
    round trip and expose the device-side rate the int8 claim is about."""
    from ..api.types import GenerateRequest
    from ..serving.batcher import BatchingDecoder

    mode = ("int8_native" if int8_matmul else (quantize or "bf16"))
    dec = BatchingDecoder(module, variables, slots=batch,
                          chunk_steps=chunk_steps, quantize=quantize,
                          int8_matmul=int8_matmul, name=f"qbench-{mode}")
    r = np.random.default_rng(1)

    def one_round(seed: int) -> float:
        prompts = r.integers(1, VOCAB, size=(batch, PROMPT_LEN)).astype(np.int32)
        t0 = time.perf_counter()
        entries = [dec.submit(GenerateRequest(prompts=[p.tolist()],
                                              max_new_tokens=new_tokens))
                   for p in prompts]
        for e in entries:
            dec.wait(e, timeout=1200)
        return batch * new_tokens / (time.perf_counter() - t0)

    try:
        one_round(0)  # warmup: prefill + chunk compiles
        best = max(one_round(i + 1) for i in range(reps))
    finally:
        dec.close()
    return {"tokens_per_sec": round(best, 1),
            "weight_bytes": int(dec.weight_bytes)}


# (row key, decoder quantize mode, native int8 matmul)
MODES = (("bf16", "", False), ("int8", "int8", False),
         ("int8_native", "int8", True))


def three_way_rows(module, variables, *, batches, new_tokens: int,
                   chunk_steps: int = 16, reps: int = 3,
                   model: str = "small") -> list:
    """One row per batch with the bf16 / int8-dequant / int8-native decode
    rates measured back-to-back (same regime on a shared chip) — the
    comparison the chip harness records (bench.py, scripts/)."""
    rows = []
    for batch in batches:
        row = {"metric": "decode-rate", "model": model, "batch": int(batch),
               "new_tokens": new_tokens, "chunk_steps": chunk_steps}
        for key, quantize, native in MODES:
            r = decode_rate(module, variables, batch=batch,
                            new_tokens=new_tokens, quantize=quantize,
                            int8_matmul=native, reps=reps,
                            chunk_steps=chunk_steps)
            row[f"{key}_tokens_per_sec"] = r["tokens_per_sec"]
            row[f"{key}_weight_bytes"] = r["weight_bytes"]
        base = max(row["bf16_tokens_per_sec"], 1e-9)
        row["int8_speedup"] = round(row["int8_tokens_per_sec"] / base, 3)
        row["int8_native_speedup"] = round(
            row["int8_native_tokens_per_sec"] / base, 3)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="int8 vs bf16 decode bench")
    p.add_argument("--batches", default="1,8,16")
    p.add_argument("--new-tokens", type=int, default=128)
    p.add_argument("--chunk-steps", type=int, default=16)
    p.add_argument("--model", default="small", choices=("small", "large"))
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--skip-quality", action="store_true")
    args = p.parse_args(argv)
    batches = [int(b) for b in args.batches.split(",")]

    module, variables = _served(PROMPT_LEN + args.new_tokens, args.model)

    if not args.skip_quality:
        from ..serving.quant import quality_report

        sample_len = min(64, PROMPT_LEN + args.new_tokens)
        toks = np.random.default_rng(2).integers(
            1, VOCAB, size=(4, sample_len)).astype(np.int32)
        q = quality_report(module, variables, toks)
        print(json.dumps({"metric": "int8-quality", **{
            k: round(v, 5) for k, v in q.items()}}), flush=True)

    # interleave modes per batch: same-regime comparison on a shared chip
    for row in three_way_rows(module, variables, batches=batches,
                              new_tokens=args.new_tokens,
                              chunk_steps=args.chunk_steps, reps=args.reps,
                              model=args.model):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
