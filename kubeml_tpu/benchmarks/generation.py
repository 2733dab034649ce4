"""LM decode throughput benchmark — tokens/sec for the KV-cache serving path.

The reference has no generation story (classifier `/infer` only); this
measures the extension's serving numbers the way the training benchmarks do:
one JSON line per config, value-fetch barrier, best-of-N reps after a warmup
compile. Decode is latency/HBM-bound, not MXU-bound — the interesting axes
are batch (amortizes the per-step weight reads) and context length (cache
reads grow linearly).

    python -m kubeml_tpu.benchmarks.generation                # default grid
    python -m kubeml_tpu.benchmarks.generation --batches 1 4 16 --new-tokens 64
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def run_point(batch: int, prompt_len: int, new_tokens: int,
              reps: int = 3) -> dict:
    from ..models.generation import make_generate_fn
    from ..models.gpt import GPTSmall

    module = GPTSmall(vocab_size=32000, max_len=prompt_len + new_tokens,
                      dtype=jnp.bfloat16)
    r = np.random.default_rng(0)
    prompt = jnp.asarray(r.integers(1, 32000, size=(batch, prompt_len)),
                         jnp.int32)
    variables = module.init(jax.random.PRNGKey(0), prompt)
    fn = make_generate_fn(module, max_new_tokens=new_tokens, temperature=0.8,
                          top_k=40)
    out = fn(variables, prompt, jax.random.PRNGKey(0))  # warmup/compile
    jax.block_until_ready(out.tokens)

    best = 0.0
    for i in range(reps):
        t0 = time.perf_counter()
        out = fn(variables, prompt, jax.random.PRNGKey(i + 1))
        jax.block_until_ready(out.tokens)
        best = max(best, batch * new_tokens / (time.perf_counter() - t0))
    return {
        "metric": "gpt2small-decode-throughput",
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "value": round(best, 1),
        "unit": "tokens/sec",
        "steps_per_sec": round(best / batch, 1),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="LM decode throughput benchmark")
    p.add_argument("--batches", type=int, nargs="*", default=[1, 4, 16])
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--new-tokens", type=int, default=64)
    args = p.parse_args(argv)
    rows: List[dict] = []
    for b in args.batches:
        rows.append(run_point(b, args.prompt_len, args.new_tokens))
        print(json.dumps(rows[-1]), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
