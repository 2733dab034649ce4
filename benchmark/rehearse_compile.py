#!/usr/bin/env python3
"""Compile a serving cell's model programs for a described TPU v5e, here,
without the chip: what the chip's compiler (XLA and Mosaic) refuses costs no
chip time, and ``memory_analysis()`` sizes the pool before any is spent.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py --workload gpt2-xl.docs

For each table width the mix's warm-up reaches it compiles the model's paged
forward pass as the engine calls it: the prefill (``slots`` rows x the suffix
bucket) and one decode step (``slots`` rows x 1 token), weights and arena as
arguments, the arena read through the page-walk kernel. It does not build the
engine's own programs (those wrap this forward pass in the chunk's scan and
the sampler), so it says whether the kernel and the layers compile and what
one forward pass holds, not the engine's exact peak. Nothing runs: it gives
no time and no result."""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import spec  # noqa: E402


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cfg, dep = cell.config, cell.config["deployment"]

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the program asks jax.default_backend() whether to hand the kernel to
    # Mosaic or to the interpreter; here it has to take its TPU branch
    jax.default_backend = lambda: "tpu"

    ns: dict = {}
    builder = spec.plugin("models", cfg["builder"])
    exec(builder.function_source(cfg), ns)
    pt, slots = dep["serving_page_tokens"], dep["serving_slots"]
    table = -(-cfg["n_positions"] // pt)
    module = ns["Model"]().build().clone(
        page_tokens=pt, kv_pages=slots * table + 1, paged_attn="pallas")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def forward(variables, cache, ids, positions, pages, seq_lens):
        logits, upd = module.apply(
            {**variables, "cache": cache}, ids, decode=True,
            positions=positions, pages=pages, seq_lens=seq_lens,
            mutable=["cache"])
        return logits, upd["cache"]

    i32 = jnp.int32
    shapes = set()
    for w in cell.traffic["warmup"]:
        plen = w["prompt_tokens"]
        width = min(max(_pow2(-(-plen // pt)), 8), table)
        shapes.add(("prefill", _pow2(plen), width))
        end = plen + w["new_tokens"]
        shapes.add(("step", 1, min(max(_pow2(-(-end // pt)), 8), table)))
    for kind, length, width in sorted(shapes):
        ids = jax.ShapeDtypeStruct((slots, length), i32)
        vec = jax.ShapeDtypeStruct((slots,), i32)
        pages = jax.ShapeDtypeStruct((slots, width), i32)
        full = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0),
                                jnp.zeros((slots, length), i32), decode=True,
                                positions=jnp.zeros((slots,), i32),
                                pages=jnp.zeros((slots, width), i32),
                                seq_lens=jnp.ones((slots,), i32)))
        full = nn.meta.unbox(full)
        variables = {"params": full["params"]}
        t0 = time.time()
        compiled = jax.jit(forward, donate_argnums=(1,)).lower(
            on_chip(variables), on_chip(full["cache"]), on_chip(ids),
            on_chip(vec), on_chip(pages), on_chip(vec)).compile()
        m = compiled.memory_analysis()
        kernel = "tpu_custom_call" in compiled.as_text()
        print(f"{cell.name} {kind} tokens={length} table_width={width}: "
              f"compiled in {time.time() - t0:.0f} s, page-walk kernel "
              f"{'present' if kernel else 'ABSENT'}, arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
