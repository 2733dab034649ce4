"""Subprocess entry for tests/test_arena_copies.py: compile the paged forward
pass of a decode step and of a one-row admit, ahead of time, with the real
TPU compiler against a chipless topology description, at the attention
shapes of the three configurations that use the K/V arena, and report every
``copy``, ``copy-start`` or ``transpose`` in the compiled program whose
result has the arena's element count or more. One kind is told apart and
allowed in the admit alone, once a layer: a ``copy-start`` whose source and
destination differ in memory space and in nothing else. XLA's memory-space
assignment carries the arena into fast memory for a 1,024-row scatter and
back (asynchronous, no relayout); a decode step's 4-32 rows do not move it.

Only attention's shapes are the published ones (heads, K/V heads, head size,
rows, pages): two layers, a small vocabulary and a narrow MLP, so that no
weight is as large as the arena and a relayout of a weight cannot be taken
for one of the pool.

Prints ``OK <case>`` / ``COPIES <case>: <n> <first few>`` per case; exit 0
when no case holds a forbidden operation, 1 when one does, 77 when this
installation cannot describe a TPU topology (the caller skips)."""

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

PT = 16
# name -> (embed, query heads, K/V heads, head size, rows, table pages,
#          admit bucket): BENCHMARK.json's deployments
SHAPES = {
    "gpt2-large": (1280, 20, 0, 0, 8, 64, 1024),
    "gpt2-xl": (1600, 25, 0, 0, 4, 64, 1024),
    "falcon-h1-34b": (5120, 20, 4, 128, 32, 64, 128),
}

_SHAPE = r"\w+\[[0-9,]*\](?:\{[^}]*\})?"
_OP = re.compile(rf" = \(?({_SHAPE})(?:, ({_SHAPE}))?.*? "
                 r"(copy|copy-start|transpose)\(")
_SPACE = re.compile(r"S\(\d+\)")


def pool_sized(hlo: str, floor: int) -> list:
    """``(operation, result dims)`` of every copy, copy-start or transpose
    whose (first) result holds ``floor`` elements or more; a copy-start
    between memory spaces that changes nothing else is named ``move``."""
    out = []
    for line in hlo.splitlines():
        m = _OP.search(line)
        if not m:
            continue
        dest, src, op = m.groups()
        dims = dest[dest.index("[") + 1:dest.index("]")]
        n = 1
        for d in dims.split(","):
            n *= int(d or 1)
        if n < floor:
            continue
        if (op == "copy-start" and src and dest != src
                and _SPACE.sub("", dest) == _SPACE.sub("", src)):
            op = "move"
        out.append((op, dims))
    return out


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"  # the session's devices stay CPU
    # libtpu asks the environment what host it is on; there is none
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    sys.path.insert(0, os.path.dirname(HERE))  # kubeml_tpu

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu, or one that needs a chip to describe it
        print(f"no TPU topology description here: {type(e).__name__}: {e}")
        return 77
    chip = SingleDeviceSharding(topo.devices[0])
    # the kernel asks jax.default_backend() whether Mosaic or the
    # interpreter gets it; here it has to take its TPU branch
    jax.default_backend = lambda: "tpu"

    import flax.linen as nn

    from kubeml_tpu.models.gpt import CausalTransformer

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    failed = 0
    i32 = jnp.int32
    for name, (embed, heads, kv_heads, head_dim, rows, table,
               bucket) in SHAPES.items():
        module = CausalTransformer(
            vocab_size=512, max_len=table * PT, embed_dim=embed, depth=2,
            num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
            mlp_dim=512, dtype=jnp.bfloat16, attn_bias=True,
            pos="rope" if kv_heads else "learned", page_tokens=PT,
            kv_pages=rows * table + 1, paged_attn="pallas")

        def forward(variables, cache, ids, positions, pages, seq_lens):
            logits, upd = module.apply(
                {**variables, "cache": cache}, ids, decode=True,
                positions=positions, pages=pages, seq_lens=seq_lens,
                mutable=["cache"])
            return logits, upd["cache"]

        for case, (b, length) in (("step", (rows, 1)), ("admit", (1, bucket))):
            full = nn.meta.unbox(jax.eval_shape(
                lambda: module.init(
                    jax.random.PRNGKey(0), jnp.zeros((b, length), i32),
                    decode=True, positions=jnp.zeros((b,), i32),
                    pages=jnp.zeros((b, table), i32),
                    seq_lens=jnp.ones((b,), i32))))
            arena = full["cache"]["block_0"]["attn"]["kv_rows"]
            vec = jax.ShapeDtypeStruct((b,), i32)
            hlo = jax.jit(forward, donate_argnums=(1,)).lower(
                on_chip({"params": full["params"]}), on_chip(full["cache"]),
                on_chip(jax.ShapeDtypeStruct((b, length), i32)),
                on_chip(vec),
                on_chip(jax.ShapeDtypeStruct((b, table), i32)),
                on_chip(vec)).compile().as_text()
            found = pool_sized(hlo, arena.size)
            moves = [f for f in found if f[0] == "move"]
            if case == "admit" and len(moves) <= module.depth:
                found = [f for f in found if f[0] != "move"]
            if hlo.count('custom_call_target="tpu_custom_call"') != 2:
                found.append(("page-walk calls", str(hlo.count(
                    'custom_call_target="tpu_custom_call"'))))
            if found:
                failed += 1
                print(f"COPIES {name}-{case}: {len(found)} {found[:6]}",
                      flush=True)
            else:
                print(f"OK {name}-{case}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
