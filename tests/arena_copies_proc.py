"""Subprocess entry for tests/test_arena_copies.py: compile the paged forward
pass of a decode step and of a one-row admit, ahead of time, with the real
TPU compiler against a chipless topology description, at the attention
shapes of the three configurations that use the K/V arena and of the three
that use the latent one, and report every ``copy``, ``copy-start`` or
``transpose`` in the compiled program whose result has the arena's element
count or more. One kind is told apart and allowed in the admit alone, once
an arena: a ``copy-start`` whose source and destination differ in memory
space and in nothing else. XLA's memory-space assignment carries the arena
into fast memory for a 1,024-row scatter and back (asynchronous, no
relayout); a decode step's 4-64 rows do not move it.

Only attention's shapes are the published ones (heads, K/V heads, head size
or the latent attention's ranks, rows, pages): two layers, a small
vocabulary and a narrow MLP, so that no weight is as large as the arena and
a relayout of a weight cannot be taken for one of the pool. LongCat-Flash's
two layers are the two attentions of one double layer, with four small
experts on its side branch.

An admit is compiled as the engine calls it: the one sampled position
handed to the module (``head_positions``), so the head multiplies one row.
The two admit-bound configurations' admits carry their published vocabulary
and bfloat16 weights, as their cells hold them (PR 46), and any array of
``[bucket, vocabulary]`` that the compiled program's entry computation
makes (the head's product over the whole bucket, or its float32 convert)
is reported beside the copies. The head's own weight there is larger than
the arena, so a relayout of it would be reported too, and should be.

Two GPT-2 cases more carry their published token table, ``50257 x 1600``
and ``50257 x 1280`` float32, in step and admit alike, handed over as the
parameter server holds a tree (``serving.quant.held_as``: the products'
operands in bfloat16, the table the lookup gathers held by whole lane rows
where the described chip would store it by columns) and taken as the
engines take it (``quant.unpadded`` inside the program). The device's own
layout for ``f32[50257,1600]`` puts the VOCABULARY on the lanes
(``{0,1:T(8,128)}``: 50,257 pads less than 1,600), and a program that takes
it so answers its row gather with a ``copy`` of all 321 MB, every step and
every admit (PR 50). With the table handed over at its own width, as before
PR 50 (``quant.held_width`` made to give the width back), the two
1,600-wide cases fail, ``COPIES gpt2-xl-embed-step: 2 [('copy',
'50257,1600'), ('move', '257,16,3200')]`` (beside the copy a step then
carries an arena into fast memory) and ``COPIES gpt2-xl-embed-admit: 1
[('copy', '50257,1600')]``, and the two 1,280-wide ones pass: 1,280 is ten
whole lane rows, stored by rows as it is.

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
# name -> (embed, heads, (q_lora_rank, kv_lora_rank, nope, rope, value head
#          dims), scaled latents and a double layer, rows, table pages,
#          admit bucket): the same, of the configurations with a latent arena
LATENT_SHAPES = {
    "glm-4.7-flash": (2048, 20, (768, 512, 192, 64, 256), False,
                      32, 256, 2048),
    "xing4.0-29b-a4b": (3584, 32, (768, 512, 128, 64, 128), False,
                        32, 256, 2048),
    "longcat-flash-omni": (6144, 64, (1536, 512, 128, 64, 128), True,
                           64, 128, 512),
}
# the vocabulary an admit is compiled at, where the head is a fifth or a
# quarter of an admit (extract, rag); 512 elsewhere, as in every step
HEAD_VOCAB = {"glm-4.7-flash": 154880, "xing4.0-29b-a4b": 131072}
# name -> (the SHAPES entry it is, its vocabulary): step and admit alike
# carry the token table, and take the tree as the server holds it
TABLES = {"gpt2-large-embed": ("gpt2-large", 50257),
          "gpt2-xl-embed": ("gpt2-xl", 50257)}
# Kimi-Linear's stack as run (PR 51): two latent arenas WITHOUT rotation
# under a direct query, each behind a Kimi Delta Attention layer whose state
# slab (128 rows of 32 x 128 x 128 float32, 268 MB a layer) the in-place
# ``kda_update`` kernel and the admit's one-row write must not copy either
KIMI = "kimi-linear-48b-a3b"
CASES = {f"{name}-{case}"
         for name in (*SHAPES, *LATENT_SHAPES, *TABLES, KIMI)
         for case in ("step", "admit")}

_SHAPE = r"\w+\[[0-9,]*\](?:\{[^}]*\})?"
_OP = re.compile(rf" = \(?({_SHAPE})(?:, ({_SHAPE}))?.*? "
                 r"(copy|copy-start|transpose)\(")
_SPACE = re.compile(r"S\(\d+\)")
# a page walk (either arena's): the kernel call under the module's name,
# as a device trace shows it; an expert layer's kernels have their own
_WALK = re.compile(r'^\s*%attn[.\d]* = .*custom_call_target="tpu_custom_call"',
                   re.M)


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


def bucket_by_vocab(hlo: str, bucket: int, vocab: int) -> list:
    """``(operation, result dims)`` of every instruction of the entry
    computation, parameters apart, whose result is ``[bucket, vocab]``
    (under leading ones): what a head over all of an admit's positions
    leaves behind. A fused computation's own lines are no arrays in memory
    (a one-row head multiplies the weight by its row broadcast inside
    one), and GLM's head weight is itself 2,048 wide: a parameter."""
    dims = re.compile(rf"\w+\[(?:1,)*{bucket},{vocab}\]")
    entry = hlo[hlo.find("\nENTRY "):]
    out = []
    for line in entry[:entry.find("\n}")].splitlines():
        m = re.search(rf" = \(?({_SHAPE}).*? ([\w-]+)\(", line)
        if m and m.group(2) != "parameter" and dims.match(m.group(1)):
            shape = m.group(1)
            out.append((m.group(2), shape[shape.index("[") + 1:
                                          shape.index("]")]))
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

    from kubeml_tpu.models.experts import ExpertsConfig
    from kubeml_tpu.models.gated_deltanet import KDAConfig
    from kubeml_tpu.models.gpt import AttnKind, CausalTransformer
    from kubeml_tpu.models.mla import MLAConfig
    from kubeml_tpu.serving import quant

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def held(module, variables):
        """``variables`` (abstract) as the server's hold hands them to the
        engine: each leaf in the type of its rule, a table the lookup
        gathers at the width ``quant.rows_on_lanes`` would hold it at on
        the described chip."""
        leaves, treedef = jax.tree.flatten(variables)
        types, rows = quant.held_as(module, variables)
        out = []
        for s, to, by_rows in zip(leaves, types, rows, strict=True):
            leaf = jax.ShapeDtypeStruct(s.shape, to or s.dtype, sharding=chip)
            if by_rows:
                own = jax.jit(lambda t: t[:1]).lower(leaf).compile()
                width = quant.held_width(
                    s.shape, own.input_formats[0][0].layout)
                if width != s.shape[1]:
                    leaf = quant.PaddedRows(jax.ShapeDtypeStruct(
                        (s.shape[0], width), leaf.dtype, sharding=chip),
                        s.shape[1])
            out.append(leaf)
        return treedef.unflatten(out)

    def stack(table, rows, **kw):
        return CausalTransformer(
            vocab_size=512, max_len=table * PT, mlp_dim=512,
            dtype=jnp.bfloat16, page_tokens=PT, kv_pages=rows * table + 1,
            paged_attn="pallas", **kw)

    # (name, module, the arena's key, rows, table, bucket, page walks a
    # step and an admit hold: a latent admit attends without the kernel;
    # cache leaves beside the arenas that may not be copied whole either)
    models = [
        (name, stack(table, rows, embed_dim=embed, depth=2, num_heads=heads,
                     num_kv_heads=kv_heads, head_dim=head_dim,
                     attn_bias=True, pos="rope" if kv_heads else "learned"),
         "kv_rows", rows, table, bucket, {"step": 2, "admit": 2}, ())
        for name, (embed, heads, kv_heads, head_dim, rows, table, bucket)
        in [*SHAPES.items(),
            *((name, SHAPES[of]) for name, (of, _) in TABLES.items())]]
    for name, (embed, heads, (rq, dc, dn, dr, dv), double, rows, table,
               bucket) in LATENT_SHAPES.items():
        mla = MLAConfig(q_lora_rank=rq, kv_lora_rank=dc, qk_nope_head_dim=dn,
                        qk_rope_head_dim=dr, v_head_dim=dv,
                        mla_scale_q_lora=double, mla_scale_kv_lora=double)
        kind = (dict(depth=1, mlp="shortcut", experts=ExpertsConfig(
            n_routed_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=256, n_shared_experts=0))
            if double else dict(depth=2, mlp="swiglu"))
        models.append((name, stack(table, rows, embed_dim=embed,
                                   num_heads=heads, norm="rmsnorm",
                                   pos="rope", mla=mla, **kind),
                       "latent_pages", rows, table, bucket,
                       {"step": 2, "admit": 0}, ()))
    models.append((KIMI, stack(
        192, 128, embed_dim=2304, num_heads=32, norm="rmsnorm", pos="none",
        depth=4, mlp="swiglu", state_rows=128,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, mla_use_nope=True),
        attn_kinds=(AttnKind(), AttnKind(linear=True)),
        attn_pattern=(1, 0, 1, 0), gdn=KDAConfig(num_heads=32, head_dim=128)),
        "latent_pages", 128, 192, 1024, {"step": 2, "admit": 0},
        ("gdn_state",)))

    failed = 0
    i32 = jnp.int32
    for name, module, key, rows, table, bucket, walks, beside in models:

        for case, (b, length) in (("step", (rows, 1)), ("admit", (1, bucket))):
            admit = case == "admit"
            vocab = (TABLES[name][1] if name in TABLES
                     else HEAD_VOCAB.get(name, 512) if admit else 512)
            sized = module.clone(vocab_size=vocab)

            def forward(variables, cache, ids, positions, pages, seq_lens):
                logits, upd = sized.apply(
                    {**quant.unpadded(variables), "cache": cache}, ids,
                    decode=True,
                    positions=positions, pages=pages, seq_lens=seq_lens,
                    head_positions=seq_lens - 1 if admit else None,
                    mutable=["cache"])
                return logits, upd["cache"]

            full = nn.meta.unbox(jax.eval_shape(
                lambda: sized.init(
                    jax.random.PRNGKey(0), jnp.zeros((b, length), i32),
                    decode=True, positions=jnp.zeros((b,), i32),
                    pages=jnp.zeros((b, table), i32),
                    seq_lens=jnp.ones((b,), i32))))
            arenas = [leaf for path, leaf in
                      jax.tree_util.tree_leaves_with_path(full["cache"])
                      if path[-1].key == key]
            assert len(arenas) == 2, (name, len(arenas))
            params = full["params"]
            if name in TABLES:
                variables = held(sized, {"params": params})
            else:
                if vocab != 512:
                    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                        s.shape, jnp.bfloat16), params)
                variables = on_chip({"params": params})
            vec = jax.ShapeDtypeStruct((b,), i32)
            hlo = jax.jit(forward, donate_argnums=(1,)).lower(
                variables, on_chip(full["cache"]),
                on_chip(jax.ShapeDtypeStruct((b, length), i32)),
                on_chip(vec),
                on_chip(jax.ShapeDtypeStruct((b, table), i32)),
                on_chip(vec)).compile().as_text()
            whole = arenas + [
                leaf for path, leaf in
                jax.tree_util.tree_leaves_with_path(full["cache"])
                if path[-1].key in beside]
            found = pool_sized(hlo, min(leaf.size for leaf in whole))
            moves = [f for f in found if f[0] == "move"]
            if case == "admit" and len(moves) <= len(arenas):
                found = [f for f in found if f[0] != "move"]
            calls = len(_WALK.findall(hlo))
            if calls != walks[case]:
                found.append(("page-walk calls", str(calls)))
            if name in HEAD_VOCAB and admit:
                found += bucket_by_vocab(hlo, bucket, vocab)
            if found:
                failed += 1
                print(f"COPIES {name}-{case}: {len(found)} {found[:6]}",
                      flush=True)
            else:
                print(f"OK {name}-{case}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
