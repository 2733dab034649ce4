"""What type a served weight is held in (ISSUE 45).

With ``Config.serving_param_dtype`` empty the parameter server asks the
module what it does with each leaf (``serving.quant.held_as``: the serving
forward traced abstractly) and holds in the compute type every leaf whose
every use is a cast to it: the same bits the programs made of it every step
and every admit, read at half the bytes. Held here: which leaves a GPT-2 stack
narrows and which it keeps, that the plain forward answers as the paged
engine's own programs do, that the five families whose leaves are bfloat16
already keep their trees, that the narrowed tree's logits equal the float32
tree's to the bit, what the rule makes of uses it cannot see through, and the
parameter server's hold (its span, its telemetry, serve-time int8 beside
it).

Type, then layout (ISSUE 50): the same trace names the table of which the
programs only gather whole rows (``gathered_rows``: the token lookup), and
the hold keeps that leaf with a row contiguous on the lanes
(``rows_on_lanes``). A TPU stores ``f32[50257, 1600]`` by columns and every
program copied all of it before a lookup; such a table is held padded to
whole lane rows (``PaddedRows``), which the device stores by rows, and the
engines slice it to its own width inside their programs (``unpadded``). The
CPU stores every width by rows and never pads, so here the rule that decides
is fed a TPU's layouts, a padded tree is served beside the plain one, and
the programs compiled for a described v5e are ``test_arena_copies``'."""

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_falcon_h1 as falcon
import test_glm_moe_lite as glm
import test_hyper_connections as xing
import test_longcat_flash as longcat
import test_mimo_v2 as mimo
from test_block_trace_once import gpt2, tiny_engine, traced
from test_falcon_h1 import SLOTS, TABLE, paged, prompts, serve
from test_paged_serving import _finished_job
from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import init_paged_cache
from kubeml_tpu.serving import quant
from kubeml_tpu.serving.quant import (cast_leaves, gathered_rows, held_as,
                                      narrowing_casts)

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)


def held_types(module, tree):
    return held_as(module, tree)[0]


def stack(dtype, ln_eps):
    """A GPT-2 stack of two layers computing in ``dtype`` over float32
    parameters, as a checkpoint hands them over: (module, host tree)."""
    m, vs = gpt2(2, ln_eps=ln_eps, dtype=dtype)
    return m, jax.tree.map(np.asarray, nn.meta.unbox(vs))


def by_path(tree, types):
    paths = ["/".join(str(k.key) for k in path[1:]) for path, _ in
             jax.tree_util.tree_leaves_with_path(tree)]
    return dict(zip(paths, types, strict=True))


# --- the rule on the models -----------------------------------------------


def test_a_bfloat16_stack_narrows_its_products_operands_and_nothing_else():
    m, tree = stack(jnp.bfloat16, 4.1e-5)
    got = by_path(tree, held_types(m, tree))
    products = [[p] for p in ("query", "key", "value", "proj", "mlp_in",
                              "mlp_out", "lm_head")]
    for path, to in got.items():
        *parents, leaf = path.split("/")
        cast = parents[-1:] in products and leaf in ("kernel", "bias")
        assert to == (BF16 if cast else None), path
    assert {p for p, to in got.items() if to is None} == {
        "pos_embed", "token_embed/embedding", "ln_f/scale", "ln_f/bias",
        *(f"block_{i}/{ln}/{leaf}" for i in range(2)
          for ln in ("ln1", "ln2") for leaf in ("scale", "bias"))}


def test_a_float32_stack_narrows_nothing():
    m, tree = stack(jnp.float32, 4.2e-5)
    assert held_types(m, tree) == [None] * len(jax.tree.leaves(tree))


@pytest.mark.parametrize("program", ["step", "admit", "plain"])
def test_the_decode_apply_answers_as_the_programs_that_serve(program):
    """The rule traces a one-token decode apply as a stand-in for the
    programs that serve: read off the paged engine's own step (a scan in a
    jit) and admit, and off the plain forward (``module.apply(params,
    tokens)``: /infer, the one-shot fallback), every leaf gets the same
    answer."""
    m, tree = stack(jnp.bfloat16, 4.3e-5)
    if program == "plain":
        abstract = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
        jaxpr = jax.make_jaxpr(lambda p, t: m.apply(p, t))(
            abstract, jax.ShapeDtypeStruct((1, 8), jnp.int32)).jaxpr
        leaves = jax.tree.leaves(tree)
    else:
        dec = tiny_engine(m, tree)
        try:
            jaxpr, _ = traced(dec, program)
            leaves = jax.tree.leaves(dec._variables)
        finally:
            dec.close()
    assert [l.dtype for l in leaves] == [F32] * len(leaves)
    rule, rows = held_as(m, tree)
    assert narrowing_casts(jaxpr, len(leaves)) == rule
    assert BF16 in rule and None in rule
    assert gathered_rows(jaxpr, len(leaves)) == rows and sum(rows) == 1


FAMILIES = {"falcon": falcon, "glm": glm, "xing": xing, "longcat": longcat,
            "mimo": mimo}


def falcon_files():
    """Falcon's small stack computing in bfloat16 over the float32 leaves
    its builder writes whatever ``param_dtype`` says: (module, tree)."""
    cfg = {**falcon.tiny_cfg(), "compute_dtype": "bfloat16",
           "param_dtype": "bfloat16"}
    ns = {}
    exec(falcon.builder.function_source(cfg), ns)
    tree = {}
    weights = falcon.builder.init_weights(cfg, 3)
    for path, arr in falcon.builder.program_leaves(cfg, weights):
        node = tree
        *parents, leaf = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[leaf] = arr
    return ns["Model"]().build(), tree


def family_model(name):
    """A family's small test stack computing in bfloat16 over the leaves its
    cell serves: the builder's bfloat16 leaves, or Falcon's float32 files
    cast whole as its cell's option does."""
    if name == "falcon":
        module, tree = falcon_files()
        return module, quant.cast_tree(tree, "bfloat16")
    mod = FAMILIES[name]
    _, _, module, tree = mod.build(mod.tiny_cfg(
        compute_dtype="bfloat16", param_dtype="bfloat16"))
    return module, tree


def test_float32_files_of_another_family_narrow_their_products_too():
    """No model's name in the rule: Falcon's float32 files under a module
    computing in bfloat16 give up every product's kernel and keep what is
    used as float32 (norms, the mixer's scalars and convolution, the token
    table)."""
    module, tree = falcon_files()
    got = by_path(tree, held_types(module, tree))
    assert set(got.values()) == {BF16, None}
    for path, to in got.items():
        assert (to == BF16) == path.endswith(
            ("query/kernel", "key/kernel", "value/kernel", "proj/kernel",
             "mlp_gate/kernel", "mlp_up/kernel", "mlp_out/kernel",
             "lm_head/kernel")), path


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_whose_leaves_are_bfloat16_keeps_its_tree(family, tmp_path):
    """The five configurations that hand over bfloat16 leaves: nothing in
    them is wider than what it is cast to, the rule finds nothing, and the
    hold hands back the very leaves it was given."""
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.ps.parameter_server import ParameterServer

    module, tree = family_model(family)
    types = {str(l.dtype) for l in jax.tree.leaves(tree)}
    assert "bfloat16" in types and types <= {"bfloat16", "float32"}
    assert held_types(module, tree) == [None] * len(jax.tree.leaves(tree))
    ps = ParameterServer(config=Config(data_root=tmp_path))
    held, narrowed = ps._held(tree, module)
    assert narrowed == 0
    for was, now in zip(jax.tree.leaves(tree), jax.tree.leaves(held),
                        strict=True):
        assert now is was


# --- equal to the bit -----------------------------------------------------


def test_the_narrowed_trees_logits_equal_the_float32_trees_to_the_bit():
    """An admit and three decode steps of the paged path, same tokens, same
    pages, on the tree as restored and on the tree as held: what enters
    every product, sum and norm is the same bits, so every logit is."""
    m, wide = stack(jnp.bfloat16, 4.4e-5)
    types = held_types(m, wide)
    narrow = cast_leaves(wide, types)
    assert ({l.dtype for l in jax.tree.leaves(narrow)} == {BF16, F32}
            and {l.dtype for l in jax.tree.leaves(wide)} == {F32})
    m = paged(m)
    seqs = [p[:n] for p, n in zip(prompts(2, 30, 30, seed=11), (9, 23))]
    rows = [3, 1]
    ids = np.zeros((2, 32), np.int32)
    tbl = np.zeros((SLOTS, TABLE), np.int32)
    for i, (r, s) in enumerate(zip(rows, seqs)):
        ids[i, :len(s)] = s
        tbl[r] = 1 + r * TABLE + np.arange(TABLE)
    admit = jax.jit(lambda tree, c: m.apply(
        {**tree, "cache": c}, jnp.asarray(ids), decode=True,
        positions=jnp.zeros((2,), jnp.int32), pages=jnp.asarray(tbl[rows]),
        seq_lens=jnp.asarray([len(s) for s in seqs], jnp.int32),
        mutable=["cache"]))
    step = jax.jit(lambda tree, c, tok, pos: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=jnp.asarray(tbl), mutable=["cache"]))
    out = {}
    for name, tree in (("wide", wide), ("narrow", narrow)):
        cache = init_paged_cache(m, tree, SLOTS, TABLE)
        logits, upd = admit(tree, cache)
        seen, cache = [np.asarray(logits)], upd["cache"]
        pos = np.zeros((SLOTS,), np.int32)
        pos[rows] = [len(s) for s in seqs]
        for t in range(3):
            tok = (1 + (7 * t + np.arange(SLOTS)) % 100).astype(np.int32)
            logits, upd = step(tree, cache, jnp.asarray(tok),
                               jnp.asarray(pos))
            seen.append(np.asarray(logits)[rows])
            cache, pos = upd["cache"], pos + 1
        out[name] = seen
    assert len(out["wide"]) == 4
    for a, b in zip(out["wide"], out["narrow"]):
        assert a.dtype == b.dtype and np.abs(a).max() > 0
        assert np.array_equal(a, b)


def test_an_engine_serves_the_same_tokens_from_either_tree():
    m, wide = stack(jnp.bfloat16, 4.5e-5)
    narrow = cast_leaves(wide, held_types(m, wide))
    ps = prompts(5, 6, 28, seed=12)
    served = []
    for tree in (wide, narrow):
        dec = tiny_engine(m, tree)
        try:
            served.append(serve(dec, ps, 6))
            held = dec.telemetry()["param_bytes"]
        finally:
            dec.close()
    assert served[0] == served[1]
    assert held == sum(l.nbytes for l in jax.tree.leaves(narrow))


# --- what the rule sees through, and what it does not ---------------------


def _tied(w, x):
    # the table looked up as float32 and multiplied as bfloat16: a tied head
    h = jnp.take(w, x, axis=0).astype(jnp.bfloat16)
    return h @ w.astype(jnp.bfloat16).T


def _two_types(w, x):
    return (w.astype(jnp.bfloat16)[x].astype(jnp.float32)
            + w.astype(jnp.float16)[x])


def _through_a_jit_and_a_scan(w, x):
    inner = jax.jit(lambda w, h: h @ w.astype(jnp.bfloat16))

    def one(h, _):
        return inner(w, h), None

    h = jnp.ones((x.shape[0], w.shape[0]), jnp.bfloat16)
    return jax.lax.scan(one, h, None, length=2)[0]


def _carried(w, x):
    return jax.lax.scan(lambda c, _: (c * 2, c.astype(jnp.bfloat16)), w,
                        None, length=2)[1]


def _branch(w, x):
    return jax.lax.cond(x[0] > 0, lambda w: w.astype(jnp.bfloat16),
                        lambda w: w.astype(jnp.bfloat16), w)


def _handed_back(w, x):
    return w.astype(jnp.bfloat16)[x], w


def _unused(w, x):
    return x + 1


def _widened(w, x):
    return w.astype(jnp.float64 if jax.config.jax_enable_x64
                    else jnp.float32)[x]


@pytest.mark.parametrize("fn, want", [
    (_through_a_jit_and_a_scan, BF16), (_tied, None), (_two_types, None),
    (_carried, None), (_branch, None), (_handed_back, None),
    (_unused, None), (_widened, None)],
    ids=lambda v: getattr(v, "__name__", None))
def test_the_rule_errs_to_the_type_a_leaf_has(fn, want):
    """Held narrower: only a leaf whose every use is a cast to one narrower
    floating type, a nested jit's operand and a scan's constant followed
    inside. Kept: a leaf also used as float32 (a tied head would be one),
    cast to two types, carried through a loop, handed to a branch, handed
    back, not used, or cast to nothing narrower."""
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((4,), jnp.int32)
    assert narrowing_casts(jax.make_jaxpr(fn)(w, x).jaxpr, 1) == [want]
    ints = jax.ShapeDtypeStruct((16, 16), jnp.int32)
    if fn is _through_a_jit_and_a_scan:
        assert narrowing_casts(jax.make_jaxpr(fn)(ints, x).jaxpr, 1) == [None]


class TiedLM(nn.Module):
    """A token-in model whose head is its embedding table."""

    @nn.compact
    def __call__(self, tokens):
        table = self.param("table", nn.initializers.normal(0.02), (32, 16))
        h = nn.Dense(16, dtype=jnp.bfloat16)(jnp.take(table, tokens, axis=0))
        return h @ table.astype(jnp.bfloat16).T


def test_a_tied_head_is_kept_beside_a_product_that_is_narrowed():
    m = TiedLM()
    tree = m.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    got = by_path(tree, held_types(m, tree))
    assert got == {"Dense_0/bias": BF16, "Dense_0/kernel": BF16,
                   "table": None}


# --- type, then layout ----------------------------------------------------


@pytest.mark.parametrize("family", ["gpt2", "gpt2-float32", "falcon-files",
                                    *sorted(FAMILIES)])
def test_the_hold_names_the_table_the_lookup_gathers_and_nothing_else(family):
    """Not ``pos_embed`` (a slice of three axes), not the head, not a
    kernel, not an expert stack: the one leaf whose whole rows a gather
    takes, in every family, whatever type it computes in."""
    if family.startswith("gpt2"):
        module, tree = stack(jnp.float32 if family.endswith("32")
                             else jnp.bfloat16, 4.6e-5)
    elif family == "falcon-files":
        module, tree = falcon_files()
    else:
        module, tree = family_model(family)
    _, rows = held_as(module, tree)
    named = [path for path, by_rows in by_path(tree, rows).items() if by_rows]
    assert named == ["token_embed/embedding"]


def _lookup(w, x):
    return jnp.take(w, x, axis=0)


def _indexed(w, x):
    return w[x]


def _columns(w, x):
    return w[:, x]


def _cast_first(w, x):
    return jnp.take(w.astype(jnp.bfloat16), x, axis=0)


def _part_of_a_row(w, x):
    return jax.lax.gather(
        w, x[:, None], jax.lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(0,),
            start_index_map=(0,)), slice_sizes=(1, 8))


def _one_row_sliced(w, x):
    return jax.lax.dynamic_slice(w, (x[0], 0), (1, 16))


def _looked_up_in_a_jit_in_a_scan(w, x):
    inner = jax.jit(_lookup)
    return jax.lax.scan(lambda c, i: (c, inner(w, i)), 0, x)[1]


@pytest.mark.parametrize("fn, want", [
    (_lookup, True), (_indexed, True),
    (_looked_up_in_a_jit_in_a_scan, True), (_tied, False), (_columns, False),
    (_cast_first, False), (_part_of_a_row, False), (_one_row_sliced, False),
    (_through_a_jit_and_a_scan, False), (_unused, False)],
    ids=lambda v: getattr(v, "__name__", None))
def test_gathers_of_whole_rows_and_no_other_use_name_a_table(fn, want):
    """Named: a leaf of which whole rows are gathered and nothing else is
    done, through a nested jit and as a scan's constant too. Not named: a
    table a product reads too (a tied head: padded columns would enter it),
    a gather of columns or of part of a row, a lookup in a cast copy (the
    copy is what is gathered), a slice, a product."""
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((4,), jnp.int32)
    assert gathered_rows(jax.make_jaxpr(fn)(w, x).jaxpr, 1) == [want]
    cube = jax.ShapeDtypeStruct((2, 16, 16), jnp.float32)
    if fn in (_lookup, _indexed):
        assert gathered_rows(jax.make_jaxpr(fn)(cube, x).jaxpr, 1) == [False]


def tpu_layout(*major_to_minor):
    from jax.experimental.layout import Layout

    return Layout(major_to_minor=major_to_minor, tiling=((8, 128),))


@pytest.mark.parametrize("shape, layout, want", [
    # GPT-2-XL's table as a v5e stores it: the vocabulary on the lanes
    ((50257, 1600), tpu_layout(1, 0), 1664),
    # gpt2-large's, GLM's, a table one lane row wide: by rows already
    ((50257, 1280), tpu_layout(0, 1), 1280),
    ((154880, 2048), tpu_layout(0, 1), 2048),
    ((101, 128), tpu_layout(0, 1), 128),
    # by columns though its rows are whole: padding would change nothing
    ((50304, 1664), tpu_layout(1, 0), 1664),
    # a device that says nothing of tiles (the CPU), or of layouts
    ((101, 48), None, 48),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_a_table_is_held_at_whole_lane_rows_where_the_device_says_columns(
        shape, layout, want):
    assert quant.held_width(shape, layout) == want


def test_the_cpu_stores_a_table_by_rows_and_the_hold_pads_nothing():
    from jax.experimental.layout import Layout

    assert quant.held_width((101, 48), Layout(major_to_minor=(1, 0))) == 48
    table = np.arange(101 * 48, dtype=np.float32).reshape(101, 48)
    held = quant.rows_on_lanes(table)
    assert isinstance(held, jax.Array) and not held.committed
    assert np.array_equal(held, table)
    assert quant.rows_on_lanes(held) is held


def padded(tree, lanes=128):
    """``tree`` with its token table held as a chip holds XL's: the rows
    padded to whole lane rows."""
    table = tree["params"]["token_embed"]["embedding"]
    pad = -table.shape[1] % lanes
    held = jax.tree.map(lambda l: l, tree)
    held["params"]["token_embed"]["embedding"] = quant.PaddedRows(
        jnp.pad(jnp.asarray(table), ((0, 0), (0, pad))), table.shape[1])
    return held


def test_a_padded_table_is_the_same_table_inside_a_program():
    """``unpadded`` traced into a program hands ``nn.Embed`` the table's own
    shape and the same float32 bits; the node is one leaf of the tree, its
    width static; and the bytes the padding adds are counted."""
    m, tree = stack(jnp.bfloat16, 4.7e-5)
    table = tree["params"]["token_embed"]["embedding"]
    assert table.shape == (101, 48) and table.dtype == F32
    held = padded(tree)
    node = held["params"]["token_embed"]["embedding"]
    assert node.rows.shape == (101, 128) and node.width == 48
    assert len(jax.tree.leaves(held)) == len(jax.tree.leaves(tree))
    assert quant.padded_bytes(held) == 101 * 80 * 4
    assert quant.padded_bytes(tree) == 0
    ids = jnp.asarray(prompts(2, 8, 8, seed=13))
    embed = nn.Embed(*table.shape)
    lookup = jax.jit(lambda t, i: embed.apply({"params": quant.unpadded(
        {"embedding": t})}, i))
    rows = [np.asarray(lookup(t, ids)) for t in (table, node)]
    assert rows[0].any() and np.array_equal(*rows)
    back = jax.jit(quant.unpadded)(held)
    for was, now in zip(jax.tree.leaves(tree), jax.tree.leaves(back),
                        strict=True):
        assert was.shape == now.shape and np.array_equal(was, now)
    assert jax.device_put(held)["params"]["token_embed"][
        "embedding"].width == 48


@pytest.mark.parametrize("engine", ["paged", "slot"])
def test_an_engine_serves_the_same_tokens_from_a_padded_table(engine):
    """Both engines' programs slice the held table before the module sees
    it: the served tokens are the plain tree's."""
    from kubeml_tpu.serving.batcher import BatchingDecoder

    m, tree = stack(jnp.bfloat16, 4.9e-5)
    ps = prompts(3, 6, 20, seed=14)
    served = []
    for t in (tree, padded(tree)):
        dec = (tiny_engine(m, t) if engine == "paged"
               else BatchingDecoder(m, t, slots=4, chunk_steps=4))
        try:
            held = dec._variables["params"]["token_embed"]["embedding"]
            assert isinstance(held, quant.PaddedRows) == (t is not tree)
            served.append(serve(dec, ps, 5))
            if engine == "paged":
                assert dec.telemetry()["param_bytes"] == sum(
                    l.nbytes for l in jax.tree.leaves(t))
        finally:
            dec.close()
    assert served[0] == served[1]


def test_the_servers_dense_view_is_the_table_at_its_own_width():
    """``/infer`` and the one-shot fallback take the held tree through
    ``_densified``: a plain tree, the table as the module made it."""
    from kubeml_tpu.ps.parameter_server import ParameterServer

    m, tree = stack(jnp.bfloat16, 5.0e-5)
    dense = ParameterServer._densified(padded(tree))
    tokens = jnp.asarray(prompts(2, 8, 8, seed=15))
    assert np.array_equal(m.apply(dense, tokens), m.apply(tree, tokens))


def test_a_table_the_device_lays_out_by_rows_comes_back_as_it_went_in(
        tmp_path):
    """128 wide, whole lane rows, float32 under a float32 module: nothing to
    narrow and nothing to lay out, so the hold hands back the very leaves
    it was given, the table it names among them."""
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.ps.parameter_server import ParameterServer

    m = CausalTransformer(vocab_size=101, max_len=64, embed_dim=128, depth=2,
                          num_heads=4, attn_bias=True, ln_eps=4.8e-5)
    tree = nn.meta.unbox(m.init(jax.random.PRNGKey(2),
                                np.zeros((1, 8), np.int32)))
    types, rows = held_as(m, tree)
    assert types == [None] * len(rows) and sum(rows) == 1
    held, narrowed = ParameterServer(
        config=Config(data_root=tmp_path))._held(tree, m)
    assert narrowed == 0
    for was, now in zip(jax.tree.leaves(tree), jax.tree.leaves(held),
                        strict=True):
        assert now is was and not now.committed


# --- the parameter server's hold ------------------------------------------


def loaded(tmp_path, monkeypatch, **config):
    """A finished job of a GPT-2 stack computing in bfloat16 over a float32
    checkpoint, loaded by a first /generate with the tracer on: (answer,
    the hold's span, the decoder's telemetry, the served tree's leaves)."""
    import test_paged_serving
    from kubeml_tpu.ps.parameter_server import ParameterServer
    from kubeml_tpu.utils import tracing

    monkeypatch.setattr(
        test_paged_serving, "PAGED_FN", test_paged_serving.PAGED_FN.replace(
            "num_heads=4)", "num_heads=4, dtype='bfloat16')"))
    cfg, reg = _finished_job(tmp_path, **config)
    ps = ParameterServer(registry=reg, config=cfg)
    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enabled = True
    try:
        out = ps.generate("pagedjob", GenerateRequest(
            prompts=[[1, 2, 3, 4, 5, 6, 7, 8]], max_new_tokens=4))
        (hold,) = [s for s in tracer.spans() if s.name == "ps.serving.hold"]
        dec = ps._decoders["pagedjob"][0]
        return (out, hold, dec.telemetry(),
                jax.tree.leaves(dec._variables, is_leaf=quant._is_q))
    finally:
        tracer.enabled = was_on
        tracer.clear()
        for dec, _ in ps._decoders.values():
            dec.close()


@pytest.mark.paged
def test_a_finished_job_is_held_as_its_module_uses_it(tmp_path, monkeypatch):
    out, hold, tel, leaves = loaded(tmp_path, monkeypatch)
    assert len(out["tokens"][0]) == 4
    assert (hold.attrs["from"], hold.attrs["to"]) == (
        "float32", "bfloat16,float32")
    narrowed = [l for l in leaves if l.dtype == BF16]
    kept = [l for l in leaves if l.dtype == F32]
    assert hold.attrs["narrowed"] == len(narrowed) > 0
    assert hold.attrs["kept"] == len(kept) > 0
    assert len(narrowed) + len(kept) == len(leaves)
    assert hold.attrs["narrowed_bytes"] == 2 * sum(l.size for l in narrowed)
    assert tel["param_bytes"] == hold.attrs["bytes"] == (
        2 * sum(l.size for l in narrowed) + 4 * sum(l.size for l in kept))
    assert tel["param_leaves_narrowed"] == len(narrowed)
    assert hold.attrs["programs"] >= 1
    assert hold.attrs["row_tables"] == 1 and hold.attrs["padded_bytes"] == 0


@pytest.mark.paged
def test_a_job_held_by_padded_rows_answers_as_the_plain_hold(tmp_path,
                                                             monkeypatch):
    """A device that stores the job's 64-wide table by columns, played by a
    rule that pads every named table to 128: the hold's tree carries the
    padded node, its span the bytes, and the first /generate the tokens of
    the hold that pads nothing."""
    (tmp_path / "plain").mkdir()
    (tmp_path / "padded").mkdir()
    plain, hold, _, leaves = loaded(tmp_path / "plain", monkeypatch)
    assert hold.attrs["padded_bytes"] == 0
    monkeypatch.setattr(quant, "held_width",
                        lambda shape, layout: -(-shape[1] // 128) * 128)
    out, hold, tel, held = loaded(tmp_path / "padded", monkeypatch)
    assert out["tokens"] == plain["tokens"]
    wider = [(a.shape, b.shape) for a, b in zip(leaves, held, strict=True)
             if a.shape != b.shape]
    assert len(wider) == hold.attrs["row_tables"] == 1
    (table, rows), = wider
    assert rows == (table[0], 128) and table[1] % 128
    assert hold.attrs["padded_bytes"] == 4 * table[0] * (128 - table[1])
    assert tel["param_bytes"] == hold.attrs["bytes"]


@pytest.mark.paged
def test_serve_time_int8_quantizes_the_checkpoints_own_values(tmp_path,
                                                              monkeypatch):
    """A process that quantizes at serve time skips the narrow hold: the
    quantizer sees float32, and its values and scales are those made from
    the checkpoint's tree."""
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore

    _, hold, tel, leaves = loaded(tmp_path, monkeypatch,
                                  serving_quantize="int8")
    assert (hold.attrs["from"], hold.attrs["to"]) == ("float32", "float32")
    assert hold.attrs["narrowed"] == hold.attrs["narrowed_bytes"] == 0
    assert tel["param_leaves_narrowed"] == 0
    from kubeml_tpu.api.config import Config

    ck = CheckpointStore(config=Config(data_root=tmp_path)).restore(
        "pagedjob", tag=FINAL_TAG)
    want = jax.tree.leaves(quant.quantize_tree(ck.variables),
                           is_leaf=quant._is_q)
    assert any(quant._is_q(l) for l in want)
    for a, b in zip(leaves, want, strict=True):
        assert quant._is_q(a) == quant._is_q(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
            assert x.dtype == y.dtype and np.array_equal(x, y)
