"""What type a served weight is held in (ISSUE 45).

With ``Config.serving_param_dtype`` empty the parameter server asks the
module what it does with each leaf (``serving.quant.held_types``: the serving
forward traced abstractly) and holds in the compute type every leaf whose
every use is a cast to it: the same bits the programs made of it every step
and every admit, read at half the bytes. Held here: which leaves a GPT-2 stack
narrows and which it keeps, that the plain forward answers as the paged
engine's own programs do, that the five families whose leaves are bfloat16
already keep their trees, that the narrowed tree's logits equal the float32
tree's to the bit, what the rule makes of uses it cannot see through, and the
parameter server's hold (its span, its telemetry, serve-time int8 beside
it)."""

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
from kubeml_tpu.serving.quant import (cast_leaves, held_types,
                                      narrowing_casts)

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)


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
    rule = held_types(m, tree)
    assert narrowing_casts(jaxpr, len(leaves)) == rule
    assert BF16 in rule and None in rule


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
