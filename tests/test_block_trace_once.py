"""One trace of the block per decode program (ISSUE 29).

A decode apply of ``CausalTransformer`` sends every layer through one jitted
function (``models/gpt.py _decode_block``), so what a program costs to trace
does not grow with its depth, and its layers' kernel equations are one object,
lowered once. Held here: the count of traces and the kernel equations of the
paged engine's own programs, and the numbers against the layer loop as it
was, a Python ``for`` over bound blocks, which this file keeps as the
oracle."""

from typing import Any

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_falcon_h1 import (PT, SLOTS, TABLE, engine, model,  # noqa: F401
                            paged, prompts, serve)
from kubeml_tpu.models.generation import init_cache, init_paged_cache
from kubeml_tpu.models.gpt import (CausalTransformer, GPTBlock, MuP, _norm,
                                   _scaled, block_traces)

VOCAB = 101


def gpt2(depth, ln_eps=1e-5, **kw):
    """A tiny GPT-2. ``ln_eps`` tells one test's blocks from another's:
    equal blocks share their traces across the whole process."""
    m = CausalTransformer(vocab_size=VOCAB, max_len=PT * TABLE, embed_dim=48,
                          depth=depth, num_heads=4, ln_eps=ln_eps,
                          attn_bias=True, **kw)
    return m, m.init(jax.random.PRNGKey(depth), np.zeros((1, 8), np.int32))


def tiny_engine(m, vs):
    """The paged engine of ``test_falcon_h1`` (4 rows, pages of 8 tokens,
    one step a program) around another model."""
    return engine((None, None, m, vs))


# --- the engine's programs: traces and lowered text -----------------------


def traced(dec, program):
    """The engine's own step or admit program, traced for the shapes it
    would be called with; and how often that traced the block."""
    shape = lambda dt, *s: jax.ShapeDtypeStruct(s, dt)
    i32 = lambda *s: shape(jnp.int32, *s)
    slab = jax.tree.map(lambda a: shape(a.dtype, *a.shape),
                        dec._init_slab_impl())
    k, w = dec.slots, dec.table_pages
    before = block_traces()
    if program == "step":
        out = dec._steps[1].trace(dec._variables, slab, i32(k, w))
    else:
        out = dec._prefill_admit.trace(
            dec._variables, slab, i32(k, w), i32(k, 16), i32(k), i32(k),
            i32(k), i32(k), shape(jnp.float32, k), i32(k), i32(k),
            shape(jnp.uint32, k, 2))
    return out.jaxpr.jaxpr, block_traces() - before


def kernel_equations(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, inner jaxprs (the chunk's
    scan) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_equations(sub)
    return found


@pytest.mark.parametrize("program", ["step", "admit"])
def test_a_program_traces_the_block_once_whatever_its_depth(program):
    eps = {"step": 1.1e-5, "admit": 1.2e-5}[program]
    for depth in (2, 5):
        dec = tiny_engine(*gpt2(depth, ln_eps=eps * depth))
        try:
            jaxpr, traces = traced(dec, program)
        finally:
            dec.close()
        assert traces == 1
        # every layer walks its pages with the first layer's kernel, the
        # same object: jax lowers it to Mosaic once and finds it again
        kernels = kernel_equations(jaxpr)
        assert len(kernels) == depth
        assert len({id(e.params["jaxpr"]) for e in kernels}) == 1


def test_equal_blocks_share_a_trace_and_unequal_ones_do_not():
    """Two models in one process (two engines in a test run): the trace is
    found again by the block's fields and the arguments' shapes, never by
    which model or layer asked."""
    def sized(m, vs, table=TABLE):
        before = block_traces()
        init_paged_cache(paged(m), vs, SLOTS, table)
        return block_traces() - before

    assert sized(*gpt2(3, ln_eps=2.1e-5)) == 1
    assert sized(*gpt2(4, ln_eps=2.1e-5)) == 0     # depth is not a field
    assert sized(*gpt2(3, ln_eps=2.2e-5)) == 1
    assert sized(*gpt2(3, ln_eps=2.1e-5), table=2 * TABLE) == 1


# --- the numbers: against a Python loop over bound blocks -----------------


class Layers(nn.Module):
    """The layer loop as it was before ISSUE 29: ``n`` bound blocks named
    ``block_i``, each traced where it stands."""

    fields: Any
    n: int

    @nn.compact
    def __call__(self, x, **at):
        valid = jnp.ones(x.shape[:2], jnp.bool_)
        for i in range(self.n):
            x = GPTBlock(name=f"block_{i}", **self.fields)(
                x, valid, False, True, **at)
        return x


def oracle(m, variables, cache, ids, positions=None, exit_layer=None, **at):
    """``m.apply(..., decode=True, mutable=["cache"])`` with the embedding
    and the head by hand and :class:`Layers` between them."""
    # (a tree fresh from ``init`` holds its leaves boxed with their mesh axes)
    p, mup = nn.meta.unbox(variables["params"]), m.mup or MuP()
    L = ids.shape[1]
    new = dict(cache)
    x = _scaled(p["token_embed"]["embedding"][ids], mup.embedding)
    if m.pos == "learned":
        first = cache["index"] if positions is None else positions[:, None]
        x = x + p["pos_embed"][0][first + jnp.arange(L)]
    if positions is None:
        new["index"] = cache["index"] + L
    n = exit_layer or m.depth
    names = [f"block_{i}" for i in range(n)]
    fields = dict(
        num_heads=m.num_heads, mlp_ratio=m.mlp_ratio, dtype=m.dtype,
        ln_eps=m.ln_eps, attn_bias=m.attn_bias, cache_len=m.max_len,
        rope=m.pos == "rope", rope_theta=m.rope_theta,
        page_tokens=m.page_tokens, kv_pages=m.kv_pages,
        paged_attn=m.paged_attn, kv_quant=m.kv_quant, norm=m.norm, mlp=m.mlp,
        mlp_dim=m.mlp_dim, num_kv_heads=m.num_kv_heads, head_dim=m.head_dim,
        ssm=m.ssm, mup=m.mup, state_rows=m.state_rows)
    x, upd = Layers(fields, n).apply(
        {"params": {k: p[k] for k in names},
         "cache": {k: cache[k] for k in names}},
        x.astype(m.dtype), positions=positions, mutable=["cache"], **at)
    new.update(upd["cache"])
    x = _norm(m.norm, None, m.ln_eps).apply({"params": p["ln_f"]}, x)
    logits = x.astype(m.dtype) @ p["lm_head"]["kernel"].astype(m.dtype)
    return logits.astype(jnp.float32) * mup.lm_head, new


def agree(got, want):
    """Logits and every leaf of the cache, and the cache's tree itself."""
    (glog, gcache), (wlog, wcache) = got, want
    assert (jax.tree_util.tree_structure(gcache)
            == jax.tree_util.tree_structure(wcache))
    assert float(jnp.abs(wlog).max()) > 0.1
    np.testing.assert_allclose(glog, wlog, rtol=0, atol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(gcache),
                            jax.tree.leaves(wcache)):
        assert g.dtype == w.dtype, path
        np.testing.assert_allclose(np.asarray(g, np.float64),
                                   np.asarray(w, np.float64),
                                   rtol=0, atol=1e-5, err_msg=str(path))


def both(m, vs, cache, ids, **kw):
    """The model's decode apply and the oracle on the same inputs."""
    kw = {k: jnp.asarray(v) if k != "exit_layer" else v
          for k, v in kw.items()}
    logits, upd = m.apply({**vs, "cache": cache}, jnp.asarray(ids),
                          decode=True, mutable=["cache"], **kw)
    return (logits, upd["cache"]), oracle(m, vs, cache, jnp.asarray(ids),
                                          **kw)


def tables(rows):
    tbl = np.zeros((len(rows), TABLE), np.int32)
    for i, r in enumerate(rows):
        tbl[i] = 1 + r * TABLE + np.arange(TABLE)
    return tbl


def paged_prefill_and_step(m, vs, recurrent, exit_layer=None):
    """An admission of three rows of different lengths into slab rows 2, 0,
    3, then one decode step over the whole slab."""
    kw = {} if exit_layer is None else {"exit_layer": exit_layer}
    cache = init_paged_cache(m, vs, SLOTS, TABLE)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(cache)]
    for i in range(m.depth):
        assert f"['block_{i}']['attn']['kv_rows']" in paths
        if recurrent:
            assert f"['block_{i}']['mixer']['ssm_state']" in paths
            assert f"['block_{i}']['mixer']['conv_tail']" in paths
    rows, lens = [2, 0, 3], [5, 17, 30]
    ids = np.zeros((3, 32), np.int32)
    for i, (p, n) in enumerate(zip(prompts(3, 40, 40, seed=5), lens)):
        ids[i, :n] = p[:n]
    at = {"rows": np.asarray(rows, np.int32)} if recurrent else {}
    got, want = both(m, vs, cache, ids, positions=np.zeros(3, np.int32),
                     pages=tables(rows), seq_lens=np.asarray(lens, np.int32),
                     **at, **kw)
    agree(got, want)
    pos = np.zeros(SLOTS, np.int32)
    pos[rows] = lens
    got, want = both(m, vs, got[1], np.full((SLOTS, 1), 7, np.int32),
                     positions=pos, pages=tables(range(SLOTS)), **kw)
    agree(got, want)
    return got[1], cache


def test_gpt2_block_paged_prefill_and_step():
    m, vs = gpt2(3)
    paged_prefill_and_step(paged(m), vs, recurrent=False)


def test_falcon_h1_block_paged_prefill_and_step(model):  # noqa: F811
    """Rows, state and tail of the mixer through the shared trace."""
    _, _, module, tree = model
    with jax.default_matmul_precision("highest"):
        paged_prefill_and_step(paged(module), tree, recurrent=True)


def test_exit_layer_leaves_the_later_layers_alone():
    m, vs = gpt2(4)
    after, before = paged_prefill_and_step(paged(m), vs, recurrent=False,
                                           exit_layer=2)
    for name in ("block_2", "block_3"):
        for a, b in zip(jax.tree.leaves(after[name]),
                        jax.tree.leaves(before[name])):
            assert np.array_equal(a, b)
    assert float(jnp.abs(after["block_1"]["attn"]["kv_rows"]).max()) > 0


def test_dense_cache_prefill_and_step():
    """The scalar-cursor cache (models.generation, the slot engine)."""
    m, vs = gpt2(3)
    cache = init_cache(m, vs, 2)
    assert "k" in cache["block_2"]["attn"]
    ids = np.stack(prompts(2, 11, 11, seed=2))
    got, want = both(m, vs, cache, ids)
    agree(got, want)
    assert int(got[1]["block_0"]["attn"]["index"]) == 11
    got, want = both(m, vs, got[1], np.full((2, 1), 9, np.int32))
    agree(got, want)
    # per-row cursors, as the slot engine steps
    got, want = both(m, vs, got[1], np.full((2, 1), 4, np.int32),
                     positions=np.asarray([12, 12], np.int32))
    agree(got, want)


# --- the counter, where an operator reads it ------------------------------


def test_telemetry_block_traces_follows_programs_not_layers():
    depth = 5
    m, vs = gpt2(depth, ln_eps=3.1e-5)
    before = block_traces()
    dec = tiny_engine(m, vs)
    try:
        out = serve(dec, prompts(5, 3, 30, seed=9), 4)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert all(len(t) == 4 for t in out)
    programs = tel["compiled_programs"]
    assert programs >= 2                     # an admit and a step at least
    assert tel["block_traces"] == block_traces()
    traces = tel["block_traces"] - before
    # one a program, and one where the arena was sized
    assert programs <= traces <= programs + 1 < depth * programs
