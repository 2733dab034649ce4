"""One real row per admission program (ISSUE 31).

The paged engine hands its suffix-prefill program exactly one row, the
request it admits: a wave of n rows is n programs of shape ``(1, bucket)``
under a ``(1, table width)`` page table, the key stays ``("prefill",
(bucket, width))`` with ONE compiled program behind it whatever the waves
that came before, and the only padding left is the bucket's. Held here, on
tiny models on the CPU:

* SHAPES AND COUNTS — every call of the program (admission, intermediate
  chunk, either speculation backend) has one row; the jit's cache and the
  compile tracker hold one program a key; ``prefill_pad_tokens`` is the sum
  of ``bucket - suffix``.
* THE REAL ROW IS WHOLE — greedy streams equal one-shot ``generate``,
  sampled streams equal the slot engine's (which still pads its admits and
  splits a request's key the same way), and both are the same whether the
  rows arrive together or one by one: for the GPT-2 block, for the tiny
  Falcon-H1 block (state in the right slab row, a reused slot from zeros),
  with a prefix hit, with ``prefill_chunk`` set, with ``spec=self`` and
  ``spec=draft``.
"""

import numpy as np
import pytest

import jax

from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import generate, init_paged_cache
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.serving.batcher import PagedBatchingDecoder

# the tiny Falcon-H1 preset, its engine and its module-level admit
import test_falcon_h1 as fh1
from test_falcon_h1 import model  # noqa: F401  (the module's fixture)

VOCAB, SLOTS, PT, BUCKET_MIN = 101, 4, 4, 16


@pytest.fixture(scope="module")
def served():
    m = CausalTransformer(vocab_size=VOCAB, max_len=96, embed_dim=64,
                          depth=2, num_heads=4)
    return m, m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


def gpt_engine(served, **kw):
    m, variables = served
    # pipeline_depth over the slots: a wave of ``slots`` rows has room
    args = dict(slots=SLOTS, page_tokens=PT, chunk_steps=1,
                bucket_min=BUCKET_MIN, pipeline_depth=2 * SLOTS)
    args.update(kw)
    return PagedBatchingDecoder(m, variables, **args)


def spy(dec):
    """Every call of the suffix-prefill program, as the engine made it."""
    calls, run = [], dec._run_program
    first = 4 if dec.spec == "draft" else 2   # past the weights and caches

    def wrapped(program, sig, fn, *args, **kw):
        if program == "prefill":
            ptbl, suffix, _, slens, rowids = args[first:first + 5]
            calls.append(dict(
                sig=sig, kind=kw["kind"], tokens=tuple(suffix.shape),
                table=tuple(ptbl.shape), rows=int(rowids.shape[0]),
                suffix=int(slens[0]), slot=int(rowids[0]),
                state_rows=kw.get("state_rows", 0),
                group=len(kw["group"]) if kw.get("group") else 0))
        return run(program, sig, fn, *args, **kw)

    dec._run_program = wrapped
    return calls


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in lengths]


def one_shot(m, variables, prompt, n):
    out = generate(m, variables, np.asarray(prompt, np.int32)[None],
                   max_new_tokens=n)
    return np.asarray(out.tokens)[0].tolist()


def serve(dec, ps, n_new, together, **req):
    """One request a prompt: all queued before any is awaited, or each
    awaited before the next is sent."""
    def send(i, p):
        kw = dict(req)
        if "seed" in kw:
            kw["seed"] += i
        return dec.submit(GenerateRequest(prompts=[p.tolist()],
                                          max_new_tokens=n_new, **kw))

    if together:
        entries = [send(i, p) for i, p in enumerate(ps)]
        return [dec.wait(e, timeout=300)["tokens"][0] for e in entries]
    return [dec.wait(send(i, p), timeout=300)["tokens"][0]
            for i, p in enumerate(ps)]


def prefill_programs(dec):
    return {k for k in dec.stats._compiled if k[0] == "prefill"}


def assert_one_row_programs(dec, calls):
    """Every call one row; one compiled program a key, in the jit's own
    cache as in the compile tracker."""
    assert calls
    for c in calls:
        assert c["rows"] == 1 and c["tokens"][0] == 1 and c["table"][0] == 1
        assert c["sig"] == (c["tokens"][1], c["table"][1])
    keys = {("prefill", c["sig"]) for c in calls}
    assert prefill_programs(dec) == keys
    assert dec._prefill_admit._cache_size() == len(keys)


# --- a wave of n rows is n programs of one row ----------------------------


@pytest.mark.parametrize("waves", [(1,), (2,), (SLOTS,), (SLOTS, 1, 2),
                                   (1, SLOTS), (2, SLOTS, SLOTS, 1)],
                         ids=lambda w: "-".join(map(str, w)))
def test_wave_of_n_rows_is_n_programs_of_one_row(served, waves):
    """Each wave is ONE request of n prompts, queued under one lock, so the
    loop takes it as one wave wherever it has room. Whatever the wave sizes
    that came before: n admission programs of one row each, one compiled
    program, and no padding but the bucket's."""
    m, variables = served
    dec = gpt_engine(served)
    calls = spy(dec)
    real = 0
    try:
        for w, n in enumerate(waves):
            # one length a wave (a request is a rectangle), all in one
            # bucket (9-16 tokens) and one table width
            plen = 9 + (3 * w + n) % 8
            batch = np.stack(prompts([plen] * n, seed=10 * w + n))
            before = len(calls)
            out = dec.wait(dec.submit(GenerateRequest(
                prompts=batch.tolist(), max_new_tokens=3)), timeout=300)
            wave = calls[before:]
            assert len(wave) == n
            assert len({c["slot"] for c in wave}) == n
            assert all(c["suffix"] == plen for c in wave)
            for p, toks in zip(batch, out["tokens"]):
                assert toks == one_shot(m, variables, p, 3)
            real += n * plen
        tel = dec.telemetry()
    finally:
        dec.close()
    rows = sum(waves)
    assert_one_row_programs(dec, calls)
    assert all(c["kind"] == "admit" and c["group"] == 1
               and c["tokens"] == (1, 16) and c["table"] == (1, 8)
               and c["state_rows"] == 0 for c in calls)
    assert prefill_programs(dec) == {("prefill", (16, 8))}
    assert tel["admission_waves"] == rows
    assert tel["prefill_tokens"] == real
    assert tel["prefill_pad_tokens"] == 16 * rows - real


# --- the streams: one shot, together, one by one --------------------------


@pytest.fixture(scope="module")
def gpt_streams(served):
    """The same seven requests (more than the rows: slots are reused)
    served four ways by fresh engines."""
    ps = prompts([5, 23, 9, 40, 16, 31, 12], seed=3)
    got = {}
    for mode, req in (("greedy", {}),
                      ("sampled", dict(temperature=0.8, top_k=7, seed=42))):
        for together in (True, False):
            dec = gpt_engine(served)
            calls = spy(dec)
            try:
                got[mode, together] = serve(dec, ps, 7, together, **req)
                tel = dec.telemetry()
            finally:
                dec.close()
            assert_one_row_programs(dec, calls)
            assert len(calls) == len(ps)
            assert tel["prefill_pad_tokens"] == sum(
                c["tokens"][1] - c["suffix"] for c in calls)
    return ps, got


@pytest.mark.parametrize("together", [True, False],
                         ids=["together", "one_by_one"])
def test_greedy_streams_equal_one_shot(served, gpt_streams, together):
    m, variables = served
    ps, got = gpt_streams
    assert got["greedy", together] == [one_shot(m, variables, p, 7)
                                       for p in ps]


def test_sampled_streams_do_not_depend_on_arrival(gpt_streams):
    _, got = gpt_streams
    assert got["sampled", True] == got["sampled", False]
    assert got["sampled", True] != got["greedy", True]   # they were drawn


def test_sampled_streams_equal_the_slot_engines(served, gpt_streams):
    """The slot engine (untouched: it still pads an admit to ``slots``
    rows) splits a request's key the same way, so it draws the chain the
    padded paged admit drew."""
    from kubeml_tpu.serving.batcher import BatchingDecoder

    m, variables = served
    ps, got = gpt_streams
    dec = BatchingDecoder(m, variables, slots=SLOTS, chunk_steps=1,
                          bucket_min=BUCKET_MIN)
    try:
        want = serve(dec, ps, 7, True, temperature=0.8, top_k=7, seed=42)
    finally:
        dec.close()
    assert got["sampled", True] == want


# --- recurrent state: the right slab row, a reused slot from zeros --------


@pytest.mark.parametrize("together", [True, False],
                         ids=["together", "one_by_one"])
def test_falcon_streams_equal_one_shot(model, together):  # noqa: F811
    """Nine requests on four rows: every slot is reused, and every stream
    is the one-shot path's, so each admit wrote its state into its own row
    and started from zeros."""
    _, _, module, tree = model
    ps = fh1.prompts(9, 3, 30, seed=21)
    with jax.default_matmul_precision("highest"):
        dec = fh1.engine(model)
        calls = spy(dec)
        try:
            out = serve(dec, ps, 6, together)
        finally:
            dec.close()
        want = [one_shot(module, tree, p, 6) for p in ps]
    assert out == want
    assert_one_row_programs(dec, calls)
    assert len(calls) == len(ps)
    assert all(c["state_rows"] == 1 and c["kind"] == "admit" for c in calls)
    assert sorted({c["slot"] for c in calls}) == list(range(fh1.SLOTS))


def test_falcon_state_lands_in_its_row_and_a_reused_slot_starts_from_zeros(
        model):  # noqa: F811
    """After each one-row admit the slab row it named holds the state a
    lone admit into an empty cache leaves, whatever the row held before."""
    _, _, module, tree = model
    m = fh1.paged(module)
    empty = init_paged_cache(m, tree, fh1.SLOTS, fh1.TABLE)
    ps = fh1.prompts(fh1.SLOTS + 2, 17, 30, seed=22)
    with jax.default_matmul_precision("highest"):
        dec = fh1.engine(model)
        calls = spy(dec)
        try:
            seen = set()
            for i, p in enumerate(ps):
                serve(dec, [p], 1, True)     # ends at its first token
                slot = calls[-1]["slot"]
                _, alone = fh1.admit(m, tree, empty, [slot], [p], 32)
                got = fh1.states(dec._slab.cache, slot)
                for a, b in zip(got, fh1.states(alone, slot)):
                    assert np.abs(a).max() > 0
                    assert float(np.abs(a - b).max()) < 1e-5
                seen.add(slot)
                for other in set(range(fh1.SLOTS)) - seen:
                    assert all(not s.any() for s in
                               fh1.states(dec._slab.cache, other))
        finally:
            dec.close()
    assert len(calls) == len(ps) > len(seen) == fh1.SLOTS   # slots reused


# --- a prefix hit: only the suffix runs, in its own (smaller) bucket ------


def test_prefix_hit_prefills_one_row_of_the_suffix(served):
    m, variables = served
    first = prompts([24], seed=5)[0]
    second = np.concatenate([first[:20], prompts([6], seed=6)[0]])
    dec = gpt_engine(served)
    calls = spy(dec)
    try:
        out = serve(dec, [first, second], 5, together=False)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert out == [one_shot(m, variables, p, 5) for p in (first, second)]
    assert_one_row_programs(dec, calls)
    assert [(c["tokens"], c["suffix"]) for c in calls] == [((1, 32), 24),
                                                           ((1, 16), 6)]
    assert tel["prefix_hits"] == 1 and tel["prefix_tokens_saved"] == 20
    assert tel["prefill_tokens"] == 24 + 6
    assert tel["prefill_pad_tokens"] == (32 - 24) + (16 - 6)


# --- chunked prefill: the same program, one row a chunk -------------------


@pytest.mark.parametrize("together", [True, False],
                         ids=["together", "one_by_one"])
def test_prefill_chunks_are_one_row_programs_under_the_same_keys(
        served, together):
    m, variables = served
    ps = prompts([40, 37, 12], seed=7)
    dec = gpt_engine(served, prefill_chunk_tokens=16)
    calls = spy(dec)
    try:
        out = serve(dec, ps, 5, together)
        keys = prefill_programs(dec)
        # a later wave of another size: nothing new to compile
        late = prompts([38], seed=9)
        again = serve(dec, late, 5, True)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert out == [one_shot(m, variables, p, 5) for p in ps]
    assert again == [one_shot(m, variables, late[0], 5)]
    assert_one_row_programs(dec, calls)
    assert prefill_programs(dec) == keys == {
        ("prefill", (16, 8)), ("prefill", (16, 16))}
    # 40, 37 and 38 tokens: chunks at 0 and 16, the rest admits; 12: one admit
    kinds = [c["kind"] for c in calls]
    assert kinds.count("pchunk") == 2 * 3 and kinds.count("admit") == 4
    assert all(c["tokens"] == (1, 16) for c in calls)
    assert all(c["suffix"] == 16 and c["group"] == 0
               for c in calls if c["kind"] == "pchunk")
    assert tel["prefill_chunks"] == 3 * 3          # two rows, then one again
    assert tel["prefill_tokens"] == 40 + 37 + 12 + 38
    assert tel["prefill_pad_tokens"] == sum(16 - c["suffix"] for c in calls)


# --- speculation: both backends admit through the same one-row arguments --


@pytest.mark.parametrize("backend", ["self", "draft"])
def test_spec_backends_admit_one_row(served, backend):
    m, variables = served
    kw = dict(spec="self", spec_exit_layer=1)
    if backend == "draft":
        dm = CausalTransformer(vocab_size=VOCAB, max_len=96, embed_dim=32,
                               depth=1, num_heads=4)
        kw = dict(spec="draft", draft_module=dm, draft_variables=dm.init(
            jax.random.PRNGKey(5), np.zeros((1, 8), np.int32)))
    ps = prompts([5, 23, 9, 40, 16], seed=8)
    outs = []
    for together in (True, False):
        dec = gpt_engine(served, spec_k=3, spec_adaptive=False, **kw)
        calls = spy(dec)
        try:
            outs.append(serve(dec, ps, 8, together))
            tel = dec.telemetry()
            chk = dec._pool.check()
        finally:
            dec.close()
        assert_one_row_programs(dec, calls)
        assert len(calls) == len(ps) and tel["spec_steps"] > 0
        assert chk["held"] == chk["trie_pages"]
    assert outs[0] == outs[1] == [one_shot(m, variables, p, 8) for p in ps]


# --- the sampled row is taken before the output head (ISSUE 46) -----------
#
# An admission samples one token a row, and the hyper-connection read-out,
# ``ln_f`` and ``lm_head`` are all per-position: handed ``head_positions``
# the module gathers that one position before them and returns
# ``[B, 1, vocab]``. Held here: the row is the one ``take_along_axis`` picked
# from the bucket's logits, for every kind of block; no admission program of
# either engine holds a ``[*, bucket, vocab]`` array any more, while the
# speculative verify still holds all ``k + 1`` rows; and the count
# ``prefill_head_positions`` is one a program row.

import jax.numpy as jnp  # noqa: E402

from kubeml_tpu.models.gpt import MuP  # noqa: E402
from kubeml_tpu.utils import tracing  # noqa: E402

import test_glm_moe_lite as glm  # noqa: E402
import test_hyper_connections as hcx  # noqa: E402

ROWS, TABLE = 3, 8    # an admit of three rows under 8 pages of 8 tokens


def _blocks(name, served, model):  # noqa: F811
    """(module, tree) of one kind of block, all float32."""
    if name == "gpt2":
        return served
    if name == "mup_head":
        m = CausalTransformer(vocab_size=VOCAB, max_len=96, embed_dim=64,
                              depth=2, num_heads=4,
                              mup=MuP(embedding=3.0, lm_head=0.125))
        return m, m.init(jax.random.PRNGKey(1), np.zeros((1, 8), np.int32))
    if name == "falcon_h1":           # recurrent, and a muP head of its own
        return model[2], model[3]
    family = {"latent_experts": glm, "hyper_connected": hcx}[name]
    return family.build(family.tiny_cfg())[2:]


def _admit_logits(m, tree, ids, lens, head_positions):
    """The module called as an admission calls it: ``ids`` [ROWS, bucket]
    from position 0 through each row's own pages."""
    kw = dict(page_tokens=PT * 2, kv_pages=ROWS * TABLE + 1,
              paged_attn="pallas")
    at = {}
    if m.ssm is not None:
        kw["state_rows"] = ROWS
        at["rows"] = jnp.arange(ROWS)
    m = m.clone(**kw)
    cache = init_paged_cache(m, tree, ROWS, TABLE)
    pages = 1 + np.arange(ROWS * TABLE, dtype=np.int32).reshape(ROWS, TABLE)
    logits, _ = jax.jit(lambda cache, hp: m.apply(
        {**tree, "cache": cache}, jnp.asarray(ids), decode=True,
        positions=jnp.zeros((ROWS,), jnp.int32), pages=jnp.asarray(pages),
        seq_lens=jnp.asarray(lens), head_positions=hp, mutable=["cache"],
        **at))(cache, head_positions)
    return np.asarray(logits)


@pytest.mark.parametrize("block", ["gpt2", "falcon_h1", "latent_experts",
                                   "hyper_connected", "mup_head"])
def test_the_head_takes_the_one_row_it_is_given(served, model,  # noqa: F811
                                                block):
    m, tree = _blocks(block, served, model)
    bucket, lens = 32, np.asarray([32, 5, 19], np.int32)
    rng = np.random.default_rng(4)
    ids = np.zeros((ROWS, bucket), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(1, m.vocab_size, size=n)
    with jax.default_matmul_precision("highest"):
        whole = _admit_logits(m, tree, ids, lens, None)
        one = _admit_logits(m, tree, ids, lens, jnp.asarray(lens - 1))
        # and in a plain forward (no cache), at any position
        at = np.asarray([0, 17, 31], np.int32)
        plain = np.asarray(m.apply(tree, jnp.asarray(ids)))
        plain_one = np.asarray(m.apply(tree, jnp.asarray(ids),
                                       head_positions=jnp.asarray(at)))
    assert whole.shape == (ROWS, bucket, m.vocab_size)
    assert one.shape == plain_one.shape == (ROWS, 1, m.vocab_size)
    assert one.dtype == whole.dtype == np.float32
    want = np.take_along_axis(whole, (lens - 1)[:, None, None], axis=1)
    assert np.abs(want).max() > 1e-3      # a muP head scales, not zeroes
    np.testing.assert_allclose(one, want, rtol=0, atol=1e-5)
    np.testing.assert_allclose(
        plain_one, np.take_along_axis(plain, at[:, None, None], axis=1),
        rtol=0, atol=1e-5)


def _abstract(args):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)


def program_spy(dec):
    """Every program the engine dispatches: its record's kind, the jitted
    function and its arguments' shapes (a donated slab is gone after the
    call; its shape is all a trace needs)."""
    seen, run = [], dec._run_program

    def wrapped(program, sig, fn, *args, **kw):
        seen.append((kw["kind"], fn, _abstract(args)))
        return run(program, sig, fn, *args, **kw)

    dec._run_program = wrapped
    return seen


def shapes_of(jaxpr):
    """The shape of every value a jaxpr makes, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from shapes_of(sub)


def over_the_vocabulary(fn, args):
    """The second-to-last sizes of the ``[*, n, vocab]`` values in the trace
    of ``fn``: the positions its heads multiplied, a row of the batch."""
    return {s[-2] for s in shapes_of(fn.trace(*args).jaxpr.jaxpr)
            if len(s) >= 3 and s[-1] == VOCAB}


def _draft(served):
    dm = CausalTransformer(vocab_size=VOCAB, max_len=96, embed_dim=32,
                           depth=1, num_heads=4)
    return dict(spec="draft", draft_module=dm, spec_k=3, spec_adaptive=False,
                draft_variables=dm.init(jax.random.PRNGKey(5),
                                        np.zeros((1, 8), np.int32)))


@pytest.mark.parametrize("engine", ["paged", "paged_chunked", "paged_draft",
                                    "slot"])
def test_no_admission_program_holds_the_buckets_logits(served, engine):
    """Traced as the engine called them. The bucket is 16 and no other
    size of these models is, so a ``[*, 16, vocab]`` value could only be a
    head over the bucket; the walker is shown to find one in the module
    called without the argument, and the verify's ``k + 1`` rows."""
    from kubeml_tpu.serving.batcher import BatchingDecoder

    m, variables = served
    kw = {"paged": {}, "paged_chunked": dict(prefill_chunk_tokens=16),
          "paged_draft": _draft(served)}.get(engine)
    dec = (gpt_engine(served, **kw) if kw is not None else BatchingDecoder(
        m, variables, slots=SLOTS, chunk_steps=1, bucket_min=BUCKET_MIN))
    seen = program_spy(dec)
    try:
        serve(dec, prompts([40, 12] if engine == "paged_chunked" else [12],
                           seed=11), 6, True)
        tel = dec.telemetry()
    finally:
        dec.close()
    prefills = [p for p in seen if p[0] in ("admit", "pchunk")]
    assert {p[0] for p in prefills} == (
        {"admit", "pchunk"} if engine == "paged_chunked" else {"admit"})
    for kind, fn, args in prefills:
        # one position a program row went through a head, no bucket
        assert over_the_vocabulary(fn, args) == {1}, (kind, engine)
    rows = SLOTS if engine == "slot" else 1
    assert tel["prefill_head_positions"] == rows * len(prefills)
    if engine == "paged_draft":
        verifies = [over_the_vocabulary(fn, args)
                    for kind, fn, args in seen if kind == "spec"]
        # the verify accepts against all k + 1 rows (beside them the
        # drafter's one-token heads and its k proposals, stacked)
        assert verifies and all(3 + 1 in v for v in verifies)
    # the walker sees a bucket's head where there is one
    whole = jax.jit(lambda v, ids: m.apply(v, ids))
    assert over_the_vocabulary(
        whole, _abstract((variables, np.zeros((1, 16), np.int32)))) == {16}


def test_prefill_head_positions_is_one_a_program_on_count_and_span(served):
    """Admissions and intermediate chunks alike: the counter grows by one a
    prefill program, and the program's ``engine.dispatch`` span says so; a
    step's span says 0."""
    t = tracing.get_tracer()
    was_on = t.enabled
    t.clear()
    t.enabled = True
    dec = gpt_engine(served, prefill_chunk_tokens=16)
    calls = spy(dec)
    try:
        serve(dec, prompts([40, 37, 12], seed=7), 4, True)
        tel = dec.telemetry()
        spans = [s for s in t.spans() if s.name == "engine.dispatch"]
    finally:
        dec.close()
        t.enabled = was_on
        t.clear()
    assert len(calls) == 3 + 2 * 2      # three admits, two chunks of two rows
    assert tel["prefill_head_positions"] == len(calls)
    assert tel["prefill_tokens"] + tel["prefill_pad_tokens"] == 16 * len(calls)
    by_program = {}
    for s in spans:
        by_program.setdefault(s.attrs["program"], []).append(
            s.attrs["head_positions"])
    assert by_program["admit"] == [1] * 3 and by_program["pchunk"] == [1] * 4
    assert set(by_program["step"]) == {0}
