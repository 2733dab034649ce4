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
