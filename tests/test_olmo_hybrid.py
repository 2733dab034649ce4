"""Olmo-Hybrid's stack through the normal path (ISSUE 48): Gated DeltaNet
layers that keep a recurrent state and NO paged cache, three to every
full-attention layer (``models/gpt.py AttnKind.linear``,
``models/gated_deltanet.py``, ``ops/gated_delta.py``), RMSNorm on each
branch's output, normed whole query and key projections, no positional term.

Everything here runs a tiny preset with the published structure (hidden 96;
8 layers: linear x3, full, twice; 6 heads, so THREE stored head pairs; keys
of 8 and values of 16 in the linear layers, ``d_v = 2 d_k`` as published;
attention heads of 16; pages of 4) in float32 on the CPU, built by the
benchmark's own builder and held against the benchmark's plain reference
(``benchmark/reference/olmo_hybrid.py``)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.models import olmo_hybrid as builder  # noqa: E402
from benchmark.reference import olmo_hybrid as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.generation import (generate,  # noqa: E402
                                          init_paged_cache,
                                          supports_paged_decode)
from kubeml_tpu.ops import gated_delta as gd  # noqa: E402
from kubeml_tpu.serving.batcher import (BatchingDecoder,  # noqa: E402
                                        CacheFeatureUnsupported,
                                        PagedBatchingDecoder)

ROOT = Path(__file__).resolve().parent.parent
# float32 against float32 at precision "highest": what is left is the order
# of summation (a chunk's triangular solve and masked products against a
# scan over positions; an online softmax over pages against one softmax).
# Logits are about 4 wide; 5e-4 is a fiftieth of a bfloat16 rounding of
# one, and a state kept in bfloat16, a missing delta term and a missing
# decay each miss it by one to three orders (below).
TOL = 5e-4
VOCAB, PT, SLOTS, TABLE = 211, 4, 4, 16
H, DK, DV = 6, 8, 16


def tiny_cfg(**over):
    cfg = json.loads((ROOT / "benchmark/tests/data_olmo/configs/"
                      "tiny-olmo.json").read_text())
    cfg.update(compute_dtype="float32", param_dtype="float32", n_positions=64)
    cfg.update(over)
    return cfg


def tree_of(leaves):
    tree = {}
    for path, arr in leaves:
        node = tree
        *parents, leaf = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[leaf] = jnp.asarray(arr)
    return tree


def build(cfg, seed=3):
    weights = builder.init_weights(cfg, seed)
    ns = {}
    exec(builder.function_source(cfg), ns)
    return (cfg, weights, ns["Model"]().build(),
            tree_of(builder.program_leaves(cfg, weights)))


@pytest.fixture(scope="module")
def model():
    return build(tiny_cfg())


def ref_logits(cfg, weights, ids, at, precision="float32"):
    """The reference's logits at positions ``at`` of ``ids``, both padded to
    the preset's 64 positions so that every call is one compiled program
    (padding behind a causal model's last position is harmless)."""
    T = cfg["n_positions"]
    ids, at = np.asarray(ids, np.int32), np.asarray(at, np.int32)
    with jax.default_matmul_precision("highest"):
        out = reference.logits_at(
            weights, jnp.asarray(np.pad(ids, (0, T - len(ids)))),
            jnp.asarray(np.pad(at, (0, T - len(at)))),
            n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"],
            precision=precision)
    return out[:len(at)]


def prompts(n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, int(rng.integers(lo, hi + 1))).astype(
        np.int32) for _ in range(n)]


# --- the recurrence, three ways ---------------------------------------------


def _inputs(L, b=2, seed=0):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    l2 = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = l2(normal(b, L, H, DK)) * DK ** -0.5
    k = l2(normal(b, L, H, DK))
    v = normal(b, L, H, DV)
    # decays from e^-1.6 a step to nearly none, beta over (0, 2)
    g = -16.0 * rng.random((b, L, H), np.float32) * np.log1p(
        np.exp(normal(b, L, H) - 3.0))
    beta = 2.0 / (1.0 + np.exp(-normal(b, L, H)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


# jitted: op by op the chunked form is three dozen small programs a call
_sequential = jax.jit(gd.gdn_sequential)
_chunked = jax.jit(gd.gdn_chunked, static_argnames=("chunk",))


@pytest.mark.parametrize("L,chunk", [(64, 64), (128, 64), (37, 64), (100, 64),
                                     (5, 64), (1, 64), (50, 16), (48, 16),
                                     (200, 32)])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_matches_sequential(L, chunk, carried):
    a = _inputs(L, seed=L)
    S0 = (jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, H, DK, DV)), jnp.float32) if carried else None)
    o1, S1 = _sequential(*a, init_state=S0)
    o2, S2 = _chunked(*a, chunk=chunk, init_state=S0)
    assert float(jnp.abs(o1 - o2).max()) < 2e-5
    assert float(jnp.abs(S1 - S2).max()) < 2e-5


def test_masked_positions_leave_the_state_alone():
    """``g = beta = 0`` past a row's length: the state after 70 padded
    positions is the state after the 23 real ones."""
    q, k, v, g, beta = _inputs(70, seed=1)
    keep = (jnp.arange(70) < 23).astype(jnp.float32)[None, :, None]
    _, S_pad = _chunked(q, k, v, g * keep, beta * keep, chunk=32)
    _, S_cut = _sequential(*(a[:, :23] for a in (q, k, v, g, beta)))
    assert float(jnp.abs(S_pad - S_cut).max()) < 2e-5


def test_the_delta_term_and_the_decay_are_in_the_oracle():
    """What the chunked scan is held to is the delta rule: without the
    ``S^T k`` term, or without the decay, the sequential form itself gives
    another state."""
    q, k, v, g, beta = _inputs(40, seed=2)
    _, S = _sequential(q, k, v, g, beta)
    _, undecayed = _sequential(q, k, v, jnp.zeros_like(g), beta)
    plain = jnp.einsum("blhk,blhv->bhkv", k * beta[..., None], v)
    assert float(jnp.abs(S - undecayed).max()) > 0.05
    assert float(jnp.abs(undecayed - plain).max()) > 0.05


@pytest.mark.parametrize("heads,dk,dv", [(6, 8, 16), (2, 8, 16), (5, 8, 16),
                                         (4, 8, 128)])
def test_gdn_update_kernel_matches_one_sequential_position(heads, dk, dv):
    """Three stored pairs (an odd count), one pair, an odd head count
    (nothing to pair) and values of whole lane rows (no need to)."""
    R = 5
    ks = jax.random.split(jax.random.key(heads), 7)
    S0 = jax.random.normal(ks[0], (R, heads, dk, dv))
    q = jax.random.normal(ks[1], (R, heads, dk)) * 0.3
    k = jax.random.normal(ks[2], (R, heads, dk)) * 0.3
    v = jax.random.normal(ks[3], (R, heads, dv))
    g = -jax.random.uniform(ks[4], (R, heads)) * 1.6
    beta = 2.0 * jax.random.uniform(ks[5], (R, heads))
    live = jnp.asarray([1, 0, 1, 1, 0], jnp.float32)[:, None]
    g, beta = g * live, beta * live
    o_ref, S_ref = gd.gdn_update_reference(S0, q, k, v, g, beta)
    packed = gd.pack_state(S0)
    assert packed.shape == (R, heads // gd.heads_packed(heads, dv), dk,
                            gd.heads_packed(heads, dv) * dv)
    assert gd.heads_packed(heads, dv) == (2 if (heads, dv) in (
        (6, 16), (2, 16)) else 1)
    o, S = gd.gdn_update(packed, q, k, v, g, beta, interpret=True)
    S = gd.unpack_state(S, heads)
    assert float(jnp.abs(o - o_ref)[live[:, 0] > 0].max()) < 1e-5
    assert float(jnp.abs(S - S_ref).max()) < 1e-5
    # a dead row gets its state back bit for bit
    assert jnp.array_equal(S[1], S0[1]) and jnp.array_equal(S[4], S0[4])


def test_gdn_update_writes_the_state_in_place():
    """The state is aliased to the kernel's output: the lowered call says
    so, and a donated state is not copied."""
    args = (jnp.zeros((4, 3, DK, 2 * DV)), jnp.zeros((4, H, DK)),
            jnp.zeros((4, H, DK)), jnp.zeros((4, H, DV)), jnp.zeros((4, H)),
            jnp.zeros((4, H)))
    fn = lambda *a: gd.gdn_update(*a, interpret=False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "gdn_update" in text
    assert "output_operand_aliases" in text


# --- the model --------------------------------------------------------------


def test_whole_model_matches_reference(model):
    cfg, weights, module, tree = model
    assert supports_paged_decode(module)
    spec = cache_spec(module)
    assert spec.properties == {"recurrent"}
    assert (spec.sublayers, spec.full_layers, spec.state_layers) == (2, 2, 6)
    ids = np.stack([p[:40] for p in prompts(2, 40, 40, seed=1)])
    with jax.default_matmul_precision("highest"):
        got = module.apply(tree, jnp.asarray(ids))
    for row, out in zip(ids, got):
        want = ref_logits(cfg, weights, row, np.arange(40))
        assert float(jnp.abs(out - want).max()) < TOL


@pytest.mark.parametrize("control,least", [
    ("delta_off", 0.05), ("decay_off", 0.05), ("qknorm_off", 0.05),
    ("bfloat16", 0.005)])
def test_a_control_departs(model, control, least):
    """Each planted fault, and the whole forward in bfloat16, lies well
    outside the tolerance the program is held to."""
    cfg, weights, _, _ = model
    ids = prompts(1, 48, 48, seed=2)[0]
    want = ref_logits(cfg, weights, ids, np.arange(48))
    got = ref_logits(cfg, weights, ids, np.arange(48), precision=control)
    assert float(jnp.abs(got - want).max()) > max(least, 10 * TOL)


def paged(module):
    return module.clone(page_tokens=PT, kv_pages=SLOTS * TABLE + 1,
                        paged_attn="pallas", state_rows=SLOTS)


def admit(m, tree, cache, rows, seqs, bucket, base=None):
    """One admission program as the engine calls it: ``seqs`` padded to
    ``bucket``, row i of the batch living in slab row ``rows[i]``."""
    n = len(seqs)
    ids = np.zeros((n, bucket), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    tbl = np.zeros((n, TABLE), np.int32)
    for i, r in enumerate(rows):
        tbl[i] = 1 + r * TABLE + np.arange(TABLE)
    base = np.zeros((n,), np.int32) if base is None else np.asarray(base)
    with jax.default_matmul_precision("highest"):
        logits, upd = jax.jit(lambda *a: m.apply(
            {**tree, "cache": a[0]}, a[1], decode=True, positions=a[2],
            pages=a[3], seq_lens=a[4], rows=a[5], mutable=["cache"]))(
            cache, jnp.asarray(ids), jnp.asarray(base), jnp.asarray(tbl),
            jnp.asarray([len(s) for s in seqs], jnp.int32),
            jnp.asarray(rows, jnp.int32))
    return logits, upd["cache"]


def states(cache, row):
    """A slab row's state and tail (the tail's taps lead its rows)."""
    return [np.asarray(l[:, row] if path[-1].key == "conv_tail" else l[row])
            for path, l in jax.tree_util.tree_leaves_with_path(cache)
            if getattr(path[-1], "key", "") in ("gdn_state", "conv_tail")]


def test_the_cache_tree_has_two_arenas_and_six_states(model):
    _, _, module, tree = model
    cache = init_paged_cache(paged(module), tree, SLOTS, TABLE)
    names = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        names.setdefault(path[-1].key, []).append(leaf.shape)
    assert names["kv_rows"] == [(SLOTS * TABLE + 1, PT, 256)] * 2
    assert names["gdn_state"] == [(SLOTS, 3, DK, 2 * DV)] * 6
    assert names["conv_tail"] == [(3, SLOTS, H * (2 * DK + DV))] * 6
    spec = cache_spec(module)
    assert spec.state_bytes(SLOTS) == sum(
        l.nbytes for path, l in jax.tree_util.tree_leaves_with_path(cache)
        if path[-1].key in ("gdn_state", "conv_tail"))


def _decode(m, tree, cache, rows, full, steps, spoil=None):
    """``steps`` decode steps over the whole slab, the rows in ``rows``
    live: the widest gap of a row's logits to the reference's."""
    tbl = np.zeros((SLOTS, TABLE), np.int32)
    for r in rows:
        tbl[r] = 1 + r * TABLE + np.arange(TABLE)
    step_fn = jax.jit(lambda c, tok, pos, tbl, live: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=live, mutable=["cache"]))
    worst = 0.0
    for step in range(steps):
        tok = np.zeros((SLOTS,), np.int32)
        pos = np.zeros((SLOTS,), np.int32)
        live = np.zeros((SLOTS,), np.int32)
        for r, f in zip(rows, full):
            tok[r], pos[r], live[r] = (1 + (7 * step + r) % (VOCAB - 1),
                                       len(f), 1)
            f.append(int(tok[r]))
        if spoil is not None:
            cache = spoil(cache)
        with jax.default_matmul_precision("highest"):
            logits, upd = step_fn(cache, jnp.asarray(tok), jnp.asarray(pos),
                                  jnp.asarray(tbl), jnp.asarray(live))
        cache = upd["cache"]
        yield cache, [logits[r, 0] for r in rows]


def test_prefill_then_decode_logits_match_reference(model):
    """Rows of different lengths in one padded admit, then decode steps
    over the whole slab with one row dead: every logit against the
    reference's full forward. And the same steps over a state rounded to
    bfloat16 between them miss the tolerance."""
    cfg, weights, module, tree = model
    m = paged(module)
    cache = init_paged_cache(m, tree, SLOTS, TABLE)
    seqs = [p[:n] for p, n in zip(prompts(3, 40, 40, seed=5), (5, 17, 30))]
    rows = [2, 0, 3]
    logits, cache = admit(m, tree, cache, rows, seqs, 32)
    for i, s in enumerate(seqs):
        want = ref_logits(cfg, weights, s, np.arange(len(s)))
        assert float(jnp.abs(logits[i, :len(s)] - want).max()) < TOL
    before = states(cache, 1)            # slab row 1 was never admitted
    full = [list(s) for s in seqs]
    for cache1, got in _decode(m, tree, cache, rows, full, 6):
        for f, lg in zip(full, got):
            want = ref_logits(cfg, weights, f, [len(f) - 1])
            assert float(jnp.abs(lg - want[0]).max()) < TOL
    for a, b in zip(before, states(cache1, 1)):
        assert (a == b).all()            # a dead row's state and tails
    # a state held in bfloat16 is another program: the tolerance sees it
    narrow = lambda c: jax.tree_util.tree_map_with_path(
        lambda path, l: (l.astype(jnp.bfloat16).astype(l.dtype)
                         if path[-1].key == "gdn_state" else l), c)
    full = [list(s) for s in seqs]
    worst = 0.0
    for _, got in _decode(m, tree, cache, rows, full, 6, spoil=narrow):
        for f, lg in zip(full, got):
            want = ref_logits(cfg, weights, f, [len(f) - 1])
            worst = max(worst, float(jnp.abs(lg - want[0]).max()))
    assert worst > 4 * TOL


def test_state_ignores_pad_bucket_and_program_row(model):
    """A bucket's padding cannot be seen in the state: the same prompt
    admitted under 16 and under 32 positions, alone and beside another
    row, into another slab row, leaves the same state and tails."""
    _, _, module, tree = model
    m = paged(module)
    prompt = prompts(1, 13, 13, seed=6)[0]
    other = prompts(1, 29, 29, seed=7)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    _, c16 = admit(m, tree, empty, [0], [prompt], 16)
    _, c32 = admit(m, tree, empty, [3, 1], [prompt, other], 32)
    for a, b in zip(states(c16, 0), states(c32, 3)):
        # float32 sums in another order, eight layers deep, on states
        # about 10 wide
        assert float(np.abs(a - b).max()) < 1e-4 * np.abs(a).max()
    assert any(np.abs(a).max() > 0 for a in states(c16, 0))


def test_chunked_prefill_state_equals_monolithic(model):
    _, _, module, tree = model
    m = paged(module)
    prompt = prompts(1, 27, 27, seed=8)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    lg_mono, mono = admit(m, tree, empty, [1], [prompt], 32)
    _, part = admit(m, tree, empty, [1], [prompt[:16]], 16)
    lg_rest, both = admit(m, tree, part, [1], [prompt[16:]], 16, base=[16])
    for a, b in zip(states(mono, 1), states(both, 1)):
        # float32 sums in another order, eight layers deep, on states
        # about 10 wide
        assert float(np.abs(a - b).max()) < 1e-4 * np.abs(a).max()
    assert float(jnp.abs(lg_mono[0, 26] - lg_rest[0, 10]).max()) < TOL


# --- the paged path: the engine ---------------------------------------------


def engine(model, **kw):
    _, _, module, tree = model
    args = dict(slots=SLOTS, page_tokens=PT, chunk_steps=1, bucket_min=16,
                paged_attn="pallas", prefix_cache=False,
                prefill_chunk_tokens=0)
    args.update(kw)
    return PagedBatchingDecoder(module, tree, **args)


def serve(dec, ps, n_new):
    entries = [dec.submit(GenerateRequest(prompts=[p.tolist()],
                                          max_new_tokens=n_new))
               for p in ps]
    return [dec.wait(e, timeout=300)["tokens"][0] for e in entries]


def served_gap(cfg, weights, prompt, toks, precision="float32"):
    """check.py's reading: how far a served token's reference logit lies
    under the reference's best, worst over the answer."""
    ids = list(prompt) + list(toks)
    at = np.arange(len(prompt) - 1, len(ids) - 1)
    lg = np.asarray(ref_logits(cfg, weights, ids[:-1] + [0], at, precision))
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


def test_engine_serves_the_reference_tokens(model):
    """More requests than rows, lengths all different, answers of different
    lengths: rows at different depths in one step, admits into a running
    batch, rows reused after their requests end. Every served token is the
    reference's first choice (to ``TOL`` of a logit), and is NOT the first
    choice of a reference without the delta term."""
    cfg, weights, module, tree = model
    ps = prompts(7, 3, 30, seed=9)
    news = [9, 4, 12, 9, 6, 9, 3]
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            entries = [dec.submit(GenerateRequest(
                prompts=[p.tolist()], max_new_tokens=n))
                for p, n in zip(ps, news)]
            out = [dec.wait(e, timeout=300)["tokens"][0] for e in entries]
            tel = dec.telemetry()
        finally:
            dec.close()
        one = generate(module, tree, ps[0][None], max_new_tokens=9)
    for p, toks, n in zip(ps, out, news):
        assert len(toks) == n
        assert served_gap(cfg, weights, p, toks) < TOL
    assert max(served_gap(cfg, weights, p, toks, "delta_off")
               for p, toks in zip(ps, out)) > 20 * TOL
    assert list(np.asarray(one.tokens)[0]) == out[0]   # the one-shot path
    # six layers keep a state: 6 x 8 x 16 float32 and 3 x 6 x 32 of tail
    row = 4 * (H * DK * DV + 3 * H * (2 * DK + DV))
    assert tel["recurrent_layers"] == 6.0
    assert tel["recurrent_state_bytes"] == SLOTS * 6 * row
    assert (tel["cache_sublayers"], tel["full_layers"]) == (2.0, 2.0)
    # the kernel moves every slab row (in each of the six layers), a step
    assert tel["state_rows_moved"] == tel["chunks"] * SLOTS
    assert 0 < tel["state_rows_live"] < tel["state_rows_moved"]
    assert tel["prefix_cache_off_recurrent"] == 0.0


def test_engine_spans_count_the_rows_whose_state_a_program_writes(model):
    from kubeml_tpu.utils import tracing

    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enabled = True
    try:
        dec = engine(model)
        try:
            serve(dec, prompts(2, 10, 20, seed=12), 4)
        finally:
            dec.close()
        spans = [s for s in tracer.spans() if s.name == "engine.dispatch"]
    finally:
        tracer.enabled = was_on
        tracer.clear()
    by = lambda p: [s.attrs["state_rows"] for s in spans
                    if s.attrs["program"] == p]
    assert by("admit") == [1, 1]
    assert by("step") and set(by("step")) <= {1, 2}


def test_reused_slot_starts_from_zero_state(model):
    """One row: the second request runs in the slab row the first left,
    and is served what it is served alone."""
    cfg, weights, _, _ = model
    ps = prompts(2, 12, 25, seed=13)
    with jax.default_matmul_precision("highest"):
        dec = engine(model, slots=1)
        try:
            out = serve(dec, ps, 6)
        finally:
            dec.close()
    for p, toks in zip(ps, out):
        assert served_gap(cfg, weights, p, toks) < TOL


@pytest.mark.parametrize("feature,kw", [
    ("spec_self", dict(spec="self")), ("snapshot", {}), ("slot_engine", {})])
def test_the_recurrent_refusals_fire_for_this_model(model, feature, kw):
    """What is refused for a Mamba-2 mixer's state is refused for this
    one's, by the same row of the table; prefix sharing is switched off."""
    from kubeml_tpu.serving import kvsnap

    _, _, module, tree = model
    with pytest.raises(CacheFeatureUnsupported) as refused:
        if feature == "slot_engine":
            dec = BatchingDecoder(module, tree, slots=2)
        else:
            dec = PagedBatchingDecoder(module, tree, slots=2, page_tokens=PT,
                                       prefix_cache=False, **kw)
        try:
            dec.submit_snapshot(kvsnap.RequestSnapshot(
                model=dec.name, request_id="r", page_tokens=PT,
                kv_quant="none", spec="off", prompt=[1, 2, 3], out=[4],
                max_new=5, temp=0.0, topk=0, eos=-1, key=(0, 0), layers=[]))
        finally:
            dec.close()
    assert (refused.value.property, refused.value.feature) == (
        "recurrent", feature)
    assert refused.value.status_code == 409


def test_prefix_sharing_is_switched_off_for_this_model(model):
    _, _, module, tree = model
    dec = PagedBatchingDecoder(module, tree, slots=2, page_tokens=PT,
                               prefix_cache=True)
    try:
        assert dec._pool.trie is None
        assert dec.telemetry()["prefix_cache_off_recurrent"] == 1.0
    finally:
        dec.close()


def test_block_traces_grow_by_two_a_program():
    """Two kinds of layer (a linear mixer, a full attention; the MLP is one
    kind): sizing the cache, an admission program and a step program cost
    two traces each, whatever the depth."""
    before = gpt.block_traces()
    dec = engine(build(tiny_cfg(), seed=4), slots=3)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["compiled_programs"] == 2.0
    assert gpt.block_traces() - before == 3 * 2
