"""Falcon-H1 through the normal path (ISSUE 27): a Mamba-2 mixer beside
grouped-query attention in every block, recurrent state beside the pages.

Everything here runs a tiny preset (hidden 64, two layers) in float32 on the
CPU, built by the benchmark's own builder and held against the benchmark's
plain reference (``benchmark/reference/falcon_h1.py``), with every muP
multiplier at its published value (none of them is 1 but
``attention_in_multiplier``, which is set off 1 here)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.models import falcon_h1 as builder  # noqa: E402
from benchmark.reference import falcon_h1 as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.generation import generate, init_paged_cache  # noqa: E402
from kubeml_tpu.ops import ssm  # noqa: E402
from kubeml_tpu.ops.paged_attention import (pack_kv_rows,  # noqa: E402
                                            paged_attention)
from kubeml_tpu.serving.batcher import PagedBatchingDecoder  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
VOCAB = 97
# float32 against float32 at precision "highest": what is left is the order
# of summation (chunked scan against a scan over positions, fused in_proj
# against five products). Logits are about 1 wide; 2e-4 is a hundred times
# the largest gap seen (2e-6) and a thousandth of a bfloat16 rounding.
TOL = 2e-4


def tiny_cfg():
    cfg = json.loads(
        (ROOT / "benchmark/configs/falcon-h1-34b.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, num_hidden_layers=2, n_layer=2, n_head=4,
               intermediate_size=96, mamba_n_heads=4, mamba_d_head=8,
               mamba_d_ssm=32, mamba_n_groups=2, mamba_d_state=16,
               mamba_chunk_size=8, vocab_size=VOCAB, n_positions=64,
               attention_in_multiplier=0.5, compute_dtype="float32",
               param_dtype="float32")
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    weights = builder.init_weights(cfg, 3)
    tree = {}
    for path, arr in builder.program_leaves(cfg, weights):
        node = tree
        *parents, leaf = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[leaf] = jnp.asarray(arr)
    ns = {}
    exec(builder.function_source(cfg), ns)
    return cfg, weights, ns["Model"]().build(), tree


def ref_logits(cfg, weights, ids, at):
    """One compiled shape: the sequence right-padded to the preset's 64
    positions (everything is causal), the positions asked for padded too."""
    T = cfg["n_positions"]
    padded = np.zeros((T,), np.int32)
    padded[:len(ids)] = ids
    where = np.zeros((T,), np.int32)
    where[:len(at)] = at
    return reference.logits_at(
        weights, jnp.asarray(padded), jnp.asarray(where),
        n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"],
        precision="float32")[:len(at)]


def prompts(n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


# --- the model against the reference -------------------------------------


def test_whole_model_matches_reference(model):
    cfg, weights, module, tree = model
    assert cache_spec(module).recurrent
    ids = prompts(1, 37, 37)[0]
    with jax.default_matmul_precision("highest"):
        got = module.apply(tree, ids[None])[0]
    want = ref_logits(cfg, weights, ids, np.arange(len(ids)))
    assert float(jnp.sqrt((want ** 2).mean())) > 0.3   # not all rounding
    assert float(jnp.abs(got - want).max()) < TOL


def test_one_block_matches_reference(model):
    """A single block (attention + mixer + MLP): the one-layer model built
    from the first layer's weights."""
    cfg, weights, module, tree = model
    one = dict(cfg, num_hidden_layers=1, n_layer=1)
    w1 = {k: (v[:1] if k in reference.LAYER_NAMES else v)
          for k, v in weights.items()}
    t1 = {"params": {k: v for k, v in tree["params"].items()
                     if k != "block_1"}}
    ns = {}
    exec(builder.function_source(one), ns)
    ids = prompts(1, 21, 21, seed=4)[0]
    with jax.default_matmul_precision("highest"):
        got = ns["Model"]().build().apply(t1, ids[None])[0]
    want = ref_logits(one, w1, ids, np.arange(len(ids)))
    assert float(jnp.abs(got - want).max()) < TOL


def test_lower_precision_control_departs(model):
    """The control the limits are set against has to move the logits far
    more than the tolerance above."""
    cfg, weights, _, _ = model
    ids = prompts(1, 30, 30, seed=2)[0]
    at = np.arange(len(ids))
    want = ref_logits(cfg, weights, ids, at)
    low = reference.logits_at(weights, jnp.asarray(ids), jnp.asarray(at),
                              n_head=cfg["n_head"],
                              eps=cfg["layer_norm_epsilon"],
                              precision="fp8_e4m3")
    assert float(jnp.abs(low - want).max()) > 100 * TOL


# --- the recurrence -------------------------------------------------------


def _ssd_inputs(L, b=2, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, L, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, L, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, L, G, N)),
            jax.random.normal(k[4], (b, L, G, N)),
            jax.random.normal(k[5], (b, H, N, P)))


@pytest.mark.parametrize("L,chunk", [(37, 8), (37, 16), (5, 8), (64, 8),
                                     (129, 128)])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_matches_sequential(L, chunk, carried):
    x, dt, A, B, C, S0 = _ssd_inputs(L)
    init = S0 if carried else None
    y0, s0 = ssm.ssd_sequential(x, dt, A, B, C, init)
    y1, s1 = ssm.ssd_scan(x, dt, A, B, C, chunk=chunk, init_state=init)
    # relative to the largest value: a long chunk sums 128 terms
    assert float(jnp.abs(y0 - y1).max()) < 1e-5 * float(jnp.abs(y0).max() + 10)
    assert float(jnp.abs(s0 - s1).max()) < 1e-5 * float(jnp.abs(s0).max() + 10)


def test_masked_positions_leave_the_state_alone():
    x, dt, A, B, C, S0 = _ssd_inputs(24)
    keep = (jnp.arange(24) < 13).astype(jnp.float32)
    _, s_masked = ssm.ssd_scan(x * keep[None, :, None, None],
                               dt * keep[None, :, None], A, B, C, chunk=8,
                               init_state=S0)
    _, s_short = ssm.ssd_scan(x[:, :13], dt[:, :13], A, B[:, :13], C[:, :13],
                              chunk=8, init_state=S0)
    assert float(jnp.abs(s_masked - s_short).max()) < 1e-5


@pytest.mark.parametrize("H,G,P,N", [(4, 2, 8, 16), (8, 1, 16, 8),
                                     (32, 2, 128, 256)])
def test_ssm_update_kernel_matches_jnp(H, G, P, N):
    S = 3
    k = jax.random.split(jax.random.PRNGKey(H), 6)
    state = jax.random.normal(k[0], (S, H, N, P))
    x = jax.random.normal(k[1], (S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (S, H)))
    dt = dt.at[1].set(0.0)                      # a row that is not live
    x = x.at[1].set(0.0)
    A = -jnp.exp(jax.random.normal(k[3], (H,)))
    B = jax.random.normal(k[4], (S, G, N))
    C = jax.random.normal(k[5], (S, G, N))
    y0, s0 = ssm.ssm_update_reference(state, x, dt, A, B, C)
    y1, s1 = ssm.ssm_update(state, x, dt, A, B, C, interpret=True)
    assert float(jnp.abs(y0 - y1).max()) < 1e-3 * float(jnp.abs(y0).max())
    assert float(jnp.abs(s0 - s1).max()) < 1e-5
    assert bool((s1[1] == state[1]).all())      # exactly, not nearly


# --- grouped-query attention through the page-walk kernel ----------------


@pytest.mark.parametrize("H,Hkv,D,L", [(4, 4, 16, 1), (4, 2, 16, 1),
                                       (20, 4, 128, 1), (4, 1, 32, 9)])
def test_gqa_page_walk_kernel(H, Hkv, D, L):
    """The kernel over rows of Hkv heads against (a) the gather oracle and
    (b) the same kernel over rows with each K/V head repeated for its query
    heads: bit-identical in the tile body, since head h does the same
    arithmetic on the same numbers. A decode step (L 1) contracts a head
    over the whole of a row's K lanes, zeros at the other heads', and the
    repeated row is wider: the same products summed in another order, equal
    to a few units in the last place. With Hkv == H the kernel is the one it
    was."""
    B, pt, P, N = 3, 8, 4, 13
    k = jax.random.split(jax.random.PRNGKey(H * 7 + Hkv), 4)
    q = jax.random.normal(k[0], (B, L, H, D))
    ka = jax.random.normal(k[1], (N, pt, Hkv, D))
    va = jax.random.normal(k[2], (N, pt, Hkv, D))
    pages = jax.random.permutation(k[3], jnp.arange(1, N))[:B * P].reshape(
        B, P).astype(jnp.int32)
    pos = jnp.asarray([0, 11, 22], jnp.int32)
    out = paged_attention(q, pack_kv_rows(ka, va), pages, pos,
                          kv_heads=Hkv, interpret=True)
    share = H // Hkv
    wide = paged_attention(q, pack_kv_rows(jnp.repeat(ka, share, axis=2),
                                           jnp.repeat(va, share, axis=2)),
                           pages, pos, interpret=True)
    if L == 1:
        np.testing.assert_allclose(np.asarray(out), np.asarray(wide),
                                   atol=1e-5, rtol=1e-5)
    else:
        assert bool((out == wide).all())
    # gather oracle
    kg = jnp.repeat(ka[pages].reshape(B, P * pt, Hkv, D), share, axis=2)
    vg = jnp.repeat(va[pages].reshape(B, P * pt, Hkv, D), share, axis=2)
    qp = pos[:, None] + jnp.arange(L)
    mask = jnp.arange(P * pt)[None, None, :] <= qp[:, :, None]
    s = jnp.einsum("blhd,bshd->bhls", q, kg) / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhls,bshd->blhd", p, vg)
    assert float(jnp.abs(out - want).max()) < 1e-4


def test_kv_bytes_count_kv_heads(model):
    _, _, module, _ = model
    # 2 layers x (K and V) x 2 K/V heads x 32 x 4 bytes
    spec = cache_spec(module)
    assert spec.token_bytes() == 2 * 2 * 2 * 32 * 4
    assert spec.page_bytes(8) == 8 * spec.token_bytes()
    from kubeml_tpu.models.gpt import CausalTransformer
    gpt = CausalTransformer(vocab_size=11, max_len=16, embed_dim=64,
                            depth=3, num_heads=4)
    assert cache_spec(gpt).token_bytes() == 3 * 2 * 64 * 4   # as before GQA


# --- the paged path: module level ----------------------------------------


PT, SLOTS, TABLE = 8, 4, 8


def paged(module):
    return module.clone(page_tokens=PT, kv_pages=SLOTS * TABLE + 1,
                        paged_attn="pallas", state_rows=SLOTS)


def admit(m, tree, cache, rows, seqs, bucket, base=None):
    """One admission program as the engine calls it: ``seqs`` padded to
    ``bucket``, row i of the batch living in slab row ``rows[i]``. (The
    engine hands it one row a program; the model takes any number.)"""
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
    return [np.asarray(l[row]) for path, l in
            jax.tree_util.tree_leaves_with_path(cache)
            if getattr(path[-1], "key", "") in ("ssm_state", "conv_tail")]


def test_prefill_then_decode_logits_match_reference(model):
    """Rows of different lengths in one padded admit, then decode steps
    over the whole slab with one row dead: every logit against the
    reference's full forward."""
    cfg, weights, module, tree = model
    m = paged(module)
    cache = init_paged_cache(m, tree, SLOTS, TABLE)
    seqs = [p[:n] for p, n in zip(prompts(3, 40, 40, seed=5), (5, 17, 30))]
    rows = [2, 0, 3]
    logits, cache = admit(m, tree, cache, rows, seqs, 32)
    full = [list(s) for s in seqs]
    for i, s in enumerate(seqs):
        want = ref_logits(cfg, weights, s, np.arange(len(s)))
        assert float(jnp.abs(logits[i, :len(s)] - want).max()) < TOL
    tbl = np.zeros((SLOTS, TABLE), np.int32)
    for r in rows:
        tbl[r] = 1 + r * TABLE + np.arange(TABLE)
    before = states(cache, 1)            # slab row 1 was never admitted
    step_fn = jax.jit(lambda c, tok, pos, tbl, live: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=live, mutable=["cache"]))
    for step in range(6):
        tok = np.zeros((SLOTS,), np.int32)
        pos = np.zeros((SLOTS,), np.int32)
        live = np.zeros((SLOTS,), np.int32)
        for r, f in zip(rows, full):
            tok[r], pos[r], live[r] = 1 + (7 * step + r) % (VOCAB - 1), len(f), 1
            f.append(int(tok[r]))
        with jax.default_matmul_precision("highest"):
            logits, upd = step_fn(cache, jnp.asarray(tok), jnp.asarray(pos),
                                  jnp.asarray(tbl), jnp.asarray(live))
        cache = upd["cache"]
        for r, f in zip(rows, full):
            want = ref_logits(cfg, weights, f, [len(f) - 1])
            assert float(jnp.abs(logits[r, 0] - want[0]).max()) < TOL
    for a, b in zip(before, states(cache, 1)):
        assert (a == b).all()            # a dead row's state is untouched


def test_state_ignores_pad_bucket_and_program_row(model):
    _, _, module, tree = model
    m = paged(module)
    prompt = prompts(1, 13, 13, seed=6)[0]
    other = prompts(1, 29, 29, seed=7)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    _, c16 = admit(m, tree, empty, [0], [prompt], 16)
    _, c32 = admit(m, tree, empty, [3, 1], [prompt, other], 32)
    for a, b in zip(states(c16, 0), states(c32, 3)):
        assert float(np.abs(a.astype(np.float32)
                            - b.astype(np.float32)).max()) < 1e-5


def test_chunked_prefill_state_equals_monolithic(model):
    _, _, module, tree = model
    m = paged(module)
    prompt = prompts(1, 27, 27, seed=8)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    lg_mono, mono = admit(m, tree, empty, [1], [prompt], 32)
    _, part = admit(m, tree, empty, [1], [prompt[:16]], 16)
    lg_rest, both = admit(m, tree, part, [1], [prompt[16:]], 16, base=[16])
    for a, b in zip(states(mono, 1), states(both, 1)):
        assert float(np.abs(a.astype(np.float32)
                            - b.astype(np.float32)).max()) < 1e-5
    assert float(jnp.abs(lg_mono[0, 26] - lg_rest[0, 10]).max()) < TOL


# --- the paged path: the engine ------------------------------------------


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


def served_gap(cfg, weights, prompt, toks):
    """check.py's reading: how far a served token's reference logit lies
    under the reference's best, worst over the answer."""
    ids = list(prompt) + list(toks)
    at = np.arange(len(prompt) - 1, len(ids) - 1)
    lg = np.asarray(ref_logits(cfg, weights, ids[:-1] + [0], at))
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


def test_engine_serves_the_reference_tokens(model):
    """More requests than rows, lengths all different: every served token
    is the reference's first choice (to 1e-4 of a logit), through one-row
    admits, slot reuse and decode steps beside rows that ended."""
    cfg, weights, module, tree = model
    ps = prompts(7, 3, 30, seed=9)
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            out = serve(dec, ps, 9)
            tel = dec.telemetry()
        finally:
            dec.close()
        one = generate(module, tree, ps[0][None], max_new_tokens=9)
    for p, toks in zip(ps, out):
        assert len(toks) == 9
        assert served_gap(cfg, weights, p, toks) < TOL
    assert list(np.asarray(one.tokens)[0]) == out[0]   # the one-shot path
    assert tel["recurrent_layers"] == 2.0
    # per row and layer: 4 x 16 x 8 float32 state, 3 x (32 + 2*2*16) tail
    assert tel["recurrent_state_bytes"] == SLOTS * 2 * (
        4 * 16 * 8 * 4 + 3 * 96 * 4)
    assert tel["prefix_cache_off_recurrent"] == 0.0
    assert tel["param_bytes"] == sum(
        l.size * 4 for l in jax.tree.leaves(tree))


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
        spans = tracer.spans()
    finally:
        tracer.enabled = was_on
        tracer.clear()
    admits = [s for s in spans if s.name == "engine.admit"]
    assert admits and sum(s.attrs["state_rows"] for s in admits) == 2
    by_kind = {}
    for s in spans:
        if s.name == "engine.dispatch":
            by_kind.setdefault(s.attrs["program"], []).append(
                s.attrs["state_rows"])
    assert sum(by_kind["admit"]) == 2
    assert by_kind["step"] and all(1 <= n <= 2 for n in by_kind["step"])


def test_engine_chunked_prefill_equals_monolithic(model):
    ps = prompts(3, 36, 56, seed=10)
    with jax.default_matmul_precision("highest"):
        outs = []
        for chunk in (0, 16):
            dec = engine(model, prefill_chunk_tokens=chunk)
            try:
                outs.append(serve(dec, ps, 6))
                chunks = dec.telemetry().get("prefill_chunks", 0)
            finally:
                dec.close()
    assert outs[0] == outs[1]
    assert chunks > 0


def test_reused_slot_starts_from_zero_state(model):
    """One program row: the second request runs where the first one's state
    was, and answers as it does on a fresh engine."""
    a, b = prompts(2, 20, 28, seed=11)
    with jax.default_matmul_precision("highest"):
        dec = engine(model, slots=1)
        try:
            serve(dec, [a], 8)
            after = serve(dec, [b], 8)
        finally:
            dec.close()
        dec = engine(model, slots=1)
        try:
            fresh = serve(dec, [b], 8)
        finally:
            dec.close()
    assert after == fresh
