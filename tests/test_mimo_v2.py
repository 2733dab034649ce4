"""MiMo-V2-Flash's stack through the normal path (ISSUE 44): full and window
attention layers mixed by ``hybrid_layer_pattern`` at their own K/V head
counts and rotary bases, K heads wider than V heads, rotary on a share of a
head, a learned sink in the window layers' softmax, a value scale, one dense
SwiGLU layer and then routed experts of which this chip holds a share
(``models/gpt.py AttnKind``), over TWO kinds of paged cache: a page for every
position in the full layers, a ring of pages a row in the window layers
(``serving/kvpool.py``).

Everything here runs a tiny preset with the published structure (hidden 128;
7 layers: full + dense, then window x 4, full, window over experts; 8 heads
of 24 with V heads of 16 on 2 / 4 K/V heads; rotary on 8 of 24 lanes; window
8; pages of 4, so a ring of 4 pages; sigmoid router over 32 outputs, 4 a
token, 4 experts held) in float32 on the CPU, built by the benchmark's own
builder and held against the benchmark's plain reference
(``benchmark/reference/mimo_v2.py``)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.models import mimo_v2 as builder  # noqa: E402
from benchmark.reference import mimo_v2 as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models import experts as experts_mod  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models.experts import ExpertMLP, ExpertsConfig  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.generation import (init_paged_cache,  # noqa: E402
                                          supports_paged_decode)
from kubeml_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from kubeml_tpu.ops.paged_attention import ring_pages  # noqa: E402
from kubeml_tpu.serving.batcher import PagedBatchingDecoder  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# float32 against float32 at precision "highest": what is left is the order
# of summation (an online softmax over chunks of pages against one softmax
# over a row of scores, the sink joined at the end against a column of its
# own, sorted grouped products against a masked sum). Logits are about 1
# wide; 1e-4 is a hundredth of a bfloat16 rounding, and the same forward
# pass in bfloat16 misses it by three orders (below).
TOL = 1e-4
VOCAB, PT, SLOTS, TABLE = 211, 4, 4, 16
WINDOW, RING = 8, 4


def tiny_cfg(**over):
    cfg = json.loads((ROOT / "benchmark/tests/data_mimo/configs/"
                      "tiny-mimo.json").read_text())
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
    T = cfg["n_positions"]
    padded = np.zeros((T,), np.int32)
    padded[:len(ids)] = ids
    where = np.zeros((T,), np.int32)
    where[:len(at)] = at
    return reference.logits_at(
        weights, jnp.asarray(padded), jnp.asarray(where),
        n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"],
        precision=precision)[:len(at)]


def prompts(n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def force_kernels(monkeypatch):
    """Put the experts' Pallas kernel (interpret mode) where a TPU would
    run it."""
    monkeypatch.setattr(
        experts_mod, "grouped_matmul",
        lambda *a, kernel, **kw: grouped_matmul(*a, kernel=True, **kw))


# --- (a) the whole-sequence forward against the reference -----------------


def test_whole_model_matches_reference(model):
    cfg, weights, module, tree = model
    spec = cache_spec(module)
    assert [l.window for l in spec.layers] == [0, 8, 8, 8, 8, 0, 8]
    assert [(l.kv_heads, l.k_dim, l.v_dim) for l in spec.layers[:2]] == [
        (2, 24, 16), (4, 24, 16)]
    assert spec.window_layers == 5 and spec.sublayers == 7
    assert spec.expert_layers == 6 and supports_paged_decode(module)
    # no page size cloned in yet
    assert spec.ring_pages(module.page_tokens) == 0
    assert spec.ring_pages(PT) == RING == ring_pages(WINDOW, PT)
    assert ring_pages(128, 16) == 10           # the published window
    assert set(tree["params"]["block_1"]["attn"]) == {
        "query", "key", "value", "proj", "sink"}
    assert "sink" not in tree["params"]["block_5"]["attn"]
    ids = prompts(1, 41, 41)[0]
    with jax.default_matmul_precision("highest"):
        got = module.apply(tree, ids[None])
    want = ref_logits(cfg, weights, ids, np.arange(len(ids)))
    assert float(jnp.sqrt((want ** 2).mean())) > 0.3   # not all rounding
    assert float(jnp.abs(got[0] - want).max()) < TOL
    # a sink near the log of the window takes a visible share of its mass
    assert 1.0 < float(weights["s_sink"].mean()) < 3.5


def test_bfloat16_where_float32_is_stated_fails(model):
    """The tolerance is tight enough to tell the stated precision from the
    one below it."""
    cfg, weights, module, tree = model
    ids = prompts(1, 41, 41)[0]
    got = module.clone(dtype=jnp.bfloat16).apply(tree, ids[None])
    want = ref_logits(cfg, weights, ids, np.arange(len(ids)))
    assert float(jnp.abs(got[0] - want).max()) > 50 * TOL


@pytest.mark.parametrize("left_out", ["window_off", "sink_off", "held_zero"])
def test_left_out_controls_fail(model, left_out):
    """The reference with a mechanism left out (window layers that attend
    to everything, no sink, no held experts) lies far from the program: at
    least five times the tolerance, so a program that lost the mechanism
    would fail the parity tests above; and the served-token gap that the
    benchmark's check reads sees it too."""
    cfg, weights, module, tree = model
    ids = prompts(1, 41, 41)[0]
    with jax.default_matmul_precision("highest"):
        got = module.apply(tree, ids[None])[0]
    at = np.arange(len(ids))
    want = ref_logits(cfg, weights, ids, at, precision=left_out)
    sound = ref_logits(cfg, weights, ids, at)
    off = float(jnp.abs(got - want).max())
    assert off > 5 * TOL and off > 0.05
    # within the window's reach both references agree: the first positions
    # see every key either way
    if left_out == "window_off":
        assert float(jnp.abs(want[:WINDOW] - sound[:WINDOW]).max()) < TOL
    first = jnp.argmax(got, axis=-1)
    gap = want.max(-1) - jnp.take_along_axis(want, first[:, None], 1)[:, 0]
    assert float(gap.max()) > 50 * TOL


# --- (b) prefill, then decode, through both caches --------------------------


def paged(module, impl):
    return module.clone(page_tokens=PT, kv_pages=SLOTS * TABLE + 1,
                        window_pages=SLOTS * RING + 1, paged_attn=impl)


def tables(rows, n=None):
    """Row r's full-layer pages and its ring (rows not named: the trash
    page everywhere)."""
    n = len(rows) if n is None else n
    full = np.zeros((n, TABLE), np.int32)
    ring = np.zeros((n, RING), np.int32)
    for i, r in enumerate(rows):
        at = i if n == len(rows) else r
        full[at] = 1 + r * TABLE + np.arange(TABLE)
        ring[at] = 1 + r * RING + np.arange(RING)
    return jnp.asarray(full), jnp.asarray(ring)


@pytest.mark.parametrize("impl", ["pallas", "gather"])
def test_prefill_then_decode_through_both_caches(model, impl, monkeypatch):
    """Rows of different lengths in one padded admit (a window layer
    attends over the bucket's own keys and keeps the tail in its ring), then
    decode steps over the whole slab with a row dead: every logit against
    the reference's full forward, PAST THE WINDOW (8), ACROSS PAGE EDGES
    (4) and AROUND THE RING (16 positions) more than twice for the longest
    row. ``pallas`` puts every kernel of the path (interpret mode) where a
    TPU would run it; ``gather`` is the oracle of each."""
    cfg, weights, module, tree = model
    if impl == "pallas":
        force_kernels(monkeypatch)
    m = paged(module, impl)
    cache = init_paged_cache(m, tree, SLOTS, TABLE)
    assert cache["block_0"]["attn"]["kv_rows"].shape[0] == SLOTS * TABLE + 1
    assert cache["block_1"]["attn"]["kv_rows"].shape[0] == SLOTS * RING + 1
    seqs = [p[:n] for p, n in zip(prompts(3, 30, 30, seed=5), (3, 13, 26))]
    rows = [2, 0, 3]
    ids = np.zeros((3, 32), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        logits, upd = m.apply(
            {**tree, "cache": cache}, jnp.asarray(ids), decode=True,
            positions=jnp.zeros((3,), jnp.int32), pages=tables(rows),
            seq_lens=jnp.asarray([len(s) for s in seqs], jnp.int32),
            mutable=["cache"])
    cache = upd["cache"]
    full = [list(s) for s in seqs]
    for i, s in enumerate(seqs):
        want = ref_logits(cfg, weights, s, np.arange(len(s)))
        assert float(jnp.abs(logits[i, :len(s)] - want).max()) < TOL
    step_fn = jax.jit(lambda c, tok, pos, tbl, live: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=live, mutable=["cache"]))
    tbl = tables(rows, SLOTS)
    for step in range(36):
        tok = np.zeros((SLOTS,), np.int32)
        pos = np.zeros((SLOTS,), np.int32)
        live = np.zeros((SLOTS,), np.int32)
        for r, f in zip(rows, full):
            if len(f) >= cfg["n_positions"] - 2:
                continue                       # the longest row has ended
            tok[r], pos[r], live[r] = 1 + (7 * step + r) % (VOCAB - 1), len(f), 1
            f.append(int(tok[r]))
        with jax.default_matmul_precision("highest"):
            logits, upd = step_fn(cache, jnp.asarray(tok), jnp.asarray(pos),
                                  tbl, jnp.asarray(live))
        cache = upd["cache"]
        if step % 5 and step < 30:
            continue                           # every fifth step, and the end
        for r, f in zip(rows, full):
            if live[r]:
                want = ref_logits(cfg, weights, f, [len(f) - 1])
                assert float(jnp.abs(logits[r, 0] - want[0]).max()) < TOL
    # 3 + 36 positions of the shortest row: around its ring twice; the
    # longest stopped at the model's length
    assert [len(f) for f in full] == [39, 49, 62]
    # the trash page took every dead row's write, nothing else did
    assert float(jnp.abs(cache["block_1"]["attn"]["kv_rows"][
        1 + 1 * RING:1 + 2 * RING]).max()) == 0.0   # row 1 was never leased


# --- (c) the engine: two kinds of lease ------------------------------------


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


def test_engine_serves_the_reference_tokens_under_two_leases(model):
    """More requests than rows through the engine as it stands: every served
    token is the reference's first choice; the window layers' arenas hold a
    ring a program row whatever the model's length; both kinds of lease
    come back; and the counters tell the kinds apart."""
    cfg, weights, module, _ = model
    ps = prompts(6, 3, 40, seed=9)
    before = gpt.block_traces()
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            out = serve(dec, ps, 21)
            tel = dec.telemetry()
            pool = dec._pool.check()
            shapes = jax.tree.map(lambda a: a.shape, dec._slab.cache)
            token_bytes = (dec._kv_token_bytes, dec._window_token_bytes)
            arena = dec.arena_bytes
        finally:
            dec.close()
    for p, toks in zip(ps, out):
        assert len(toks) == 21
        assert served_gap(cfg, weights, p, toks) < TOL
    # a full layer's arena: every row at the model's length; a window
    # layer's: a ring a row, and one trash page each
    assert shapes["block_0"]["attn"]["kv_rows"] == (SLOTS * 16 + 1, PT, 128)
    assert shapes["block_2"]["attn"]["kv_rows"] == (SLOTS * RING + 1, PT, 256)
    assert arena == ((SLOTS * 16 + 1) * 2 * 128
                     + (SLOTS * RING + 1) * 5 * 256) * PT * 4
    # live bytes a cached token: 2 full layers of 2 heads, 5 window layers
    # of 4, each K 24 + V 16 float32 values
    assert token_bytes == (2 * 2 * 40 * 4, 5 * 4 * 40 * 4)
    assert pool == {"free": SLOTS * 16, "held": 0, "trie_pages": 0,
                    "refs_total": 0, "window_free": SLOTS * RING,
                    "window_held": 0}
    assert tel["window_layers"] == 5.0 and tel["full_layers"] == 2.0
    assert tel["cache_sublayers"] == 7.0 and tel["moe_layers"] == 6.0
    assert tel["window_ring_pages"] == RING
    assert tel["window_pages_total"] == tel["window_pages_free"] == SLOTS * RING
    # the totals keep their meaning, the window layers' part beside them
    for name in ("walk_chunks_live", "walk_chunks_grid", "tile_chunks_live",
                 "tile_chunks_grid"):
        assert 0 < tel[name + "_window"] < tel[name]
    steps = tel["live_slot_steps"]
    # a ring of 4 pages is one program a row and layer; of a live row's 4
    # pages a window of 8 keys lies in 2 or 3
    assert tel["walk_chunks_grid_window"] == tel["device_steps"] * SLOTS * 5
    assert tel["walk_chunks_live_window"] == steps * 5
    assert tel["window_pages_held"] == steps * 5 * RING
    assert 2 * steps * 5 <= tel["window_pages_live"] <= 3 * steps * 5
    held, absent = tel["moe_assignments"], tel["moe_assignments_absent"]
    assert held + absent == steps * 4 * 6 and held > 0 and absent > 0
    assert tel["moe_assignments_zero"] == 0
    programs = tel["compiled_programs"]
    assert 0 < gpt.block_traces() - before <= 3 * (programs + 1)


def test_block_traces_grow_by_three_a_program():
    """Three kinds of layer (full + dense, window + experts, full +
    experts): sizing the cache, an admission program and a step program
    cost three traces each, whatever the depth; a stack of one kind pays
    one each, as before."""
    before = gpt.block_traces()
    dec = engine(build(tiny_cfg(), seed=4), slots=3)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["compiled_programs"] == 2.0
    assert gpt.block_traces() - before == 3 * 3
    before = gpt.block_traces()
    plain = PagedBatchingDecoder(
        gpt.GPTTiny(vocab_size=VOCAB, max_len=64),
        gpt.GPTTiny(vocab_size=VOCAB, max_len=64).init(
            jax.random.key(0), jnp.ones((1, 4), jnp.int32)),
        slots=3, page_tokens=PT, chunk_steps=1, prefix_cache=False)
    try:
        serve(plain, prompts(1, 10, 10), 3)
    finally:
        plain.close()
    assert gpt.block_traces() - before == 3     # one a program, as before


def test_more_rows_than_rings_wait_and_every_lease_comes_back(model):
    """Admission counts both kinds: the pool under the engine never hands
    out more rings than the window arenas hold, and at drain every page of
    both kinds is free again."""
    dec = engine(model, slots=2)
    try:
        assert dec.window_ring == RING
        assert dec.window_arena_pages == 2 * RING + 1
        out = serve(dec, prompts(5, 5, 30, seed=11), 9)
        check = dec._pool.check()
    finally:
        dec.close()
    assert all(len(t) == 9 for t in out)
    assert check["window_held"] == 0 and check["window_free"] == 2 * RING
    assert check["held"] == 0


# --- (d) the share: sixteen chips of two experts each ------------------------


G, K, E, W = 32, 4, 64, 24


def layer_cfg(held=None):
    return ExpertsConfig(G, K, W, 1.0, scoring_func="sigmoid",
                         norm_topk_prob=True, n_shared_experts=0, held=held)


def layer_params(seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"router": f(E, G) / 8, "router_bias": jnp.asarray(
        1e-2 * rng.standard_normal(G), jnp.float32),
        "w_gate": f(G, E, W) / 8, "w_up": f(G, E, W) / 8,
        "w_down": f(G, W, E) / 5}


def share_of(params, first, count):
    cut = lambda a: a[first:first + count]
    return {**params, "w_gate": cut(params["w_gate"]),
            "w_up": cut(params["w_up"]), "w_down": cut(params["w_down"])}


def run_layer(cfg, params, x, decode=False):
    with jax.default_matmul_precision("highest"):
        out, _ = ExpertMLP(cfg).apply(
            {"params": params}, x, jnp.ones(x.shape[:2], bool), decode=decode,
            mutable=["intermediates", "cache"])
    return out


def uncut_layer(params, x):
    """The reference's equation over ALL the experts: every expert
    multiplies every token, the normalised sigmoid gates pick."""
    hi = dict(precision="highest")
    sigma = jax.nn.sigmoid(jnp.dot(x, params["router"], **hi))
    _, chosen = jax.lax.top_k(sigma + params["router_bias"], K)
    picked = (chosen[..., None] == jnp.arange(G)).any(axis=-2)
    gates = jnp.where(picked, sigma, 0.0)
    gates = gates / gates.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(G):
        y = jnp.dot(jax.nn.silu(jnp.dot(x, params["w_gate"][e], **hi))
                    * jnp.dot(x, params["w_up"][e], **hi),
                    params["w_down"][e], **hi)
        out = out + gates[..., e:e + 1] * y
    return out


@pytest.mark.parametrize("kernel", [False, True])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(kernel, monkeypatch):
    """Sixteen chips of 2 experts each: the parts of all shares equal the
    reference's layer over all 32 (no shared expert, no identity part: a
    share's output is its held experts' alone), and no share alone does."""
    if kernel:
        force_kernels(monkeypatch)
    params = layer_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 24, E)),
                    jnp.float32)
    whole = uncut_layer(params, x)
    assert float(jnp.abs(run_layer(layer_cfg(), params, x, decode=kernel)
                         - whole).max()) < 1e-5
    parts = [run_layer(layer_cfg((f, 2)), share_of(params, f, 2), x,
                       decode=kernel) for f in range(0, G, 2)]
    assert len(parts) == 16
    assert float(jnp.abs(sum(parts) - whole).max()) < 1e-5
    assert float(jnp.abs(parts[0] - whole).max()) > 0.1


# --- (e) what is refused by name stays refused -------------------------------


# (what the engines refuse for the model's caches: tests/test_cache_spec.py)


@pytest.mark.parametrize("case", ["dense_cache", "one_table", "narrow_ring",
                                  "latent"])
def test_refusals_are_named(model, case):
    _, _, module, tree = model
    ids = jnp.ones((1, 4), jnp.int32)
    if case == "dense_cache":
        with pytest.raises(ValueError, match="paged arena only"):
            module.apply(tree, ids, decode=True, mutable=["cache"])
    elif case == "one_table":
        m = paged(module, "gather")
        with pytest.raises(ValueError, match="rings"):
            m.apply(tree, ids, decode=True,
                    positions=jnp.zeros((1,), jnp.int32),
                    pages=jnp.zeros((1, TABLE), jnp.int32),
                    mutable=["cache"])
    elif case == "narrow_ring":
        m = paged(module, "gather")
        with pytest.raises(ValueError, match="does not hold a window"):
            m.apply(tree, ids, decode=True,
                    positions=jnp.zeros((1,), jnp.int32),
                    pages=(jnp.zeros((1, TABLE), jnp.int32),
                           jnp.zeros((1, 2), jnp.int32)),
                    mutable=["cache"])
    else:
        from kubeml_tpu.models.mla import MLAConfig

        with pytest.raises(ValueError, match="K/V-head attention"):
            module.clone(mla=MLAConfig(8, 8, 8, 4, 8)).init(
                jax.random.key(0), ids)
