"""GLM-4.7-Flash's block through the normal path (ISSUE 32): latent (MLA)
pages, a dense layer and then routed experts with a shared one.

Everything here runs a tiny preset with the published structure (hidden 64;
one dense layer and two expert layers; 8 experts, 2 a token, a selection
bias that is not zero; latent 16 + rope 8; 4 heads) in float32 on the CPU,
built by the benchmark's own builder and held against the benchmark's plain
reference (``benchmark/reference/glm_moe_lite.py``)."""

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from latent_rows import pad_lanes_are_zero  # noqa: E402

from benchmark.models import glm_moe_lite as builder  # noqa: E402
from benchmark.reference import glm_moe_lite as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models import experts as experts_mod  # noqa: E402
from kubeml_tpu.models.experts import ExpertsConfig, route  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.generation import (init_paged_cache,  # noqa: E402
                                          supports_paged_decode)
from kubeml_tpu.models.mla import MLAConfig  # noqa: E402
from kubeml_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from kubeml_tpu.ops import mla_attention  # noqa: E402
from kubeml_tpu.ops.mla_attention import (latent_row_width,  # noqa: E402
                                          mla_attn, mla_attn_gather,
                                          pad_lanes, span_pages, walk_trips)
from kubeml_tpu.ps.metrics import SERVING_COUNTERS  # noqa: E402
from kubeml_tpu.serving.batcher import PagedBatchingDecoder, _Row  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# float32 against float32 at precision "highest": what is left is the order
# of summation (sorted grouped products against a masked sum over experts,
# absorbed against expanded attention). Logits are about 1 wide; 1e-4 is
# thirty times the largest gap seen (3e-6) and a hundredth of a bfloat16
# rounding. It holds as long as no token's last choice is a tie between two
# experts to 1e-6 of a score, which seeded normal weights do not produce.
TOL = 1e-4


def tiny_cfg(**over):
    cfg = json.loads((ROOT / "benchmark/tests/data_glm/configs/tiny-glm.json")
                     .read_text())
    cfg.update(compute_dtype="float32", param_dtype="float32", n_positions=64)
    cfg.update(over)
    return cfg


def build(cfg, seed=3):
    weights = builder.init_weights(cfg, seed)
    # the builder's selection bias is a load-evening residue of 0.01
    # (assumed.init); ten times that changes choices, which is what the
    # comparisons with the reference here are to see
    weights["b_r"] = weights["b_r"] * 10
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


@pytest.fixture(scope="module")
def model():
    return build(tiny_cfg())


VOCAB = 211


def ref_logits(cfg, weights, ids, at, precision="float32"):
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
        precision=precision)[:len(at)]


def prompts(n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


# --- the model against the reference -------------------------------------


def test_whole_model_matches_reference(model):
    cfg, weights, module, tree = model
    spec = cache_spec(module)
    assert spec.latent is not None and spec.expert_layers == 2
    assert supports_paged_decode(module)
    assert float(jnp.abs(weights["b_r"]).max()) > 0.05   # a bias that bites
    ids = prompts(1, 37, 37)[0]
    with jax.default_matmul_precision("highest"):
        got, seen = module.apply(tree, ids[None], mutable=["intermediates"])
    want = ref_logits(cfg, weights, ids, np.arange(len(ids)))
    assert float(jnp.sqrt((want ** 2).mean())) > 0.3   # not all rounding
    assert float(jnp.abs(got[0] - want).max()) < TOL
    # and every token went to the experts the reference sent it to
    padded = np.zeros((cfg["n_positions"],), np.int32)
    padded[:len(ids)] = ids
    routed = np.asarray(reference.routing(
        weights, jnp.asarray(padded), n_head=cfg["n_head"],
        eps=cfg["layer_norm_epsilon"]))[:, :len(ids)]
    for layer in (1, 2):
        mine = np.asarray(seen["intermediates"][f"block_{layer}"]["experts"]
                          ["chosen"][0])
        assert (np.sort(mine, -1) == np.sort(routed[layer - 1], -1)).all()


def test_moe_every_models_are_still_refused():
    old = gpt.CausalTransformer(vocab_size=11, max_len=16, embed_dim=32,
                                depth=2, num_heads=2, moe_every=2)
    assert not supports_paged_decode(old)
    assert supports_paged_decode(gpt.GPTTiny())
    assert not cache_spec(old).properties


def test_selection_uses_the_bias_and_weights_do_not():
    s = jnp.asarray([[0.9, 0.8, 0.5, 0.1]], jnp.float32)
    b = jnp.asarray([0.0, -0.5, 0.0, 0.6], jnp.float32)
    chosen, gates = route(s, b, 2, 1.8)
    # s + b = .9 .3 .5 .7: experts 0 and 3 are selected, not 0 and 1 ...
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 3]
    # ... and weighted by s alone: 1.8 * (.9, .1) / 1.0
    by = dict(zip(np.asarray(chosen)[0].tolist(),
                  np.asarray(gates)[0].tolist()))
    assert by[0] == pytest.approx(1.62) and by[3] == pytest.approx(0.18)
    plain, g2 = route(s, jnp.zeros(4), 2, 1.0)
    assert sorted(np.asarray(plain)[0].tolist()) == [0, 1]
    assert sorted(np.asarray(g2)[0].tolist()) == pytest.approx(
        [0.8 / 1.7, 0.9 / 1.7])


def test_no_token_is_dropped_when_all_choose_the_same_experts():
    """A selection bias that sends every token to experts 5 and 2: 2 of 8
    groups hold everything, and the model still equals the reference (a
    capacity of 1.25 x tokens / experts would keep 16% of them)."""
    cfg, weights, module, tree = build(tiny_cfg(), seed=11)
    bias = np.zeros((2, 8), np.float32)
    bias[:, [5, 2]] = 10.0
    weights = dict(weights, b_r=jnp.asarray(bias))
    for i in (1, 2):
        tree["params"][f"block_{i}"]["experts"]["router_bias"] = \
            jnp.asarray(bias[i - 1])
    ids = prompts(1, 48, 48, seed=1)[0]
    with jax.default_matmul_precision("highest"):
        got = module.apply(tree, ids[None])[0]
    want = ref_logits(cfg, weights, ids, np.arange(len(ids)))
    assert float(jnp.abs(got - want).max()) < TOL


def test_lower_precision_control_departs_and_bfloat16_stays(model):
    """bfloat16 compute on the same weights stays inside a stated width of
    the float32 reference; the control the limits are set against (every
    product's operands in fp8 e4m3) does not."""
    cfg, weights, _, tree = model
    ids = prompts(1, 40, 40, seed=2)[0]
    at = np.arange(len(ids))
    want = ref_logits(cfg, weights, ids, at)
    low = ref_logits(cfg, weights, ids, at, precision="fp8_e4m3")
    _, _, half, _ = build(tiny_cfg(compute_dtype="bfloat16"))
    got = half.apply(tree, ids[None])[0]
    # bfloat16 keeps 8 bits: products of order 1 are off by 2^-9 each and
    # three layers of them add up to a few hundredths of a logit (0.03-0.06
    # seen); a flipped last choice of an expert moves more, and is rare at
    # 8 experts. fp8 e4m3 keeps 4 bits and moves the logits 5-10 times that
    assert float(jnp.abs(got - want).max()) < 0.15
    assert float(jnp.abs(low - want).max()) > 0.15
    assert float(jnp.abs(low - want).max()) > 3 * float(
        jnp.abs(got - want).max())


# --- the kernels against their oracles -----------------------------------


@pytest.mark.parametrize("sizes", [
    [0, 5, 0, 19, 0, 0, 0, 0],          # empty groups between
    [24, 0, 0, 0, 0, 0, 0, 0],          # a single group
    [3, 1, 7, 0, 2, 9, 10, 8],          # uneven
    [0, 0, 0, 0, 0, 0, 0, 0],           # nothing at all
    [100, 0, 300, 7, 0, 250, 1, 40],    # several row tiles
])
@pytest.mark.parametrize("gated", [False, True])
def test_grouped_product_equals_a_loop_over_experts(sizes, gated):
    rng = np.random.default_rng(sum(sizes) + gated)
    G, k, n = len(sizes), 32, 48
    m = max(sum(sizes), 8) + 5                   # rows of no group at the end
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((G, k, n)), jnp.float32)
    up = jnp.asarray(rng.standard_normal((G, k, n)), jnp.float32)
    want = np.zeros((m, n), np.float32)
    at = 0
    with jax.default_matmul_precision("highest"):
        for g, size in enumerate(sizes):
            rows = lhs[at:at + size]
            y = rows @ w[g]
            if gated:
                y = jax.nn.silu(y) * (rows @ up[g])
            want[at:at + size] = y
            at += size
        for kernel in (False, True):
            got = grouped_matmul(lhs, w, jnp.asarray(sizes, jnp.int32),
                                 up if gated else None, kernel=kernel)
            assert got.shape == (m, n)
            assert np.allclose(np.asarray(got)[:at], want[:at],
                               atol=1e-4)   # (the empty case runs too)


# (table width, positions, dc, dr, heads, page tokens, arena type, retired
# rows). First the toy shapes: a toy latent (24 values in a 128-lane row),
# the published one (576 in 640) and one that fills its rows (640: nothing
# added), under tables narrower than one span of the kernel's loop. Then
# what a loop over a row's live spans can get wrong, at the published latent
# and the three cells' head counts: a depth that ends inside a span, on a
# span's last token and one token past it, a row at position 0 and one that
# fills its table, under a table of several spans that is no whole number of
# them, of two spans and of less than one; a retired row, whose table points
# at the trash page (page 0) and whose cursor stays where its request left
# it, beside live rows (it reads that one page, whatever its cursor says, and
# what comes out is the engine's to drop); bfloat16 arenas beside float32
_WALK_CASES = [
    pytest.param(P, pos, dc, dr, 4, 4, "float32", (), id=f"P{P}-{dc}+{dr}")
    for dc, dr in [(16, 8), (512, 64), (512, 128)]
    for P, pos in [(16, [0, 17, 63]), (8, [31, 5, 8]), (3, [11, 0, 7])]
] + [
    pytest.param(80, [300, 511, 512, 0, 1279], 512, 64, 20, 16, "float32",
                 (), id="spans-2.5-H20-f32"),
    pytest.param(80, [1023, 1024, 700, 513], 512, 64, 20, 16, "bfloat16",
                 (2,), id="spans-2.5-H20-bf16-retired"),
    pytest.param(64, [1023, 40, 512, 511], 512, 64, 64, 16, "bfloat16",
                 (1,), id="spans-2-H64-bf16-retired"),
    pytest.param(16, [0, 100, 255], 512, 64, 32, 16, "bfloat16", (),
                 id="under-a-span-H32-bf16"),
    pytest.param(16, [255, 16, 15], 512, 64, 32, 16, "float32", (0,),
                 id="under-a-span-H32-f32-retired"),
    pytest.param(80, [511, 512, 513, 1279], 16, 8, 4, 16, "float32", (3,),
                 id="spans-2.5-toy-retired"),
]


@pytest.mark.parametrize("P,positions,dc,dr,H,pt,dtype,retired", _WALK_CASES)
def test_latent_page_walk_kernel_equals_gather(P, positions, dc, dr, H, pt,
                                               dtype, retired):
    rng = np.random.default_rng(P)
    B = len(positions)
    N = max(40, B * P + 1)       # the wide tables' pages all distinct
    W, R = dc + dr, latent_row_width(dc + dr)
    assert R % 128 == 0 and 0 <= R - W < 128
    assert max(positions) < P * pt
    tol = 1e-5 if dtype == "float32" else 2e-2
    q = jnp.asarray(rng.standard_normal((B, H, W)), dtype)
    live = jnp.asarray(rng.standard_normal((N, pt, W)), dtype)
    arena = pad_lanes(live, R)                   # the rows as they are stored
    assert arena.shape == (N, pt, R) and (arena is live) == (R == W)
    table = (rng.integers(1, N, (B, P)) if N == 40
             else rng.permutation(N - 1)[:B * P].reshape(B, P) + 1)
    table[list(retired)] = 0                     # the trash page, all along
    pages = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    how = dict(value_dim=dc, scale=W ** -0.5)
    # (one trace of the kernel for the calls of one shape)
    walk = jax.jit(lambda *args: mla_attn(*args, **how))
    got = walk(q, arena, pages, pos)
    want = mla_attn_gather(q, arena, pages, pos, **how)
    assert got.shape == (B, H, dc) and got.dtype == q.dtype
    alive = np.array([b not in retired for b in range(B)])

    def gap(a, b):
        return float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32))[alive].max())

    # a retired row's result is dropped; it is some average of finite rows
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())

    assert gap(got, want) < tol
    # the added lanes add exact zeros: the same call on an arena of the live
    # values alone (the layout before PR 43) gives the same bits
    assert np.array_equal(got, walk(q, live, pages, pos))
    assert np.array_equal(want, mla_attn_gather(q, live, pages, pos, **how))
    # pages past a row's depth are never looked at: poison them
    depth = np.where(alive, (np.asarray(positions) // pt) + 1, 1)
    poisoned = np.asarray(arena.astype(jnp.float32)).copy()
    keep = {int(p) for b in range(B) for p in table[b, :depth[b]]}
    for p in range(N):
        if p not in keep:
            poisoned[p] = np.nan
    again = walk(q, jnp.asarray(poisoned, dtype), pages, pos)
    assert gap(again, want) < tol
    # (a retired row looked at its page of trash and nowhere else)
    assert bool(jnp.isfinite(again.astype(jnp.float32)).all())


# --- the host's mirror of the walk's loop -----------------------------------


@pytest.mark.parametrize("w,pt,positions,want", [
    # the cells' pages of 16: a span is 32 pages, 512 positions. A row at
    # position 0 is one trip of one page; 511 fills the first span, 512
    # opens the second; 2,399 is 150 pages, 5 trips; a cursor past the
    # table walks the table and no further
    (256, 16, [0], (1, 1)), (256, 16, [511], (1, 32)),
    (256, 16, [512], (2, 33)), (256, 16, [2399], (5, 150)),
    (256, 16, [5000], (8, 256)),
    (256, 16, [0, 511, 512, 2399, 5000], (17, 472)),
    # a table's width costs nothing: the same rows under 128 and 256 pages
    (128, 16, [1100, 1500, 2000], (3 + 3 + 4, 69 + 94 + 126)),
    (256, 16, [1100, 1500, 2000], (3 + 3 + 4, 69 + 94 + 126)),
    # a table narrower than a span is its row's one trip
    (16, 16, [0, 100, 255], (3, 1 + 7 + 16)), (3, 4, [11, 0, 7], (3, 6)),
    # pages of 4: a span is 128 pages
    (300, 4, [511, 512, 1199], (1 + 2 + 3, 128 + 129 + 300)),
    (64, 16, [], (0, 0)),
])
def test_the_mirror_counts_trips_and_pages_by_hand(w, pt, positions, want):
    assert walk_trips(w, pt, positions) == want
    # a row whose table starts at the trash page: a trip of a page, each
    assert walk_trips(w, pt, positions, dead_rows=3) == (want[0] + 3,
                                                         want[1] + 3)
    assert span_pages(w, pt) == min(w, 512 // pt)


@pytest.mark.parametrize("P,positions,dead", [
    (80, [300, 511, 512, 0, 1279], ()), (16, [0, 100, 255], ()),
    (80, [300, 700, 513], (1,))])
def test_the_kernel_copies_the_pages_the_mirror_counts(P, positions, dead):
    """The mirror's rules are the kernel's own (one function each), and what
    they say is what the kernel reads: a row's pages all distinct, every
    page past the mirror's count poisoned changes nothing, and the last page
    inside it poisoned is seen, by that row alone. A dead row's count is
    the trash page, whatever its cursor."""
    rng = np.random.default_rng(7)
    pt, H, dc, dr = 16, 4, 512, 64
    B, N = len(positions), len(positions) * P + 1
    q = jnp.asarray(rng.standard_normal((B, H, dc + dr)), jnp.float32)
    arena = np.asarray(pad_lanes(jnp.asarray(
        rng.standard_normal((N, pt, dc + dr)), jnp.float32), 640)).copy()
    table = rng.permutation(N - 1)[:B * P].reshape(B, P) + 1
    table[list(dead)] = 0
    how = dict(value_dim=dc, scale=0.1)
    kernel = jax.jit(lambda *args: mla_attn(*args, **how))
    walk = lambda a: np.asarray(kernel(
        q, jnp.asarray(a), jnp.asarray(table, jnp.int32),
        jnp.asarray(positions, jnp.int32)))
    want = walk(arena)
    counted = [walk_trips(P, pt, [], dead_rows=1)[1] if b in dead
               else walk_trips(P, pt, [p])[1]
               for b, p in enumerate(positions)]
    assert sum(counted) == walk_trips(
        P, pt, [p for b, p in enumerate(positions) if b not in dead],
        dead_rows=len(dead))[1]
    past = arena.copy()
    keep = {int(page) for b, n in enumerate(counted) for page in table[b, :n]}
    past[[page for page in range(N) if page not in keep]] = np.nan
    assert np.array_equal(walk(past), want)
    for b, n in enumerate(counted):
        last = arena.copy()
        last[table[b, n - 1]] = np.nan
        got = walk(last)
        assert np.isnan(got[b]).all()
        assert np.array_equal(np.delete(got, b, 0), np.delete(want, b, 0))


# --- the latent arena's bytes ----------------------------------------------


def test_a_latent_page_is_576_values_a_token_once(model):
    published = json.loads(
        (ROOT / "benchmark/configs/glm-4.7-flash.json").read_text())
    ns = {}
    exec(builder.function_source(published), ns)
    glm = ns["Model"]().build()
    assert glm.mla.latent_width == 576 and glm.depth == 6
    # bfloat16: 1,152 B a token and layer, 6,912 B over the six layers
    assert cache_spec(glm).token_bytes() == 6 * 576 * 2 == 6912
    # stored in whole 128-lane rows: 640 lanes, 1,280 B a token and layer
    assert glm.mla.row_width == 640
    assert cache_spec(glm).page_bytes(16) == 16 * 6 * 640 * 2
    # a latent that fills its rows is stored as it is
    assert dataclasses.replace(glm.mla, qk_rope_head_dim=128).row_width == 640
    # expanded K and V for the same token would be 20 x (256 + 256) x 2 B
    mha = glm.clone(mla=None, head_dim=256)
    assert cache_spec(mha).token_bytes() == 6 * 2 * 20 * 256 * 2 == 122880
    # the arrays agree: the tiny model's arena, leaf by leaf
    _, _, module, tree = model
    m = module.clone(page_tokens=8, kv_pages=33)
    cache = init_paged_cache(m, tree, 4, 8)
    arenas = [l for path, l in jax.tree_util.tree_leaves_with_path(cache)
              if getattr(path[-1], "key", "") == "latent_pages"]
    assert [a.shape for a in arenas] == [(33, 8, 128)] * 3
    assert sum(a.nbytes for a in arenas) == 33 * cache_spec(m).page_bytes(8)
    assert not any(getattr(path[-1], "key", "") == "kv_rows"
                   for path, _ in jax.tree_util.tree_leaves_with_path(cache))


# --- the paged path: module level ----------------------------------------


PT, SLOTS, TABLE = 8, 4, 8


def paged(module, impl="pallas"):
    return module.clone(page_tokens=PT, kv_pages=SLOTS * TABLE + 1,
                        paged_attn=impl)


def table(rows, n=None):
    tbl = np.zeros((len(rows) if n is None else n, TABLE), np.int32)
    for i, r in enumerate(rows):
        tbl[i if n is None else r] = 1 + r * TABLE + np.arange(TABLE)
    return tbl


def admit(m, tree, cache, rows, seqs, bucket, base=None):
    """One admission program as the engine calls it: ``seqs`` padded to
    ``bucket``, row i of the batch paged through row ``rows[i]``'s pages."""
    n = len(seqs)
    ids = np.zeros((n, bucket), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    base = np.zeros((n,), np.int32) if base is None else np.asarray(base)
    with jax.default_matmul_precision("highest"):
        logits, upd = jax.jit(lambda *a: m.apply(
            {**tree, "cache": a[0]}, a[1], decode=True, positions=a[2],
            pages=a[3], seq_lens=a[4], mutable=["cache"]))(
            cache, jnp.asarray(ids), jnp.asarray(base),
            jnp.asarray(table(rows)),
            jnp.asarray([len(s) for s in seqs], jnp.int32))
    return logits, upd["cache"]


@pytest.mark.parametrize("impl", ["pallas", "gather"])
def test_prefill_then_decode_logits_match_reference(model, impl, monkeypatch):
    """Rows of different lengths in one padded admit (expanded attention),
    then decode steps (absorbed attention, through the kernel and through
    its oracle) over the whole slab with one row dead: every logit against
    the reference's full forward. The expert layer picks ``moe_experts`` by
    the backend alone, so the ``pallas`` case puts the kernel (interpret
    mode) in its place here as a TPU would."""
    cfg, weights, module, tree = model
    if impl == "pallas":
        monkeypatch.setattr(
            experts_mod, "grouped_matmul",
            lambda *a, kernel, **kw: grouped_matmul(*a, kernel=True, **kw))
    m = paged(module, impl)
    cache = init_paged_cache(m, tree, SLOTS, TABLE)
    seqs = [p[:n] for p, n in zip(prompts(3, 40, 40, seed=5), (5, 17, 30))]
    rows = [2, 0, 3]
    logits, cache = admit(m, tree, cache, rows, seqs, 32)
    assert pad_lanes_are_zero(cache, 3)
    full = [list(s) for s in seqs]
    for i, s in enumerate(seqs):
        want = ref_logits(cfg, weights, s, np.arange(len(s)))
        assert float(jnp.abs(logits[i, :len(s)] - want).max()) < TOL
    step_fn = jax.jit(lambda c, tok, pos, tbl, live: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=live, mutable=["cache"]))
    tbl = table(rows, SLOTS)
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
        assert pad_lanes_are_zero(cache, 3)        # the dead row's write too
        for r, f in zip(rows, full):
            want = ref_logits(cfg, weights, f, [len(f) - 1])
            assert float(jnp.abs(logits[r, 0] - want[0]).max()) < TOL
        # three live rows of two choices each, two expert layers: each
        # layer says how many of its 8 experts they chose
        touched = [int(l) for path, l in
                   jax.tree_util.tree_leaves_with_path(cache)
                   if getattr(path[-1], "key", "") == "experts_touched"]
        assert len(touched) == 2 and all(2 <= t <= 6 for t in touched)


def test_absorbed_attention_equals_expanded(model):
    """The same five positions of one row, once as a five-position window
    over a cached prefix (expanded: per-head K and V from the gathered
    latents) and once as five decode steps (absorbed)."""
    _, _, module, tree = model
    m = paged(module, "gather")
    seq = prompts(1, 30, 30, seed=8)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    _, cache = admit(m, tree, empty, [1], [seq[:25]], 32)
    # a suffix admitted over cached pages, as after a prefix hit
    window, shared = admit(m, tree, cache, [1], [seq[25:]], 8, base=[25])
    assert pad_lanes_are_zero(shared, 3)
    step_fn = jax.jit(lambda c, tok, pos, tbl: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=jnp.ones((1,), jnp.int32), mutable=["cache"]))
    for i in range(5):
        with jax.default_matmul_precision("highest"):
            logits, upd = step_fn(cache, jnp.asarray(seq[25 + i:26 + i]),
                                  jnp.asarray([25 + i], jnp.int32),
                                  jnp.asarray(table([1])))
        cache = upd["cache"]
        assert float(jnp.abs(logits[0, 0] - window[0, i]).max()) < TOL


def test_the_pad_bucket_cannot_be_seen(model):
    _, _, module, tree = model
    m = paged(module)
    prompt = prompts(1, 13, 13, seed=6)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    a, _ = admit(m, tree, empty, [0], [prompt], 16)
    b, _ = admit(m, tree, empty, [3], [prompt], 32)
    assert float(jnp.abs(a[0, :13] - b[0, :13]).max()) < TOL


def test_a_dense_decode_cache_is_refused_by_name(model):
    _, _, module, tree = model
    with pytest.raises(ValueError, match="paged arena only"):
        module.apply(tree, jnp.ones((1, 4), jnp.int32), decode=True,
                     mutable=["cache"])


# --- the engine ----------------------------------------------------------


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
    admits, slot reuse and decode steps beside rows that ended; the
    counters of the expert layers come back with the steps."""
    cfg, weights, module, tree = model
    ps = prompts(7, 3, 30, seed=9)
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            out = serve(dec, ps, 9)
            tel = dec.telemetry()
        finally:
            dec.close()
    for p, toks in zip(ps, out):
        assert len(toks) == 9
        assert served_gap(cfg, weights, p, toks) < TOL
    assert tel["kv_latent_width"] == 24.0 and tel["moe_layers"] == 2.0
    assert tel["kv_latent_row_width"] == 128.0   # the lanes they are stored in
    # the latent walk is another kernel: no grid of K/V chunks to count
    assert "walk_chunks_live" not in tel and "walk_chunks_grid" not in tel
    assert "tile_chunks_live" not in tel and "tile_chunks_grid" not in tel
    # 2 layers x 8 experts x 3 matrices of 64 x 32 float32
    assert tel["expert_param_bytes"] == 2 * 8 * 3 * 64 * 32 * 4
    # a step's live rows make 2 choices in each of 2 layers; the first
    # token of a request comes from its admission, not from a step
    assert tel["moe_assignments"] == 7 * 8 * 2 * 2
    steps, touched = tel["device_steps"], tel["moe_experts_touched"]
    assert 2 * 2 * steps <= touched <= min(tel["moe_assignments"],
                                           2 * 8 * steps)
    assert tel["paged_attn_kernel"] == 1.0
    assert tel["param_bytes"] == sum(
        l.size * 4 for l in jax.tree.leaves(tree))


def make_row(dec, prompt_len, max_new):
    ids = np.arange(1, prompt_len + 1).astype(np.int32)
    lease = dec._pool.admit(ids, max_new, max_positions=dec.max_len)
    row = _Row(entry=None, index=0, prompt=ids, max_new=max_new, temp=0.0,
               topk=0, eos=-1, key=np.zeros(2, np.uint32), lease=lease)
    row.pos_cap = prompt_len
    return row


@pytest.mark.parametrize("steps", [1, 2, 3])
def test_the_engine_counts_the_walks_trips_dead_rows_too(model, monkeypatch,
                                                         steps):
    """A span cut to 16 positions (2 pages of this engine's 8), three latent
    layers: a row whose next query sits at position 30 holds 4 pages, 2
    trips; one at 5 holds 1; the two program rows nobody holds (one retired,
    one never used) walk a page of trash a step. Step 3's query of the deep
    row, position 32, opens its fifth page and third trip."""
    monkeypatch.setattr(mla_attention, "_SPAN", 16)
    dec = engine(model)
    assert dec.stats.walks_latents and not dec.stats.walks_kv_chunks
    deep, shallow = make_row(dec, 30, 20), make_row(dec, 5, 8)
    dec._slot_rows[0], dec._slot_rows[2] = deep, shallow
    try:
        w = dec._live_table_width(steps)
        assert w == 8 and span_pages(w, PT) == 2
        live, run, pages = dec._latent_walk_trips(w, steps)
        layers = 3
        fifth = steps == 3
        assert live == (steps * (2 + 1) + fifth) * layers
        assert run == live + steps * 2 * layers
        assert pages == (steps * (4 + 1 + 1 + 1) + fifth) * layers
    finally:
        dec._slot_rows[0] = dec._slot_rows[2] = None
        for r in (deep, shallow):
            dec._pool.release(r.lease)
        dec._pool.check()
        dec.close()


def test_a_latent_engines_snapshot_carries_the_walks_counts(model,
                                                            monkeypatch):
    """One request of 7 prompt tokens and 6 new ones on four program rows:
    five steps after the admit's token, queries at positions 7-11, which
    hold 1, 2, 2, 2, 2 pages of 8; the other three rows walk one page of
    trash a step. With a span of one page the row makes 1, 2, 2, 2, 2 trips;
    the tokens are the reference's all the same."""
    cfg, weights, module, tree = model
    monkeypatch.setattr(mla_attention, "_SPAN", 8)
    p = prompts(1, 7, 7, seed=5)[0]
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            (toks,) = serve(dec, [p], 6)
            tel = dec.telemetry()
        finally:
            dec.close()
    assert served_gap(cfg, weights, p, toks) < TOL
    layers, dead = 3, SLOTS - 1
    assert tel["latent_walk_trips_live"] == (1 + 4 * 2) * layers
    assert tel["latent_walk_trips_run"] == (1 + 4 * 2 + 5 * dead) * layers
    assert tel["latent_walk_pages"] == (1 + 4 * 2 + 5 * dead) * layers
    keys = {key for key, _ in SERVING_COUNTERS.values()}
    assert {"latent_walk_trips_live", "latent_walk_trips_run",
            "latent_walk_pages"} <= keys


def test_an_engine_that_walks_no_latents_reports_no_trips():
    from kubeml_tpu.serving.stats import DecoderStats

    snap = DecoderStats(slots=2).snapshot()
    assert not [k for k in snap if k.startswith("latent_walk_")]


def test_block_traces_grow_by_two_a_program(model):
    """One trace for the dense layer's kind and one for the expert layers',
    whatever the depth: sizing the cache, an admission program and a step
    program cost two each, where a stack of one kind pays one each."""
    before = gpt.block_traces()
    dec = engine(build(tiny_cfg(num_hidden_layers=5, n_layer=5), seed=4),
                 slots=3)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["compiled_programs"] == 2.0
    assert gpt.block_traces() - before == 2 * 3
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


def test_a_prefix_hit_on_latent_pages_serves_the_same_tokens(model):
    cfg, weights, _, _ = model
    shared = prompts(1, 24, 24, seed=13)[0]           # three whole pages
    tails = prompts(3, 3, 9, seed=14)
    ps = [np.concatenate([shared, t]) for t in tails]
    with jax.default_matmul_precision("highest"):
        dec = engine(model, prefix_cache=True)
        try:
            first = serve(dec, ps[:1], 6)
            rest = serve(dec, ps[1:], 6)
            tel = dec.telemetry()
        finally:
            dec.close()
    assert tel["prefix_hits"] == 2.0 and tel["prefix_tokens_saved"] == 48.0
    for p, toks in zip(ps, first + rest):
        assert served_gap(cfg, weights, p, toks) < TOL


def test_engine_spans_name_the_expert_layers(model):
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
    steps = [s for s in spans if s.name == "engine.dispatch"
             and s.attrs["program"] == "step"]
    assert admits and steps
    assert all(s.attrs["moe_layers"] == 2 for s in admits + steps)


def test_latent_and_expert_refusals_are_named(model):
    """The model's own refusal; what the engines refuse for its caches is
    tests/test_cache_spec.py's table."""
    _, _, module, tree = model
    with pytest.raises(ValueError, match="expert models"):
        module.apply(tree, jnp.ones((1, 4), jnp.int32), exit_layer=1)
