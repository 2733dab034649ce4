"""Kimi-Linear's stack through the normal path (ISSUE 51): Kimi Delta
Attention layers (a delta rule gated PER KEY CHANNEL, ``ops/gated_delta.py``
with ``g`` ``[.., H, d_k]``, ``models/gated_deltanet.py KDAConfig``) three to
every latent-attention layer that projects its query directly and rotates
nothing (``models/mla.py`` ``q_lora_rank`` None, ``mla_use_nope``), a
recurrent state beside a latent arena in one cache description, a dense first
layer and then a chip's share of sigmoid-routed experts beside a shared one.

Everything here runs a tiny preset with the published structure (hidden 64;
8 layers: KDA x3, MLA, twice; 4 heads, keys and values of 16 in the KDA
layers, ``d_k = d_v`` as published, so TWO stored head pairs; latents of 16 +
8; 16 of 64 experts held, 4 a token; pages of 4) in float32 on the CPU, built
by the benchmark's own builder and held against the benchmark's plain
reference (``benchmark/reference/kimi_linear.py``)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.models import kimi_linear as builder  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.experts import ExpertMLP, ExpertsConfig  # noqa: E402
from kubeml_tpu.models.gated_deltanet import GDNConfig, KDAConfig  # noqa: E402
from kubeml_tpu.models.generation import (init_paged_cache,  # noqa: E402
                                          supports_paged_decode)
from kubeml_tpu.models.mla import MLAConfig, MLAttention  # noqa: E402
from kubeml_tpu.ops import gated_delta as gd  # noqa: E402
from kubeml_tpu.serving.batcher import (BatchingDecoder,  # noqa: E402
                                        CacheFeatureUnsupported,
                                        PagedBatchingDecoder)

ROOT = Path(__file__).resolve().parent.parent
# float32 against float32 at precision "highest": what is left is the order
# of summation (a chunk's triangular solve and sub-block products against a
# scan over positions; an online softmax over pages, in the absorbed form,
# against one softmax in the expanded form; a grouped product against a
# masked sum). Logits are about 4 wide; 5e-4 is a fiftieth of a bfloat16
# rounding of one, and a head's decays replaced by their mean, a rotated
# latent, a missing delta term, the held experts left out and a state kept
# in bfloat16 each miss it by one to three orders (below).
TOL = 5e-4
VOCAB, PT, SLOTS, TABLE = 211, 4, 4, 16
H, D = 4, 16


def tiny_cfg(**over):
    cfg = json.loads((ROOT / "benchmark/tests/data_kimi/configs/"
                      "tiny-kimi.json").read_text())
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


# --- the recurrence with a gate per key channel, three ways -----------------


def _inputs(L, b=2, seed=0, fastest=1.6):
    """Decays from ``e^-fastest`` a position to nearly none, every key
    channel its own; beta over (0, 1)."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    l2 = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = l2(normal(b, L, H, D)) * D ** -0.5
    k = l2(normal(b, L, H, D))
    v = normal(b, L, H, D)
    g = -fastest * rng.random((b, L, H, D), np.float32) ** 2
    beta = 1.0 / (1.0 + np.exp(-normal(b, L, H)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


# jitted: op by op the chunked form is four dozen small programs a call
_sequential = jax.jit(gd.gdn_sequential)
_chunked = jax.jit(gd.gdn_chunked, static_argnames=("chunk",))


@pytest.mark.parametrize("L,chunk", [(64, 64), (128, 64), (37, 64), (100, 64),
                                     (5, 64), (1, 64), (50, 16), (200, 32)])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_matches_sequential_per_channel(L, chunk, carried):
    a = _inputs(L, seed=L)
    S0 = (jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, H, D, D)), jnp.float32) if carried else None)
    o1, S1 = _sequential(*a, init_state=S0)
    o2, S2 = _chunked(*a, chunk=chunk, init_state=S0)
    assert float(jnp.abs(o1 - o2).max()) < 2e-5
    assert float(jnp.abs(S1 - S2).max()) < 2e-5


@pytest.mark.parametrize("L,chunk", [(64, 64), (150, 64), (90, 64)])
def test_channels_that_forget_everything_in_a_chunk_stay_finite(L, chunk):
    """Decays down to ``e^-5.5`` a position: a chunk's running sum passes
    -300 and ``exp(-gamma)`` would overflow float32 forty positions in. The
    sub-blocks' reference points keep every exponent at or under 0: finite,
    and the oracle's numbers."""
    q, k, v, g, beta = _inputs(L, seed=L + 1, fastest=5.5)
    g = jnp.minimum(g, -5.0 * (jnp.arange(D) % 2))   # every other channel
    assert float(g.sum(1).min()) < -300.0
    S0 = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, H, D, D)), jnp.float32)
    o1, S1 = _sequential(q, k, v, g, beta, init_state=S0)
    o2, S2 = _chunked(q, k, v, g, beta, chunk=chunk, init_state=S0)
    assert bool(jnp.isfinite(o2).all()) and bool(jnp.isfinite(S2).all())
    assert float(jnp.abs(o1 - o2).max()) < 2e-5
    assert float(jnp.abs(S1 - S2).max()) < 2e-5


def test_equal_channels_are_the_scalar_gate():
    """A head's ``d_k`` decays all equal IS one decay a head: the oracle bit
    for bit, the chunked scan (another path inside a chunk) to 1e-6."""
    q, k, v, g, beta = _inputs(100, seed=4)
    scalar = g[..., 0]
    same = jnp.broadcast_to(scalar[..., None], g.shape)
    o1, S1 = _sequential(q, k, v, scalar, beta)
    o2, S2 = _sequential(q, k, v, same, beta)
    assert jnp.array_equal(o1, o2) and jnp.array_equal(S1, S2)
    o3, S3 = _chunked(q, k, v, scalar, beta, chunk=64)
    o4, S4 = _chunked(q, k, v, same, beta, chunk=64)
    assert float(jnp.abs(o3 - o4).max()) < 1e-6
    assert float(jnp.abs(S3 - S4).max()) < 1e-6
    # and channels that differ are another function
    o5, _ = _sequential(q, k, v, g, beta)
    assert float(jnp.abs(o5 - o1).max()) > 0.01


def test_masked_positions_leave_the_state_alone():
    """``g = beta = 0`` past a row's length: the state after 70 padded
    positions is the state after the 23 real ones."""
    q, k, v, g, beta = _inputs(70, seed=1)
    keep = (jnp.arange(70) < 23).astype(jnp.float32)[None, :, None]
    _, S_pad = _chunked(q, k, v, g * keep[..., None], beta * keep, chunk=32)
    _, S_cut = _sequential(*(a[:, :23] for a in (q, k, v, g, beta)))
    assert float(jnp.abs(S_pad - S_cut).max()) < 2e-5


@pytest.mark.parametrize("heads,dk,dv", [(4, 16, 16), (2, 8, 16), (5, 8, 16),
                                         (3, 128, 128)])
def test_kda_update_kernel_matches_one_sequential_position(heads, dk, dv):
    """Two stored pairs, one pair, an odd head count (nothing to pair) and
    the published head of one whole lane row (eight vector rows exactly)."""
    R = 5
    ks = jax.random.split(jax.random.key(heads), 7)
    S0 = jax.random.normal(ks[0], (R, heads, dk, dv))
    q = jax.random.normal(ks[1], (R, heads, dk)) * 0.3
    k = jax.random.normal(ks[2], (R, heads, dk)) * 0.3
    v = jax.random.normal(ks[3], (R, heads, dv))
    g = -jax.random.uniform(ks[4], (R, heads, dk)) * 5.0
    beta = jax.random.uniform(ks[5], (R, heads))
    live = jnp.asarray([1, 0, 1, 1, 0], jnp.float32)[:, None]
    g, beta = g * live[..., None], beta * live
    o_ref, S_ref = gd.gdn_update_reference(S0, q, k, v, g, beta)
    packed = gd.pack_state(S0)
    assert gd.heads_packed(heads, dv) == (2 if (heads, dv) in (
        (4, 16), (2, 16)) else 1)
    o, S = gd.gdn_update(packed, q, k, v, g, beta, interpret=True)
    S = gd.unpack_state(S, heads)
    assert float(jnp.abs(o - o_ref)[live[:, 0] > 0].max()) < 1e-5
    assert float(jnp.abs(S - S_ref).max()) < 1e-5
    # a dead row gets its state back bit for bit
    assert jnp.array_equal(S[1], S0[1]) and jnp.array_equal(S[4], S0[4])
    # and a head's scalar in the gate's place is another state
    _, S_mean = gd.gdn_update(packed, q, k, v, g.mean(-1), beta,
                              interpret=True)
    assert float(jnp.abs(gd.unpack_state(S_mean, heads) - S_ref).max()) > 0.1


@pytest.mark.parametrize("channels,name", [(True, "kda_update"),
                                           (False, "gdn_update")])
def test_the_update_is_named_by_its_gate_and_writes_in_place(channels, name):
    """One entry, two names in a trace; the state is aliased to the kernel's
    output either way."""
    g = jnp.zeros((4, H, D) if channels else (4, H))
    args = (jnp.zeros((4, H // 2, D, 2 * D)), jnp.zeros((4, H, D)),
            jnp.zeros((4, H, D)), jnp.zeros((4, H, D)), g, jnp.zeros((4, H)))
    fn = lambda *a: gd.gdn_update(*a, interpret=False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and name in text
    assert ("kda_update" in text) == channels
    assert "output_operand_aliases" in text


def test_the_mixers_configurations_say_their_gate():
    kda = KDAConfig(num_heads=32, head_dim=128)
    gdn = GDNConfig(num_heads=30, key_dim=96, value_dim=192)
    assert (kda.key_dim, kda.value_dim, kda.d_conv) == (128, 128, 4)
    assert (kda.gate_width, gdn.gate_width) == (128, 1)
    assert (kda.neg_eigval, gdn.neg_eigval) == (False, True)
    # the published state: 32 x 128 x 128 float32 and three taps of 12,288
    assert kda.state_row_bytes == 4 * (32 * 128 * 128 + 3 * 12288) == 2244608


# --- latent attention: a direct query, no rotation ---------------------------


def _mla(**over):
    kw = dict(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True)
    kw.update(over)
    return MLAttention(4, MLAConfig(**kw), page_tokens=PT, kv_pages=9,
                       paged_attn="pallas")


def test_direct_query_without_rotation_expanded_against_absorbed():
    """A prompt attended in one paged call (the expanded form) against the
    same prompt but its last token, then that token as a decode step (the
    absorbed form, through the page walk): one function. There is one
    ``q_proj`` and no query latent; and with rotation the same weights give
    other numbers, so ``mla_use_nope`` is read."""
    attn = _mla()
    x = jax.random.normal(jax.random.key(0), (1, 11, 64))
    ones = jnp.ones((1, 11), bool)
    vs = attn.init(jax.random.key(1), x, ones)
    assert set(vs["params"]) == {"q_proj", "kv_down", "kv_norm", "kv_up",
                                 "proj"}
    table = jnp.arange(1, 5)[None]
    pos = lambda n: jnp.asarray([n], jnp.int32)

    def paged_call(m, cache, x, at):
        with jax.default_matmul_precision("highest"):
            out, upd = m.apply(
                {**vs, **cache}, x, jnp.ones(x.shape[:2], bool), decode=True,
                positions=pos(at), pages=table,
                seq_lens=jnp.asarray([x.shape[1]], jnp.int32),
                mutable=["cache"])
        return out, {"cache": upd["cache"]}

    whole, _ = paged_call(attn, {}, x, 0)
    _, cache = paged_call(attn, {}, x[:, :10], 0)
    step, _ = paged_call(attn, cache, x[:, 10:], 10)
    assert float(jnp.abs(whole[:, 10] - step[:, 0]).max()) < 1e-5
    with jax.default_matmul_precision("highest"):
        plain = attn.apply(vs, x, ones)
        turned = _mla(mla_use_nope=False).apply(vs, x, ones)
    assert float(jnp.abs(plain - whole).max()) < 1e-5
    assert float(jnp.abs(plain - turned).max()) > 0.01
    # a query latent is another tree: q_down, q_norm, q_up
    low = _mla(q_lora_rank=8).init(jax.random.key(1), x, ones)["params"]
    assert {"q_down", "q_norm", "q_up"} <= set(low) and "q_proj" not in low
    with pytest.raises(ValueError, match="q_lora_rank is None"):
        MLAConfig(None, 16, 16, 8, 16, mla_scale_q_lora=True)


@pytest.mark.parametrize("case", ["rotated_without_positions", "kv_kind",
                                  "double_layer"])
def test_what_a_latent_stack_refuses(model, case):
    _, _, module, tree = model
    ids = jnp.ones((1, 4), jnp.int32)
    if case == "rotated_without_positions":
        turned = MLAConfig(None, 16, 16, 8, 16)
        with pytest.raises(ValueError, match="rotary positions"):
            module.clone(mla=turned).apply(tree, ids)
    elif case == "kv_kind":
        kinds = (gpt.AttnKind(num_kv_heads=2), gpt.AttnKind(linear=True))
        with pytest.raises(ValueError, match="K/V-head attention's"):
            module.clone(attn_kinds=kinds).apply(tree, ids)
    else:
        with pytest.raises(ValueError, match="double layer"):
            module.clone(mlp="shortcut").apply(tree, ids)


# --- the model --------------------------------------------------------------


def test_whole_model_matches_reference(model):
    cfg, weights, module, tree = model
    assert supports_paged_decode(module)
    spec = cache_spec(module)
    assert spec.properties == {"recurrent", "latent", "experts"}
    assert (spec.sublayers, spec.full_layers, spec.state_layers) == (2, 2, 6)
    assert spec.state_gate_width == D and spec.expert_layers == 7
    ids = np.stack([p[:40] for p in prompts(2, 40, 40, seed=1)])
    with jax.default_matmul_precision("highest"):
        got = module.apply(tree, jnp.asarray(ids))
    for row, out in zip(ids, got):
        want = ref_logits(cfg, weights, row, np.arange(40))
        assert float(jnp.abs(out - want).max()) < TOL


@pytest.mark.parametrize("control,least", [
    ("channel_gate_off", 0.05), ("delta_off", 0.05), ("nope_off", 0.05),
    ("held_zero", 0.05), ("bfloat16", 0.005)])
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


def test_the_cache_tree_has_two_latent_arenas_and_six_states(model):
    _, _, module, tree = model
    cache = init_paged_cache(paged(module), tree, SLOTS, TABLE)
    names = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        names.setdefault(path[-1].key, []).append(leaf.shape)
    assert names["latent_pages"] == [(SLOTS * TABLE + 1, PT, 128)] * 2
    assert names["gdn_state"] == [(SLOTS, H // 2, D, 2 * D)] * 6
    assert names["conv_tail"] == [(3, SLOTS, 3 * H * D)] * 6
    assert "kv_rows" not in names
    spec = cache_spec(module)
    assert spec.state_bytes(SLOTS) == sum(
        l.nbytes for path, l in jax.tree_util.tree_leaves_with_path(cache)
        if path[-1].key in ("gdn_state", "conv_tail"))
    assert spec.page_bytes(PT) == 2 * PT * 128 * 4
    assert spec.token_bytes() == 2 * 24 * 4


def _decode(m, tree, cache, rows, full, steps, spoil=None):
    """``steps`` decode steps over the whole slab, the rows in ``rows``
    live: each step's cache and its rows' logits."""
    tbl = np.zeros((SLOTS, TABLE), np.int32)
    for r in rows:
        tbl[r] = 1 + r * TABLE + np.arange(TABLE)
    step_fn = jax.jit(lambda c, tok, pos, tbl, live: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=live, mutable=["cache"]))
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
    row, into another slab row, leaves the same state and tails; and a
    prompt admitted in two chunks leaves what one admit leaves."""
    _, _, module, tree = model
    m = paged(module)
    prompt = prompts(1, 27, 27, seed=6)[0]
    other = prompts(1, 29, 29, seed=7)[0]
    empty = init_paged_cache(m, tree, SLOTS, TABLE)
    lg_mono, c32 = admit(m, tree, empty, [3, 1], [prompt, other], 32)
    _, part = admit(m, tree, empty, [0], [prompt[:16]], 16)
    lg_rest, both = admit(m, tree, part, [0], [prompt[16:]], 16, base=[16])
    for a, b in zip(states(both, 0), states(c32, 3)):
        # float32 sums in another order, eight layers deep
        assert float(np.abs(a - b).max()) < 1e-4 * max(np.abs(a).max(), 1.0)
    assert any(np.abs(a).max() > 0 for a in states(both, 0))
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
    choice of a reference with one of the four faults planted."""
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
    for p, toks, n in zip(ps, out, news):
        assert len(toks) == n
        assert served_gap(cfg, weights, p, toks) < TOL
    for fault in reference.CONTROLS:
        assert max(served_gap(cfg, weights, p, toks, fault)
                   for p, toks in zip(ps, out)) > 20 * TOL, fault
    # six layers keep a state: 4 x 16 x 16 float32 and 3 x 192 of tail,
    # gated by 16 values a head; two hold a latent arena
    row = 4 * (H * D * D + 3 * 3 * H * D)
    assert tel["recurrent_layers"] == 6.0
    assert tel["recurrent_state_bytes"] == SLOTS * 6 * row
    assert tel["state_gate_width"] == float(D)
    assert (tel["cache_sublayers"], tel["full_layers"]) == (2.0, 2.0)
    assert (tel["kv_latent_width"], tel["kv_latent_row_width"]) == (24., 128.)
    assert (tel["moe_layers"], tel["moe_experts_held"]) == (7.0, 16.0)
    # the kernel moves every slab row (in each of the six layers), a step
    assert tel["state_rows_moved"] == tel["chunks"] * SLOTS
    assert 0 < tel["state_rows_live"] < tel["state_rows_moved"]
    # the latent walk's trips and the experts' three-way count ran too
    assert 0 < tel["latent_walk_trips_live"] <= tel["latent_walk_trips_run"]
    made = (tel["moe_assignments"] + tel["moe_assignments_absent"]
            + tel["moe_assignments_zero"])
    assert made == tel["live_slot_steps"] * 4 * 7 and tel[
        "moe_assignments_zero"] == 0
    assert 0 < tel["moe_assignments"] < made
    assert tel["prefix_cache_off_recurrent"] == 0.0


def test_engine_spans_carry_the_state_rows_and_the_table(model):
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
    by = lambda p, key: [s.attrs[key] for s in spans
                         if s.attrs["program"] == p]
    assert by("admit", "state_rows") == [1, 1]
    assert by("step", "state_rows") and set(by("step", "state_rows")) <= {
        1, 2}
    assert set(by("step", "width")) <= {4, 8, 16}


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


@pytest.mark.parametrize("feature,prop,kw", [
    ("slot_engine", "recurrent", {}),
    ("spec_self", "recurrent", dict(spec="self")),
    ("snapshot", "recurrent", {}),
    ("int8_pages", "latent", dict(kv_quant="int8"))])
def test_the_union_of_refusals_fires_for_this_model(model, feature, prop, kw):
    """Recurrent, latent and experts at once: what any of the three rows of
    the table refuses is refused, under the first property that does
    (tests/test_cache_spec.py runs every cell of the table)."""
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
    assert (refused.value.property, refused.value.feature) == (prop, feature)
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


def test_block_traces_grow_by_three_a_program():
    """Three kinds of layer (KDA + SwiGLU, KDA + experts, latent attention +
    experts): sizing the cache, an admission program and a step program
    cost three traces each, whatever the depth."""
    before = gpt.block_traces()
    dec = engine(build(tiny_cfg(), seed=4), slots=3)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["compiled_programs"] == 2.0
    assert gpt.block_traces() - before == 3 * 3


# --- the share: four chips of 16 experts each --------------------------------

G, K, E, W = 64, 4, 64, 32


def layer_cfg(held=None):
    return ExpertsConfig(G, K, W, 2.446, scoring_func="sigmoid",
                         norm_topk_prob=True, n_shared_experts=1, held=held)


def layer_params(seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"router": f(E, G) / 8, "router_bias": jnp.asarray(
        1e-2 * rng.standard_normal(G), jnp.float32),
        "w_gate": f(G, E, W) / 8, "w_up": f(G, E, W) / 8,
        "w_down": f(G, W, E) / 5,
        "shared_gate": {"kernel": f(E, W) / 8},
        "shared_up": {"kernel": f(E, W) / 8},
        "shared_out": {"kernel": f(W, E) / 5}}


def run_layer(cfg, params, x, first=0, count=G):
    cut = lambda a: a[first:first + count]
    share = {**params, **{n: cut(params[n])
                          for n in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        out, _ = ExpertMLP(cfg).apply(
            {"params": share}, x, jnp.ones(x.shape[:2], bool),
            mutable=["intermediates", "cache"])
    return out


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips of 16 experts each: the parts of all shares, the shared
    expert counted once (every chip computes it for its own tokens), equal
    the reference's layer over all 64, and no share alone does."""
    p = layer_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((24, E)),
                    jnp.float32)
    # the reference's own equations over ALL the experts: its weights'
    # names, one expert layer, every expert held
    w = {"w_r": p["router"][None], "b_r": p["router_bias"][None],
         "e_gate": p["w_gate"][None], "e_up": p["w_up"][None],
         "e_down": p["w_down"][None],
         "s_gate": p["shared_gate"]["kernel"][None],
         "s_up": p["shared_up"]["kernel"][None],
         "s_down": p["shared_out"]["kernel"][None]}
    hi = dict(precision="highest")
    swiglu = lambda f, a, b, c: jnp.dot(
        jax.nn.silu(jnp.dot(f, a, **hi)) * jnp.dot(f, b, **hi), c, **hi)
    shared = swiglu(x, w["s_gate"][0], w["s_up"][0], w["s_down"][0])
    s = jax.nn.sigmoid(jnp.dot(x, w["w_r"][0], **hi))
    _, chosen = jax.lax.top_k(s + w["b_r"][0], K)
    picked = (chosen[..., None] == jnp.arange(G)).any(axis=-2)
    gates = jnp.where(picked, s, 0.0)
    gates = 2.446 * gates / gates.sum(-1, keepdims=True)
    whole = shared + sum(
        gates[:, e:e + 1] * swiglu(x, p["w_gate"][e], p["w_up"][e],
                                   p["w_down"][e]) for e in range(G))
    xb = x[None]
    assert float(jnp.abs(run_layer(layer_cfg(), p, xb)[0]
                         - whole).max()) < 1e-5
    parts = [run_layer(layer_cfg((f, 16)), p, xb, f, 16)[0]
             for f in range(0, G, 16)]
    assert float(jnp.abs(sum(parts) - 3 * shared - whole).max()) < 1e-5
    assert float(jnp.abs(parts[0] - whole).max()) > 0.1
