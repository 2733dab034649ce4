"""Xing4.0-29B-A4B's block through the normal path (ISSUE 37): a residual
path of four streams mixed by doubly-stochastic maps (hyper-connections,
``ops/hyper_connection.py``) around latent attention with YaRN rotary and
routed experts.

Everything here runs a tiny preset with the published structure (hidden 128;
four streams, 20 Sinkhorn iterations; two dense layers and three expert
layers; 8 experts, 2 a token; latent 16 + rope 8; 4 heads; YaRN) in float32
on the CPU, built by the benchmark's own builder and held against the
benchmark's plain reference (``benchmark/reference/xing4.py``)."""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from latent_rows import pad_lanes_are_zero  # noqa: E402

from benchmark.models import falcon_h1 as falcon_builder  # noqa: E402
from benchmark.models import glm_moe_lite as glm_builder  # noqa: E402
from benchmark.models import xing4 as builder  # noqa: E402
from benchmark.reference import xing4 as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models import experts as experts_mod  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.generation import (init_paged_cache,  # noqa: E402
                                          supports_paged_decode)
from kubeml_tpu.models.mla import MLAConfig  # noqa: E402
from kubeml_tpu.ops import hyper_connection as hc  # noqa: E402
from kubeml_tpu.ops.attention import dot_product_attention  # noqa: E402
from kubeml_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from kubeml_tpu.ops.rotary import YarnScaling, rope_frequencies  # noqa: E402
from kubeml_tpu.serving.batcher import PagedBatchingDecoder  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# float32 against float32 at precision "highest": what is left is the order
# of summation (the maps' projection scaled after the product and not
# before, a reciprocal times the entries where the reference divides, sorted
# grouped products against a masked sum, absorbed against expanded
# attention). Logits are about 1 wide; 1e-4 is twenty times the largest gap
# seen (5e-6) and a hundredth of a bfloat16 rounding.
TOL = 1e-4
VOCAB = 211


def tiny_cfg(**over):
    cfg = json.loads((ROOT / "benchmark/tests/data_xing/configs/"
                      "tiny-xing.json").read_text())
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


def build(cfg, seed=3, family=builder):
    weights = family.init_weights(cfg, seed)
    ns = {}
    exec(family.function_source(cfg), ns)
    return (cfg, weights, ns["Model"]().build(),
            tree_of(family.program_leaves(cfg, weights)))


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
    """Put the Pallas kernels (interpret mode) where a TPU would run them:
    the experts' grouped products and both halves of the residual path."""
    monkeypatch.setattr(
        experts_mod, "grouped_matmul",
        lambda *a, kernel, **kw: grouped_matmul(*a, kernel=True, **kw))
    monkeypatch.setattr(
        gpt, "hc_pre", lambda *a, kernel=None: hc.hc_pre(*a, kernel=True))
    monkeypatch.setattr(
        gpt, "hc_post", lambda *a, kernel=None: hc.hc_post(*a, kernel=True))


# --- (a) the whole-sequence forward against the reference -----------------


def test_whole_model_matches_reference(model):
    cfg, weights, module, tree = model
    assert module.hc_mult == 4 and module.dense_layers == 2
    spec = cache_spec(module)
    assert spec.latent is not None and spec.expert_layers == 3
    assert spec.residual_sublayers == 10 and supports_paged_decode(module)
    assert cache_spec(gpt.GPTTiny()).residual_sublayers == 0
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
    for layer in (2, 3, 4):
        mine = np.asarray(seen["intermediates"][f"block_{layer}"]["experts"]
                          ["chosen"][0])
        assert (np.sort(mine, -1) == np.sort(routed[layer - 2], -1)).all()


def test_every_stream_and_every_map_is_read(model):
    """A program that kept one stream, or made the maps once, would pass a
    test on weights that make them constant: these do not. Each sub-layer's
    bias and alpha move the logits, and the streams differ at the end."""
    cfg, weights, module, tree = model
    ids = prompts(1, 20, 20, seed=4)[0]
    with jax.default_matmul_precision("highest"):
        base = module.apply(tree, ids[None])
        for name in ("hc1_bias", "hc2_bias", "hc1_alpha", "hc2_phi"):
            moved = jax.tree.map(lambda a: a, tree)
            leaf = moved["params"]["block_3"][name]
            moved["params"]["block_3"][name] = leaf + 0.3 * jnp.sign(leaf)
            assert float(jnp.abs(module.apply(moved, ids[None]) - base)
                         .max()) > 100 * TOL, name


# --- (b) admission, then decode steps, through the paged path --------------


PT, SLOTS, TABLE = 8, 8, 8


def paged(module, impl="pallas"):
    return module.clone(page_tokens=PT, kv_pages=SLOTS * TABLE + 1,
                        paged_attn=impl)


def table(rows, n=None):
    tbl = np.zeros((len(rows) if n is None else n, TABLE), np.int32)
    for i, r in enumerate(rows):
        tbl[i if n is None else r] = 1 + r * TABLE + np.arange(TABLE)
    return tbl


def admit(m, tree, cache, rows, seqs, bucket):
    n = len(seqs)
    ids = np.zeros((n, bucket), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        logits, upd = jax.jit(lambda *a: m.apply(
            {**tree, "cache": a[0]}, a[1], decode=True, positions=a[2],
            pages=a[3], seq_lens=a[4], mutable=["cache"]))(
            cache, jnp.asarray(ids), jnp.zeros((n,), jnp.int32),
            jnp.asarray(table(rows)),
            jnp.asarray([len(s) for s in seqs], jnp.int32))
    return logits, upd["cache"]


@pytest.mark.parametrize("impl", ["pallas", "gather"])
def test_prefill_then_decode_logits_match_reference(model, impl, monkeypatch):
    """Rows of different lengths in one padded admit (expanded attention),
    then decode steps (absorbed attention) over the whole slab with most
    rows dead: every logit against the reference's full forward. ``pallas``
    puts every kernel of the path (interpret mode) where a TPU would run it
    (the latent page walk, the experts' grouped products, ``hc_pre`` and
    ``hc_post``); ``gather`` is the oracle of each."""
    cfg, weights, module, tree = model
    if impl == "pallas":
        force_kernels(monkeypatch)
    m = paged(module, impl)
    cache = init_paged_cache(m, tree, SLOTS, TABLE)
    seqs = [p[:n] for p, n in zip(prompts(3, 40, 40, seed=5), (5, 17, 30))]
    rows = [2, 0, 7]
    logits, cache = admit(m, tree, cache, rows, seqs, 32)
    depth = cfg["num_hidden_layers"]
    assert pad_lanes_are_zero(cache, depth)
    full = [list(s) for s in seqs]
    for i, s in enumerate(seqs):
        want = ref_logits(cfg, weights, s, np.arange(len(s)))
        assert float(jnp.abs(logits[i, :len(s)] - want).max()) < TOL
    step_fn = jax.jit(lambda c, tok, pos, tbl, live: m.apply(
        {**tree, "cache": c}, tok[:, None], decode=True, positions=pos,
        pages=tbl, seq_lens=live, mutable=["cache"]))
    tbl = table(rows, SLOTS)
    for step in range(4):
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
        assert pad_lanes_are_zero(cache, depth)
        for r, f in zip(rows, full):
            want = ref_logits(cfg, weights, f, [len(f) - 1])
            assert float(jnp.abs(logits[r, 0] - want[0]).max()) < TOL


def engine(model, **kw):
    _, _, module, tree = model
    args = dict(slots=4, page_tokens=PT, chunk_steps=1, bucket_min=16,
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


def test_engine_serves_the_reference_tokens_and_counts_the_mixing(model):
    """More requests than rows through the engine as it stands (no branch on
    the stream's width): every served token is the reference's first choice,
    and the telemetry says what the residual path did."""
    cfg, weights, _, _ = model
    ps = prompts(6, 3, 30, seed=9)
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            out = serve(dec, ps, 7)
            tel = dec.telemetry()
        finally:
            dec.close()
    for p, toks in zip(ps, out):
        assert len(toks) == 7
        assert served_gap(cfg, weights, p, toks) < TOL
    assert tel["residual_streams"] == 4.0 and tel["moe_layers"] == 3.0
    # ten sub-layers mix every position a program computes: an admission's
    # whole bucket, a step's every row
    admitted = tel["prefill_tokens"] + tel["prefill_pad_tokens"]
    assert tel["hc_positions_admit"] == 10 * admitted > 0
    assert tel["hc_positions_step"] == 10 * 4 * tel["device_steps"] > 0
    assert tel["hc_positions"] == (tel["hc_positions_admit"]
                                   + tel["hc_positions_step"])


def test_a_single_stream_reports_one_and_nothing_mixed():
    plain = gpt.GPTTiny(vocab_size=VOCAB, max_len=64)
    dec = PagedBatchingDecoder(
        plain, plain.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32)),
        slots=3, page_tokens=PT, chunk_steps=1, prefix_cache=False)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["residual_streams"] == 1.0 and tel["hc_positions"] == 0.0
    from kubeml_tpu.ps import metrics

    assert metrics.SERVING_COUNTERS["kubeml_serving_hc_positions_total"][0] \
        == "hc_positions"
    assert metrics.SERVING_GAUGES["kubeml_serving_residual_streams"][0] \
        == "residual_streams"


# --- (c) the maps ---------------------------------------------------------


def hc_params(n, width, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    c = 2 * n + n * n
    return {"phi": jnp.asarray(scale * rng.standard_normal((n * width, c))
                               / np.sqrt(n * width), jnp.float32),
            "alpha": jnp.asarray([0.9, 1.1, 1.0], jnp.float32),
            "bias": jnp.asarray(0.5 * rng.standard_normal((c,)), jnp.float32)}


def test_the_maps_lie_on_their_manifolds():
    """With the configuration's initialisation (a projection of order 1):
    M's rows and columns sum to 1 within 1e-3 after 20 iterations, pre lies
    in (0, 1) and post in (0, 2), and they differ from token to token by
    the order of their own size."""
    cfg = hc.HCConfig(mult=4)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 24, 512)),
                    jnp.float32)
    p = hc_params(4, 128, 2)
    u, post, m = hc.hc_pre(x, p, cfg, kernel=False)
    xf = x.reshape(2, 24, 4, 128)
    rs = jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.norm_eps)
    pre = hc.maps_of(jnp.dot(x, p["phi"], precision="highest") * rs,
                     p["alpha"], p["bias"], cfg)[0]
    assert float(jnp.abs(m.sum(-1) - 1).max()) < 1e-3
    assert float(jnp.abs(m.sum(-2) - 1).max()) < 1e-3
    assert float(m.min()) > 0
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    assert float(jnp.abs(u - jnp.einsum("bln,blne->ble", pre, xf)).max()) < 1e-5
    for name, t in (("pre", pre), ("post", post), ("M", m)):
        spread = float(t.std(axis=(0, 1)).mean())
        assert spread > 0.2 * float(t.mean()), (name, spread)


def test_an_input_beyond_the_clamp_stays_finite():
    """A projection of 1e4 a column: Z is clipped to +-30 before the
    exponential, the maps stay finite and in their ranges, and the mixing
    still sums the streams with weights that add to about 1 a column."""
    cfg = hc.HCConfig(mult=4)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 16, 512)),
                    jnp.float32)
    p = hc_params(4, 128, 4, scale=1e4)
    for kernel in (False, True):
        u, post, m = hc.hc_pre(x, p, cfg, kernel=kernel)
        out = hc.hc_post(x, u, post, m, kernel=kernel)
        for t in (u, post, m, out):
            assert bool(jnp.isfinite(t).all())
        assert float(m.min()) >= 0 and float(m.max()) <= 1 + 1e-6
        assert float(jnp.abs(m.sum(-2) - 1).max()) < 1e-3   # columns: last
    assert float(jnp.exp(jnp.float32(60.0))) < float("inf")   # 30 - (-30)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("positions", [(2, 32), (8, 1), (1, 200)])
def test_hc_kernels_equal_the_equations(dtype, positions):
    """``hc_pre`` and ``hc_post`` as Pallas kernels (interpret mode) against
    the same equations in ``jax.numpy``, float32 and bfloat16 streams; the
    maps are float32 in both, so they agree to float32 rounding whatever
    type the streams are held in."""
    cfg = hc.HCConfig(mult=4)
    rng = np.random.default_rng(sum(positions))
    x = jnp.asarray(1.5 * rng.standard_normal(positions + (512,)), dtype)
    y = jnp.asarray(rng.standard_normal(positions + (128,)), dtype)
    p = hc_params(4, 128, 7)
    u0, post0, m0 = hc.hc_pre(x, p, cfg, kernel=False)
    u1, post1, m1 = hc.hc_pre(x, p, cfg, kernel=True)
    assert u1.dtype == x.dtype and m1.dtype == jnp.float32
    assert float(jnp.abs(post0 - post1).max()) < 2e-5
    assert float(jnp.abs(m0 - m1).max()) < 2e-5
    wide = lambda t: t.astype(jnp.float32)
    # one rounding of the stream's type apart at most
    tol = 2e-5 if dtype == "float32" else 2 ** -6
    assert float(jnp.abs(wide(u0) - wide(u1)).max()) < tol
    o0 = hc.hc_post(x, y, post0, m0, kernel=False)
    o1 = hc.hc_post(x, y, post0, m0, kernel=True)
    assert o1.shape == x.shape and o1.dtype == x.dtype
    assert float(jnp.abs(wide(o0) - wide(o1)).max()) < 4 * tol


def test_a_position_count_with_no_tile_takes_the_equations():
    cfg = hc.HCConfig(mult=4)
    x = jnp.ones((1, 7, 512), jnp.float32)
    assert hc._tile(7, 128) == 0 and hc._tile(96, 128) == 32
    a = hc.hc_pre(x, hc_params(4, 128, 1), cfg, kernel=True)
    b = hc.hc_pre(x, hc_params(4, 128, 1), cfg, kernel=False)
    assert all(bool((s == t).all()) for s, t in zip(a, b))


# --- (d) the precision the maps are made in --------------------------------


def maps_in_bfloat16(x, params, cfg, kernel=None):
    """``hc_pre`` as a program would write it that made the maps in the
    streams' bfloat16: the norm, the projection, the sigmoids and the
    Sinkhorn loop all in that type."""
    n = cfg.mult
    lead, width = x.shape[:-1], x.shape[-1] // n
    xb = x.astype(jnp.bfloat16)
    rs = jax.lax.rsqrt((xb * xb).mean(axis=-1, keepdims=True)
                       + jnp.bfloat16(cfg.norm_eps))
    proj = jnp.dot(xb, params["phi"].astype(jnp.bfloat16)) * rs
    a, b = (params[k].astype(jnp.bfloat16) for k in ("alpha", "bias"))
    pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    post = 2 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    z = jnp.clip(a[2] * proj[..., 2 * n:] + b[2 * n:], -cfg.clamp, cfg.clamp)
    m = hc.sinkhorn(jnp.exp(z - z.max(-1, keepdims=True)).reshape(
        lead + (n, n)), cfg.sinkhorn_iters, jnp.bfloat16(cfg.eps))
    u = jnp.einsum("...n,...ne->...e", pre, xb.reshape(lead + (n, width)))
    return (u.astype(x.dtype), post.astype(jnp.float32),
            m.astype(jnp.float32))


def test_maps_made_in_bfloat16_fail_the_maps_tolerance():
    """The maps are made in float32 FROM the bfloat16 streams, so on the
    same stream values they equal the float32 equations to float32 rounding
    (1e-6 seen). Made in bfloat16, the projection over 512 values carries
    2^-9 a product and the 40 normalisations 2^-9 each: M is off by 2e-3 to
    1e-2. 1e-4 lies a hundred times over the one and twenty under the
    other."""
    cfg = hc.HCConfig(mult=4)
    x = jnp.asarray(1.5 * np.random.default_rng(5).standard_normal(
        (4, 32, 512)), jnp.bfloat16)
    p = hc_params(4, 128, 6)
    _, post, m = hc.hc_pre(x.astype(jnp.float32), p, cfg, kernel=False)
    for kernel in (False, True):
        _, post1, m1 = hc.hc_pre(x, p, cfg, kernel=kernel)
        assert float(jnp.abs(m1 - m).max()) < 1e-4
        assert float(jnp.abs(post1 - post).max()) < 1e-4
    _, post2, m2 = maps_in_bfloat16(x, p, cfg)
    assert float(jnp.abs(m2 - m).max()) > 1e-3
    assert float(jnp.abs(post2 - post).max()) > 1e-3


def test_lower_precision_departs_and_bfloat16_stays(model):
    """bfloat16 compute on the same weights stays inside a stated width of
    the float32 reference; the control the limits are set against (every
    product's operands in fp8 e4m3) does not. The width is of the MEDIAN
    position's widest logit: with 8 experts and 2 a token a position in
    forty flips its last choice under bfloat16 and moves by 0.6-1.4, in the
    sound program and in the reference rounded to bfloat16 alike, which is
    why the cell is held to the mean gap and not to the widest
    (limits/xing4.0-29b-a4b.extract.json). At this level a program whose
    residual maps are made in bfloat16 reads as the sound one does (medians
    0.045-0.099 against 0.050-0.095 on three prompts): the streams' own
    rounding hides it, and the maps' tolerance above is what fails it."""
    cfg, weights, _, tree = model
    ids = prompts(1, 40, 40, seed=2)[0]
    at = np.arange(len(ids))
    want = ref_logits(cfg, weights, ids, at)
    low = ref_logits(cfg, weights, ids, at, precision="fp8_e4m3")
    _, _, half, _ = build(tiny_cfg(compute_dtype="bfloat16"))
    got = half.apply(tree, ids[None])[0]
    typical = lambda a: float(jnp.median(jnp.abs(a - want).max(axis=-1)))
    # bfloat16 keeps 8 bits: products of order 1 are off by 2^-9 each and
    # five layers of them on streams of root mean square 3 add up to a few
    # hundredths of a logit (0.05-0.095 seen on three prompts); fp8 e4m3
    # keeps 4 bits and moves the typical position by 0.5-0.9
    assert typical(got) < 0.2
    assert typical(low) > 0.2
    assert typical(low) > 3 * typical(got)


# --- (e) YaRN --------------------------------------------------------------


XING_YARN = YarnScaling(factor=64, original_max_position_embeddings=4096,
                        beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
# by hand for the published keys (dr 64, theta 1e4): inv_i = 1e4^(-i/32);
# the pair that turns 32 times in 4,096 positions is 10.47 -> low 10, the
# one that turns once 22.51 -> high 23; ramp_i = clip((i - 10) / 13, 0, 1);
# the frequency inv_i (1 - ramp_i) + inv_i ramp_i / 64
XING_FREQUENCIES = [
    1.000000e+00, 7.498942e-01, 5.623413e-01, 4.216965e-01, 3.162278e-01,
    2.371374e-01, 1.778279e-01, 1.333521e-01, 1.000000e-01, 7.498942e-02,
    5.623413e-02, 3.897652e-02, 2.683375e-02, 1.832684e-02, 1.239666e-02,
    8.286425e-03, 5.456731e-03, 3.524142e-03, 2.216923e-03, 1.343144e-03,
    7.677645e-04, 3.961790e-04, 1.624390e-04, 2.083627e-05, 1.562500e-05,
    1.171710e-05, 8.786583e-06, 6.589008e-06, 4.941059e-06, 3.705271e-06,
    2.778562e-06, 2.083627e-06]


def test_yarn_frequencies_and_scale_for_the_published_keys():
    got = rope_frequencies(64, 10000.0, XING_YARN)
    assert got.shape == (32,) and got.dtype == np.float32
    assert np.allclose(got, XING_FREQUENCIES, rtol=2e-6, atol=0)
    # the first eleven pairs are plain rotary, the last nine plain / 64
    plain = rope_frequencies(64, 10000.0)
    assert np.array_equal(got[:11], plain[:11])
    assert np.allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    # (0.1 ln 64 + 1)^2 / sqrt(128 + 64); the tables are not scaled
    mla = MLAConfig(768, 512, 128, 64, 128, rope_scaling=XING_YARN)
    assert mla.softmax_scale == pytest.approx(0.14467962580, rel=1e-9)
    assert XING_YARN.table_mscale == 1.0
    assert MLAConfig(768, 512, 192, 64, 256).softmax_scale == 1 / 16
    # and the reference's own, from the scalars it is handed
    freq, m, tbl = reference.yarn_frequencies(
        64, jnp.float32(1e4), jnp.asarray([64, 32, 1, 1, 1, 4096.0]))
    assert np.allclose(np.asarray(freq), XING_FREQUENCIES, rtol=1e-5)
    assert float(m) ** 2 / np.sqrt(192) == pytest.approx(0.1446796, rel=1e-5)
    assert float(tbl) == 1.0


def test_a_softmax_scale_of_its_own_takes_the_plain_path():
    q = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 8)),
                    jnp.float32)
    same = dot_product_attention(q, q, q, causal=True, scale=8 ** -0.5)
    assert float(jnp.abs(same - dot_product_attention(
        q, q, q, causal=True)).max()) < 1e-6
    other = dot_product_attention(q, q, q, causal=True, scale=1.0)
    assert float(jnp.abs(other - same).max()) > 1e-3


# --- (f) one trace a kind of layer -----------------------------------------


@pytest.mark.parametrize("depth", [5, 7])
def test_block_traces_grow_by_two_a_program(depth):
    """Two dense layers and then expert layers are two kinds, whatever the
    depth and whatever the residual path: sizing the cache, an admission
    program and a step program cost two traces each (a slab of its own
    size a case: equal shapes would find the other case's traces and cost
    none)."""
    before = gpt.block_traces()
    dec = engine(build(tiny_cfg(num_hidden_layers=depth, n_layer=depth),
                       seed=4), slots=depth - 2)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["compiled_programs"] == 2.0
    assert gpt.block_traces() - before == 2 * 3


# --- (g) a single stream is the program it was ------------------------------


def _equations(jaxpr, out):
    from jax._src import core as jcore

    for e in jaxpr.eqns:
        out.append(f"{e.primitive.name}:"
                   + ",".join(str(v.aval) for v in e.outvars))
        for sub in jcore.jaxprs_in_params(e.params):
            _equations(sub, out)


def program_digest(module, tree):
    """(equations, digest of their primitives and result types, kernels'
    bodies included) of a one-row admission of 16 positions and of a
    four-row decode step."""
    recurrent = module.ssm is not None
    m = module.clone(page_tokens=8, kv_pages=4 * 8 + 1, paged_attn="pallas",
                     **({"state_rows": 4} if recurrent else {}))
    cache = init_paged_cache(m, tree, 4, 8)
    out = {}
    for name, rows, L in (("admit", 1, 16), ("step", 4, 1)):
        # a recurrent model's program says which state rows it writes
        kw = {"rows": jnp.arange(rows)} if recurrent else {}
        jaxpr = jax.make_jaxpr(lambda c, ids, pos, t, sl: m.apply(
            {**tree, "cache": c}, ids, decode=True, positions=pos, pages=t,
            seq_lens=sl, mutable=["cache"], **kw))(
            cache, jnp.ones((rows, L), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows, 8), jnp.int32),
            jnp.ones((rows,), jnp.int32))
        eqns = []
        _equations(jaxpr.jaxpr, eqns)
        out[name] = (len(eqns), hashlib.sha256(
            "\n".join(eqns).encode()).hexdigest()[:16])
    return out


# made by this function on the parent commit (078c30b, PR 36), where the
# block had one residual path: jax 0.9.0 on the CPU. The two K/V families'
# "step" was made again on PR 38's tree, which moved it on purpose: a decode
# step's page walk takes the kernel's decode body (ops/paged_attention.py;
# 902 equations before for gpt2, 1295 for falcon), and their "admit" on PR
# 40's, whose admits walk a chunk of pages a program in the tile body (900
# equations before for gpt2, 1297 for falcon: the head loop is there twice,
# masked and clear). Both of glm's were made again on PR 41's tree: every
# expert layer masks its assignments by the range of the experts it holds
# and leaves three counts in the cache, whatever it holds (models/experts
# .py has one path; 843 and 1023 equations before), and again on PR 43's:
# the latent arena's rows are whole 128-lane rows, so each of the three
# layers pads the row it writes and a step the query it walks with (a
# ``jnp.pad`` is three equations: 865 and 1045 before); everything else of
# the two are PR 36's, equation for equation. PR 43 left the two K/V
# families' programs as they were. glm's "step" was made again on PR 49's
# tree, which moved it on purpose: the latent walk's kernel is a loop over a
# row's live spans that copies its pages itself (ops/mla_attention.py; 1063
# equations before, 236 more a layer: a full span's copies are written
# out); its "admit", which attends expanded and never calls the walk, is PR
# 43's still
PARENT_PROGRAMS = {
    "glm": {"admit": (874, "1c210d6a9a19ffc6"),
            "step": (1771, "0127c67bc0f42ee7")},
    "gpt2": {"admit": (1248, "e5faa9da9aaadbb1"),
             "step": (690, "66df1c13cb8cce27")},
    "falcon": {"admit": (1645, "100bfbb328f0ac69"),
               "step": (1083, "0667e1250f4b39dc")},
}


@pytest.mark.parametrize("family", ["glm", "gpt2", "falcon"])
def test_a_single_stream_keeps_its_equations(family):
    """``hc_mult=0`` (and ``rope_scaling=None``): the small decode programs
    of GLM-4.7-Flash's block and of GPT-2's trace to the equations they had
    before the block learnt a second residual path (Falcon-H1's too)."""
    if family in ("glm", "falcon"):
        name, family_builder = {
            "glm": ("data_glm/configs/tiny-glm.json", glm_builder),
            "falcon": ("data_falcon/configs/tiny-falcon.json",
                       falcon_builder)}[family]
        cfg = json.loads((ROOT / "benchmark/tests" / name).read_text())
        cfg.update(compute_dtype="float32", param_dtype="float32")
        if family == "glm":
            cfg.update(n_positions=64)
        _, _, module, tree = build(cfg, family=family_builder)
    else:
        module = gpt.GPTTiny(vocab_size=VOCAB, max_len=64)
        tree = module.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32))
    assert module.hc_mult == 0
    assert program_digest(module, tree) == PARENT_PROGRAMS[family]


def test_four_streams_are_another_program(model):
    _, _, module, tree = model
    got = program_digest(module, tree)
    assert got["step"][0] > 2000    # 20 Sinkhorn iterations, 10 sub-layers


# --- (h) what is refused by name stays refused -------------------------------


# (what the engines refuse for the model's caches: tests/test_cache_spec.py)


@pytest.mark.parametrize("case", ["exit_layer", "dense_cache", "mixer",
                                  "moe_every", "flash_scale"])
def test_refusals_are_still_named(model, case):
    _, _, module, tree = model
    ids = jnp.ones((1, 4), jnp.int32)
    if case == "exit_layer":
        with pytest.raises(ValueError, match="expert models"):
            module.apply(tree, ids, exit_layer=1)
    elif case == "dense_cache":
        with pytest.raises(ValueError, match="paged arena only"):
            module.apply(tree, ids, decode=True, mutable=["cache"])
    elif case == "mixer":
        from kubeml_tpu.models.mamba2 import SSMConfig

        mixed = gpt.CausalTransformer(
            vocab_size=11, max_len=16, embed_dim=32, depth=1, num_heads=2,
            hc_mult=2, ssm=SSMConfig(d_ssm=16, num_heads=2, head_dim=8,
                                     n_groups=1, d_state=4))
        with pytest.raises(ValueError, match="hyper-connections"):
            mixed.init(jax.random.key(0), ids)
    elif case == "moe_every":
        old = gpt.CausalTransformer(vocab_size=11, max_len=16, embed_dim=32,
                                    depth=2, num_heads=2, moe_every=2,
                                    hc_mult=2)
        with pytest.raises(ValueError, match="hyper-connections"):
            old.init(jax.random.key(0), ids)
    else:
        q = jnp.ones((1, 4, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="1/sqrt"):
            dot_product_attention(q, q, q, causal=True, impl="pallas",
                                  scale=0.5)


def test_a_fresh_model_starts_near_the_single_stream():
    """``init`` (not the benchmark's weights): pre 1/n, post 1, M within a
    few hundredths of the identity, so the four streams start as copies of
    a plain residual path and training moves them apart."""
    m = gpt.CausalTransformer(vocab_size=VOCAB, max_len=32, embed_dim=128,
                              depth=2, num_heads=4, norm="rmsnorm",
                              hc_mult=4)
    ids = jnp.asarray(prompts(1, 12, 12)[0][None])
    tree = m.init(jax.random.key(1), ids)
    import flax.linen as nn

    params = nn.meta.unbox(tree["params"])
    block = params["block_0"]
    assert block["hc1_phi"].shape == (512, 24)
    x = jnp.tile(params["token_embed"]["embedding"][ids], (1, 1, 4))
    p = {k: block[f"hc1_{k}"] for k in ("phi", "alpha", "bias")}
    u, post, mix = hc.hc_pre(x, p, hc.HCConfig(mult=4), kernel=False)
    assert float(jnp.abs(post - 1).max()) < 0.05
    assert float(jnp.abs(mix - jnp.eye(4)).max()) < 0.1
    assert bool(jnp.isfinite(m.apply(tree, ids)).all())
