"""LongCat-Flash's double layer through the normal path (ISSUE 41): two
latent attentions with the published scale factors, two dense SwiGLUs and a
shortcut-connected expert layer whose softmax router scores routed and
identity (zero-compute) experts and of whose routed experts this chip holds
a share (``models/gpt.py ShortcutBlock``, ``models/experts.py``).

Everything here runs a tiny preset with the published structure (hidden 128;
2 double layers; 8 heads of 16 + 8 / 16 on a latent of 16 + 8; 32 routed +
16 identity router outputs, 4 a token, 4 experts held) in float32 on the
CPU, built by the benchmark's own builder and held against the benchmark's
plain reference (``benchmark/reference/longcat_flash.py``)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from latent_rows import pad_lanes_are_zero  # noqa: E402

from benchmark.models import longcat_flash as builder  # noqa: E402
from benchmark.reference import longcat_flash as reference  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models import experts as experts_mod  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models.experts import ExpertMLP, ExpertsConfig  # noqa: E402
from kubeml_tpu.models.cache_spec import cache_spec  # noqa: E402
from kubeml_tpu.models.generation import (init_paged_cache,  # noqa: E402
                                          supports_paged_decode)
from kubeml_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from kubeml_tpu.serving.batcher import PagedBatchingDecoder  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# float32 against float32 at precision "highest": what is left is the order
# of summation (sorted grouped products against a masked sum, absorbed
# against expanded attention, a scale factor applied before or after a
# product). Logits are about 1 wide; 1e-4 is a hundredth of a bfloat16
# rounding.
TOL = 1e-4
VOCAB = 211


def tiny_cfg(**over):
    cfg = json.loads((ROOT / "benchmark/tests/data_longcat/configs/"
                      "tiny-longcat.json").read_text())
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


@pytest.mark.parametrize("share", [(0, 4), (8, 4), (28, 4), (0, 32)])
def test_whole_model_matches_reference(share):
    """Four shares of the same model, the uncut one (all 32 held) among
    them: logits, and every token's choices, as the reference has them."""
    first, held = share
    cfg, weights, module, tree = build(tiny_cfg(
        experts_held_from=first, n_routed_experts=held))
    assert module.mlp == "shortcut" and module.depth == 2
    assert module.experts.held_range == share
    assert module.mla.mla_scale_q_lora and module.mla.mla_scale_kv_lora
    spec = cache_spec(module)
    assert spec.latent is not None and supports_paged_decode(module)
    assert spec.expert_layers == 2 and spec.sublayers == 4
    assert cache_spec(gpt.GPTTiny()).sublayers == 2
    ids = prompts(1, 37, 37)[0]
    with jax.default_matmul_precision("highest"):
        got, seen = module.apply(tree, ids[None], mutable=["intermediates"])
    want = ref_logits(cfg, weights, ids, np.arange(len(ids)))
    assert float(jnp.sqrt((want ** 2).mean())) > 0.3   # not all rounding
    assert float(jnp.abs(got[0] - want).max()) < TOL
    padded = np.zeros((cfg["n_positions"],), np.int32)
    padded[:len(ids)] = ids
    routed = np.asarray(reference.routing(
        weights, jnp.asarray(padded), n_head=cfg["n_head"],
        eps=cfg["layer_norm_epsilon"]))[:, :len(ids)]
    for b in (0, 1):
        mine = np.asarray(seen["intermediates"][f"block_{b}"]["sub_0"]
                          ["experts"]["chosen"][0])
        assert (np.sort(mine, -1) == np.sort(routed[b], -1)).all()
    # routed, identity and (in a share) absent choices all occur
    assert (routed < 32).any() and (routed >= 32).any()


def test_every_part_of_the_double_layer_is_read(model):
    """Both attentions, both SwiGLUs, the experts and both scale factors
    move the logits: a program that ran one sub-layer twice, or dropped the
    shortcut, would pass a test on weights that make them equal."""
    cfg, weights, module, tree = model
    ids = prompts(1, 20, 20, seed=4)[0]
    with jax.default_matmul_precision("highest"):
        base = module.apply(tree, ids[None])
        for path in ("sub_0/attn/proj/kernel", "sub_1/attn/proj/kernel",
                     "sub_0/mlp_out/kernel", "sub_1/mlp_out/kernel",
                     "sub_0/experts/w_down", "sub_1/attn/kv_norm/scale",
                     "sub_0/attn/q_norm/scale"):
            moved = jax.tree.map(lambda a: a, tree)
            node = moved["params"]["block_1"]
            *parents, leaf = path.split("/")
            for k in parents:
                node = node[k]
            node[leaf] = node[leaf] * 1.5
            assert float(jnp.abs(module.apply(moved, ids[None]) - base)
                         .max()) > 100 * TOL, path
        # the scale factors: without them the logits are others
        import dataclasses

        plain = module.clone(mla=dataclasses.replace(
            module.mla, mla_scale_q_lora=False, mla_scale_kv_lora=False))
        assert float(jnp.abs(plain.apply(tree, ids[None]) - base).max()) \
            > 100 * TOL


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
    rows dead: every logit against the reference's full forward, through
    both read paths of the latent walk with the two scale factors.
    ``pallas`` puts every kernel of the path (interpret mode) where a TPU
    would run it; ``gather`` is the oracle of each."""
    cfg, weights, module, tree = model
    if impl == "pallas":
        force_kernels(monkeypatch)
    m = paged(module, impl)
    cache = init_paged_cache(m, tree, SLOTS, TABLE)
    seqs = [p[:n] for p, n in zip(prompts(3, 40, 40, seed=5), (5, 17, 30))]
    rows = [2, 0, 7]
    logits, cache = admit(m, tree, cache, rows, seqs, 32)
    assert pad_lanes_are_zero(cache, 4)          # two arenas a double layer
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
        assert pad_lanes_are_zero(cache, 4)
        for r, f in zip(rows, full):
            want = ref_logits(cfg, weights, f, [len(f) - 1])
            assert float(jnp.abs(logits[r, 0] - want[0]).max()) < TOL
        counts = cache["block_0"]["sub_0"]["experts"]
        held, zero = (int(counts[k]) for k in ("assignments_held",
                                               "assignments_zero"))
        assert 0 <= held and 0 <= zero and held + zero <= 3 * 4
        assert int(counts["experts_touched"]) <= min(held, 4)


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


def test_engine_serves_the_reference_tokens_and_counts_the_assignments(model):
    """More requests than rows through the engine as it stands: every served
    token is the reference's first choice, two caches a layer are sized and
    leased, and the counters say where the steps' assignments went."""
    cfg, weights, module, _ = model
    ps = prompts(6, 3, 30, seed=9)
    before = gpt.block_traces()
    with jax.default_matmul_precision("highest"):
        dec = engine(model)
        try:
            out = serve(dec, ps, 7)
            tel = dec.telemetry()
            token_bytes = dec._kv_token_bytes
        finally:
            dec.close()
    for p, toks in zip(ps, out):
        assert len(toks) == 7
        assert served_gap(cfg, weights, p, toks) < TOL
    assert tel["moe_layers"] == 2.0 and tel["moe_experts_held"] == 4.0
    assert tel["cache_sublayers"] == 4.0 and tel["kv_latent_width"] == 24.0
    assert tel["kv_latent_row_width"] == 128.0
    # four latent arenas of 24 float32 values a token
    assert token_bytes == 4 * 24 * 4
    held, zero, absent = (tel[k] for k in (
        "moe_assignments", "moe_assignments_zero", "moe_assignments_absent"))
    assert held + zero + absent == tel["live_slot_steps"] * 4 * 2 > 0
    assert held > 0 and zero > 0 and absent > 0
    assert 0 < tel["moe_experts_touched"] <= held
    # one trace a kind of layer and program: the double layer is one kind
    programs = tel["compiled_programs"]
    assert 0 < gpt.block_traces() - before <= 2 * programs


def test_block_traces_grow_by_one_a_program():
    """The double layer is one kind of layer: sizing the cache, an admission
    program and a step program trace it once each, whatever the depth (a
    dense-then-experts stack pays two each: tests/test_glm_moe_lite.py)."""
    before = gpt.block_traces()
    dec = engine(build(tiny_cfg(num_layers=3, n_layer=6), seed=4), slots=3)
    try:
        serve(dec, prompts(1, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["compiled_programs"] == 2.0 and tel["cache_sublayers"] == 6.0
    assert gpt.block_traces() - before == 3


def test_other_families_count_every_choice_as_held():
    """A layer that holds all its experts and has no identity ones: the new
    counters stay 0, the old one counts every choice, and a model without
    experts reports none held."""
    from benchmark.models import glm_moe_lite as glm_builder

    cfg = json.loads((ROOT / "benchmark/tests/data_glm/configs/"
                      "tiny-glm.json").read_text())
    cfg.update(compute_dtype="float32", param_dtype="float32", n_positions=64)
    weights = glm_builder.init_weights(cfg, 3)
    ns = {}
    exec(glm_builder.function_source(cfg), ns)
    module = ns["Model"]().build()
    assert module.experts.held is None and not module.experts.zero_expert_num
    tree = tree_of(glm_builder.program_leaves(cfg, weights))
    dec = PagedBatchingDecoder(module, tree, slots=3, page_tokens=PT,
                               chunk_steps=1, prefix_cache=False)
    try:
        serve(dec, prompts(2, 10, 10), 3)
        tel = dec.telemetry()
    finally:
        dec.close()
    layers, top_k = tel["moe_layers"], module.experts.num_experts_per_tok
    assert tel["moe_assignments"] == tel["live_slot_steps"] * top_k * layers
    assert tel["moe_assignments_zero"] == tel["moe_assignments_absent"] == 0
    assert tel["moe_experts_held"] == module.experts.n_routed_experts
    assert tel["cache_sublayers"] == module.depth
    plain = gpt.GPTTiny(vocab_size=VOCAB, max_len=64)
    dec = PagedBatchingDecoder(
        plain, plain.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32)),
        slots=3, page_tokens=PT, chunk_steps=1, prefix_cache=False)
    try:
        tel = dec.telemetry()
    finally:
        dec.close()
    assert tel["moe_experts_held"] == 0.0 and tel["cache_sublayers"] == 2.0
    from kubeml_tpu.ps import metrics

    for series, key in (
            ("kubeml_serving_moe_assignments_zero_total",
             "moe_assignments_zero"),
            ("kubeml_serving_moe_assignments_absent_total",
             "moe_assignments_absent")):
        assert metrics.SERVING_COUNTERS[series][0] == key
    for series, key in (("kubeml_serving_moe_experts_held",
                         "moe_experts_held"),
                        ("kubeml_serving_cache_sublayers",
                         "cache_sublayers")):
        assert metrics.SERVING_GAUGES[series][0] == key


# --- (c) the expert layer: shares, identity experts, empty choices ---------


G, Z, K, E, W = 32, 16, 4, 64, 24


def layer_cfg(held=None, zero=Z):
    return ExpertsConfig(G, K, W, 6.0, scoring_func="softmax",
                         norm_topk_prob=False, n_shared_experts=0,
                         zero_expert_num=zero, held=held)


def layer_params(seed=0, bias=None):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"router": f(E, G + Z), "router_bias": jnp.asarray(
        1e-3 * rng.standard_normal(G + Z) if bias is None else bias,
        jnp.float32),
        "w_gate": f(G, E, W) / 8, "w_up": f(G, E, W) / 8,
        "w_down": f(G, W, E) / 5}


def share_of(params, first, count):
    cut = lambda a: a[first:first + count]
    return {**params, "w_gate": cut(params["w_gate"]),
            "w_up": cut(params["w_up"]), "w_down": cut(params["w_down"])}


def run_layer(cfg, params, x, real=None, decode=False):
    real = jnp.ones(x.shape[:2], bool) if real is None else real
    with jax.default_matmul_precision("highest"):
        out, seen = ExpertMLP(cfg).apply(
            {"params": params}, x, real, decode=decode,
            mutable=["intermediates", "cache"])
    return out, seen


def identity_part(params, x):
    """The equation itself: (sum of the chosen identity experts' gates)
    times the token."""
    p = jax.nn.softmax(jnp.dot(x, params["router"], precision="highest"))
    _, chosen = jax.lax.top_k(p + params["router_bias"], K)
    gates = 6.0 * jnp.take_along_axis(p, chosen, -1)
    return jnp.where(chosen >= G, gates, 0.0).sum(-1, keepdims=True) * x


@pytest.mark.parametrize("kernel", [False, True])
def test_the_shares_add_up_to_the_uncut_layer(kernel, monkeypatch):
    """Eight chips of 4 experts each: the held parts of all shares plus the
    identity part, which every chip computes, counted once, equal the layer
    that holds all 32; and no share alone does."""
    if kernel:
        force_kernels(monkeypatch)
    params = layer_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 24, E)),
                    jnp.float32)
    whole, _ = run_layer(layer_cfg(), params, x, decode=kernel)
    same = identity_part(params, x)
    parts = [run_layer(layer_cfg((f, 4)), share_of(params, f, 4), x,
                       decode=kernel)[0] - same for f in range(0, G, 4)]
    assert float(jnp.abs(sum(parts) + same - whole).max()) < 1e-5
    assert float(jnp.abs(parts[0] + same - whole).max()) > 0.1
    assert float(jnp.abs(same).max()) > 0.1
    # uneven shares too: 4 + 12 + 16
    parts = [run_layer(layer_cfg((f, n)), share_of(params, f, n), x)[0] - same
             for f, n in ((0, 4), (4, 12), (16, 16))]
    assert float(jnp.abs(sum(parts) + same - whole).max()) < 1e-5


@pytest.mark.parametrize("kernel", [False, True])
def test_tokens_with_no_held_expert_and_with_identity_experts_only(
        kernel, monkeypatch):
    """A selection bias that keeps every choice off the held experts: the
    grouped product has no row at all and the layer gives the identity part
    alone; one that puts every choice on identity experts: the token times
    the sum of its four gates; and a share without identity outputs whose
    choices all lie elsewhere gives exactly nothing."""
    if kernel:
        force_kernels(monkeypatch)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 9, E)),
                    jnp.float32)
    real = jnp.arange(9)[None] < 7               # two positions are padding
    away = np.zeros(G + Z, np.float32)
    away[:4] = -10.0
    params = layer_params(3, bias=away)
    out, seen = run_layer(layer_cfg((0, 4)), share_of(params, 0, 4), x, real,
                          decode=True)
    assert (np.asarray(seen["intermediates"]["chosen"][0]) >= 4).all()
    assert int(seen["cache"]["assignments_held"]) == 0
    assert int(seen["cache"]["experts_touched"]) == 0
    assert bool(jnp.isfinite(out).all())
    want = jnp.where(real[..., None], identity_part(params, x), 0.0)
    assert float(jnp.abs(out - want).max()) < 5e-5
    only = np.zeros(G + Z, np.float32)
    only[G:] = 10.0
    params = layer_params(4, bias=only)
    out, seen = run_layer(layer_cfg((0, 4)), share_of(params, 0, 4), x, real,
                          decode=True)
    assert (np.asarray(seen["intermediates"]["chosen"][0]) >= G).all()
    assert int(seen["cache"]["assignments_zero"]) == 7 * K
    p = jax.nn.softmax(jnp.dot(x, params["router"], precision="highest"))
    gates = 6.0 * jnp.sort(p[..., G:], -1)[..., -K:].sum(-1, keepdims=True)
    # (values reach 15: float32 rounds them at 1e-6 of that)
    assert float(jnp.abs(out - jnp.where(real[..., None], gates * x, 0.0)
                         ).max()) < 5e-5
    # no identity outputs at all, every choice on another chip
    none = {**share_of(layer_params(5), 0, 4)}
    none["router"], none["router_bias"] = (none["router"][:, :G],
                                           jnp.asarray(away[:G]))
    out, seen = run_layer(layer_cfg((0, 4), zero=0), none, x, real,
                          decode=True)
    assert float(jnp.abs(out).max()) == 0.0
    assert int(seen["cache"]["assignments_zero"]) == 0


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("rows", [64 * 12, 512 * 12])
def test_grouped_product_where_most_rows_belong_to_no_group(rows, gated):
    """The share's shape: 16 groups of 0, 1 or 2 rows at the head of a
    step's 768 (an admit's 6,144) sorted assignments, the rest in no group:
    the kernel (interpret mode) against a loop over the groups."""
    sizes = [1, 0, 2, 1, 0, 0, 1, 2, 1, 1, 0, 2, 1, 0, 1, 2]
    rng = np.random.default_rng(rows + gated)
    k, n = 48, 32
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, k, n)), jnp.float32)
    up = jnp.asarray(rng.standard_normal((16, k, n)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = grouped_matmul(lhs, w, jnp.asarray(sizes, jnp.int32),
                             up if gated else None, kernel=True)
        at = 0
        for g, size in enumerate(sizes):
            x = lhs[at:at + size]
            want = x @ w[g]
            if gated:
                want = jax.nn.silu(want) * (x @ up[g])
            assert not size or float(
                jnp.abs(got[at:at + size] - want).max()) < 1e-4
            at += size
    assert at == sum(sizes) == 15 and got.shape == (rows, n)


def test_the_softmax_router_does_not_normalise_its_gates():
    from kubeml_tpu.models.experts import route

    p = jax.nn.softmax(jnp.asarray([[2.0, 1.0, 0.0, -1.0]]))
    bias = jnp.asarray([0.0, 0.0, 0.0, 10.0])      # selection only
    chosen, gates = route(p, bias, 2, 6.0, normalise=False)
    by = dict(zip(np.asarray(chosen)[0].tolist(),
                  np.asarray(gates)[0].tolist()))
    assert set(by) == {0, 3}
    assert by[0] == pytest.approx(6.0 * float(p[0, 0]))
    assert by[3] == pytest.approx(6.0 * float(p[0, 3]))
    with pytest.raises(ValueError, match="scoring_func"):
        ExpertsConfig(8, 2, 4, scoring_func="tanh")
    with pytest.raises(ValueError, match="no range"):
        ExpertsConfig(8, 2, 4, held=(6, 4))


# --- (d) precision -----------------------------------------------------------


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
    # bfloat16 keeps 8 bits: products of order 1 are off by 2^-9 each, and
    # four sub-layers of them add up to a hundredth of a logit; fp8 e4m3
    # keeps 4 bits and moves the logits several times that. By the root
    # mean square: under a peaked router (the builder's) one flipped 4th
    # choice moves a single logit by 0.2 in either precision
    off = lambda a: float(jnp.sqrt(((a - want) ** 2).mean()))
    assert off(got) < 0.03 < 0.06 < off(low)
    assert off(low) > 3 * off(got)


def test_the_held_controls_touch_the_held_part_alone(model):
    """``held_zero`` is the float32 reference of the same weights with the
    held experts' down-projections at 0; ``held_fp8_e4m3`` moves the logits,
    by less than fp8 everywhere does; and leaving the held part out moves
    them by more than either: what the chip's ``correct`` is held to see
    (benchmark/probe_control.py)."""
    cfg, weights, _, _ = model
    ids = prompts(1, 40, 40, seed=3)[0]
    at = np.arange(len(ids))
    want = ref_logits(cfg, weights, ids, at)
    none = ref_logits(cfg, {**weights, "e_down": 0 * weights["e_down"]},
                      ids, at)
    off = lambda a: float(jnp.sqrt(((a - want) ** 2).mean()))
    left_out = ref_logits(cfg, weights, ids, at, precision="held_zero")
    assert float(jnp.abs(left_out - none).max()) < 1e-5   # another program
    low = off(ref_logits(cfg, weights, ids, at, precision="fp8_e4m3"))
    held_low = off(ref_logits(cfg, weights, ids, at,
                              precision="held_fp8_e4m3"))
    assert 0.0 < held_low < low < off(left_out)


# --- (e) what is refused by name stays refused -------------------------------


# (what the engines refuse for the model's caches: tests/test_cache_spec.py)


@pytest.mark.parametrize("case", ["exit_layer", "dense_layers", "hc",
                                  "bare_block"])
def test_refusals_are_named(model, case):
    _, _, module, tree = model
    ids = jnp.ones((1, 4), jnp.int32)
    if case == "exit_layer":
        with pytest.raises(ValueError, match="expert models"):
            module.apply(tree, ids, exit_layer=1)
    elif case == "dense_layers":
        with pytest.raises(ValueError, match="no other kind"):
            module.clone(dense_layers=1).apply(tree, ids)
    elif case == "hc":
        with pytest.raises(ValueError, match="hyper-connections"):
            module.clone(hc_mult=2).init(jax.random.key(0), ids)
    else:
        with pytest.raises(ValueError, match="ShortcutBlock"):
            gpt.ShortcutBlock(2, mlp="swiglu").init(
                jax.random.key(0), jnp.ones((1, 4, 16)),
                jnp.ones((1, 4), bool))
