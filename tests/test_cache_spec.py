"""One description of a model's caches, one table of what a cache property
allows (ISSUE 47).

``models/cache_spec.py cache_spec`` is asked once a decoder and
``serving/batcher.py CACHE_FEATURES`` is read once a decoder; this file holds
both to the eight tiny configurations of the benchmark's own tests (read, not
edited; ``data/configs/tiny.json`` stands for both GPT-2 sizes):

* ``TABLE`` spells out every cell as it stood before the table existed
  (fourteen raise sites and one warning): the record that none changed;
* the arenas ``init_paged_cache`` builds weigh what the description says;
* ``telemetry()`` returns the parent's keys and the parent's static values.
"""

import functools
import json
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.models import (falcon_h1, glm_moe_lite, gpt2,  # noqa: E402
                              kimi_linear, longcat_flash, mimo_v2,
                              olmo_hybrid, xing4)
from kubeml_tpu.api.errors import KubeMLError  # noqa: E402
from kubeml_tpu.models import gpt  # noqa: E402
from kubeml_tpu.models.cache_spec import PROPERTIES, cache_spec  # noqa: E402
from kubeml_tpu.models.generation import init_paged_cache  # noqa: E402
from kubeml_tpu.serving import batcher, kvsnap  # noqa: E402
from kubeml_tpu.serving.batcher import (BatchingDecoder,  # noqa: E402
                                        CacheFeatureUnsupported,
                                        PagedBatchingDecoder)

ROOT = Path(__file__).resolve().parent.parent

FAMILIES = {
    "gpt2": ("data/configs/tiny.json", gpt2),
    "falcon": ("data_falcon/configs/tiny-falcon.json", falcon_h1),
    "glm": ("data_glm/configs/tiny-glm.json", glm_moe_lite),
    "xing": ("data_xing/configs/tiny-xing.json", xing4),
    "longcat": ("data_longcat/configs/tiny-longcat.json", longcat_flash),
    "mimo": ("data_mimo/configs/tiny-mimo.json", mimo_v2),
    "olmo": ("data_olmo/configs/tiny-olmo.json", olmo_hybrid),
    "kimi": ("data_kimi/configs/tiny-kimi.json", kimi_linear),
}
HAS = {
    "gpt2": set(), "falcon": {"recurrent"}, "glm": {"latent", "experts"},
    "xing": {"latent", "experts"}, "longcat": {"latent", "experts"},
    "mimo": {"window", "experts"}, "olmo": {"recurrent"},
    # the first model with three properties at once (PR 51)
    "kimi": {"recurrent", "latent", "experts"},
}

# today's cells, every one; a pair that is not here is served
FEATURES = ("slot_engine", "prefix_sharing", "chunked_prefill", "int8_pages",
            "spec_self", "spec_draft", "snapshot")
TABLE = {
    ("recurrent", "slot_engine"): "refuse",
    ("recurrent", "prefix_sharing"): "off",
    ("recurrent", "spec_self"): "refuse",
    ("recurrent", "spec_draft"): "refuse",
    ("recurrent", "snapshot"): "refuse",
    ("latent", "slot_engine"): "refuse",
    ("latent", "int8_pages"): "refuse",
    ("latent", "snapshot"): "refuse",
    ("window", "slot_engine"): "refuse",
    ("window", "prefix_sharing"): "refuse",
    ("window", "chunked_prefill"): "refuse",
    ("window", "int8_pages"): "refuse",
    ("window", "spec_self"): "refuse",
    ("window", "spec_draft"): "refuse",
    ("window", "snapshot"): "refuse",
    ("experts", "spec_self"): "refuse",
}
# what a refusal has to say of each
PROPERTY_WORDS = {
    "recurrent": "a model with recurrent state",
    "latent": "a latent KV cache",
    "window": "window layers",
    "experts": "routed-expert layers",
}
FEATURE_WORDS = {
    "slot_engine": "the slot engine",
    "prefix_sharing": "prefix sharing (serving_prefix_cache)",
    "chunked_prefill": "chunked prefill (prefill_chunk_tokens)",
    "int8_pages": "int8 page storage (kv_quant=int8)",
    "spec_self": "speculative decoding (spec='self')",
    "spec_draft": "speculative decoding (spec='draft')",
    "snapshot": "mid-stream snapshot",
}


def tree_of(leaves):
    tree = {}
    for path, arr in leaves:
        node = tree
        *parents, leaf = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[leaf] = jnp.asarray(arr)
    return tree


@functools.lru_cache(maxsize=None)
def family(name):
    """``(config, module, variables)`` of a family's tiny configuration,
    built by the benchmark's own builder."""
    path, builder = FAMILIES[name]
    cfg = json.loads((ROOT / "benchmark/tests" / path).read_text())
    ns = {}
    exec(builder.function_source(cfg), ns)
    weights = builder.init_weights(cfg, 3)
    return (cfg, ns["Model"]().build(),
            tree_of(builder.program_leaves(cfg, weights)))


def deployed(name, **over):
    """The paged decoder the configuration's ``deployment`` asks for."""
    cfg, module, tree = family(name)
    dep = cfg["deployment"]
    args = dict(slots=dep["serving_slots"],
                chunk_steps=dep["serving_chunk_steps"],
                page_tokens=dep["serving_page_tokens"],
                pages=dep["serving_pages"],
                prefix_cache=dep.get("serving_prefix_cache", True),
                paged_attn="gather")
    args.update(over)
    return PagedBatchingDecoder(module, tree, **args)


def test_the_families_have_the_properties_the_table_is_keyed_by():
    assert PROPERTIES == ("recurrent", "latent", "window", "experts")
    assert batcher.FEATURES == FEATURES
    for name, has in HAS.items():
        assert cache_spec(family(name)[1]).properties == has, name


# --- the table, cell by cell, through the constructors ----------------------


def ask(name, feature):
    """A decoder of family ``name`` with ``feature`` asked for and every
    other one off (the slot engine has none to ask for)."""
    _, module, tree = family(name)
    if feature == "slot_engine":
        return BatchingDecoder(module, tree, slots=2)
    kw = dict(slots=2, page_tokens=4, paged_attn="gather",
              prefix_cache=False)
    if feature == "spec_draft":
        draft = gpt.CausalTransformer(
            vocab_size=module.vocab_size, max_len=module.max_len,
            embed_dim=32, depth=1, num_heads=2)
        kw.update(draft_module=draft, draft_variables=draft.init(
            jax.random.key(0), jnp.ones((1, 4), jnp.int32)))
    kw.update({"prefix_sharing": dict(prefix_cache=True),
               "chunked_prefill": dict(prefill_chunk_tokens=16),
               "int8_pages": dict(kv_quant="int8"),
               "spec_self": dict(spec="self"),
               "spec_draft": dict(spec="draft"),
               "snapshot": {}}[feature])
    return PagedBatchingDecoder(module, tree, **kw)


def mid_stream(dec):
    """Hand ``dec`` a snapshot taken mid-stream (one token out of five)."""
    dec.submit_snapshot(kvsnap.RequestSnapshot(
        model=dec.name, request_id="r", page_tokens=dec.page_tokens,
        kv_quant="none", spec="off", prompt=[1, 2, 3], out=[4], max_new=5,
        temp=0.0, topk=0, eos=-1, key=(0, 0), layers=[]))


def feature_is_on(dec, feature):
    return {"slot_engine": lambda: type(dec) is BatchingDecoder,
            "prefix_sharing": lambda: dec._pool.trie is not None,
            "chunked_prefill": lambda: dec.prefill_chunk == 16,
            "int8_pages": lambda: dec.kv_quant == "int8",
            "spec_self": lambda: dec.spec == "self",
            "spec_draft": lambda: dec.spec == "draft",
            "snapshot": lambda: True}[feature]()


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("name", list(FAMILIES))
def test_a_cache_property_allows_what_the_table_says(name, feature):
    """Refused with a 409 that names property and feature exactly where
    ``TABLE`` says refuse, served with the feature reported off where it
    says off, served with the feature on everywhere else."""
    # a pair the code's table has and this file has not fails here too
    assert batcher.CACHE_FEATURES == TABLE
    verdicts = {prop: TABLE.get((prop, feature)) for prop in HAS[name]}
    if "refuse" in verdicts.values():
        with pytest.raises(CacheFeatureUnsupported) as refused:
            dec = ask(name, feature)
            try:
                if feature == "snapshot":   # asked for after construction
                    mid_stream(dec)
            finally:
                dec.close()
        e = refused.value
        assert isinstance(e, KubeMLError) and e.status_code == 409
        assert e.feature == feature and verdicts[e.property] == "refuse"
        assert PROPERTY_WORDS[e.property] in str(e)
        assert FEATURE_WORDS[feature] in str(e)
        return
    dec = ask(name, feature)
    try:
        if "off" in verdicts.values():
            assert feature == "prefix_sharing" and dec._pool.trie is None
            assert dec.telemetry()["prefix_cache_off_recurrent"] == 1.0
            return
        assert feature_is_on(dec, feature)
        if feature == "snapshot":
            # past the table; the frame's own checks (it carries no layer)
            # are another refusal
            with pytest.raises(KubeMLError, match="layers") as other:
                mid_stream(dec)
            assert not isinstance(other.value, CacheFeatureUnsupported)
        elif type(dec) is PagedBatchingDecoder:
            assert dec.telemetry()["prefix_cache_off_recurrent"] == 0.0
    finally:
        dec.close()


# --- the description against the arrays -------------------------------------

_ARENAS = ("kv_rows", "latent_pages", "k_scale", "v_scale")


@pytest.mark.parametrize("name,kv_quant", [
    *((name, "off") for name in FAMILIES), ("gpt2", "int8")])
def test_the_arenas_weigh_what_the_description_says(name, kv_quant):
    """``arena_bytes`` and the description's page bytes against the
    ``nbytes`` of the arenas ``init_paged_cache`` builds for the engine's
    own module (pages, ring pages and scale rows; recurrent state and the
    experts' counters are no arena)."""
    _, _, tree = family(name)
    dec = deployed(name, kv_quant=kv_quant)
    try:
        spec, pt = dec.cache, dec.page_tokens
        assert spec == cache_spec(dec.module)
        built = init_paged_cache(dec.module, tree, dec.slots,
                                 dec.table_pages)
        arenas = [leaf for path, leaf
                  in jax.tree_util.tree_leaves_with_path(built)
                  if getattr(path[-1], "key", None) in _ARENAS]
        rings = [a for a in arenas if a.shape[0] == dec.window_arena_pages]
        pages = [a for a in arenas if a.shape[0] == dec.arena_pages]
        assert len(rings) == spec.window_layers
        assert len(rings) + len(pages) == len(arenas) > 0
        assert sum(a.nbytes for a in pages) == (
            dec.arena_pages * spec.page_bytes(pt, kv_quant))
        assert sum(a.nbytes for a in rings) == (
            dec.window_arena_pages * spec.ring_page_bytes(pt))
        assert sum(a.nbytes for a in arenas) == dec.arena_bytes
        if kv_quant == "int8":
            # the byte budget of the unquantized arena, in int8 pages
            assert dec.arena_pages > 2 * dec.slots * dec.table_pages
            assert spec.token_bytes("int8") * spec.itemsize == (
                spec.token_bytes())
    finally:
        dec.close()


# --- telemetry() against the parent's ---------------------------------------

# every key the parent's paged decoder returned before its first request, the
# same for all six (commit db5b3f5, each configuration's own ``deployment``)
KEYS = frozenset("""
admission_waves block_traces cache_sublayers chunks compile_backend_seconds
compile_cache_hits compile_cache_misses compile_lower_seconds compile_storm
compile_trace_seconds compile_wall_seconds compiled_programs
compiles_per_minute dead_slot_steps device_steps draining expert_param_bytes
fetch_busy_seconds fetcher_utilization fetchers_inflight fetchers_total fetches
full_layers goodput_ratio goodput_tokens hc_positions hc_positions_admit
hc_positions_step hol_stall_seconds idle_slot_steps kv_latent_row_width
kv_latent_width kv_quant kv_read_bytes live_slot_steps moe_assignments
moe_assignments_absent moe_assignments_zero moe_experts_held
moe_experts_touched moe_layers overload_per_second page_occupancy page_tokens
paged_attn_kernel pages_free pages_total param_bytes param_leaves_narrowed
prefill_chunk_tokens prefill_chunks prefill_head_positions prefill_pad_tokens
prefill_tokens prefills_in_progress prefix_cache_off_recurrent
prefix_cache_pages prefix_hits prefix_tokens_saved queue_depth queue_limit
recurrent_layers recurrent_state_bytes requests_canceled requests_completed
requests_deadline_expired requests_failed requests_overload requests_rejected
requests_shed requests_submitted requests_timeout residual_streams
run_ahead_depth slot_occupancy slot_steps slots_busy slots_total
startup_decoder_seconds startup_hold_seconds startup_restore_seconds
startup_slab_seconds tokens_emitted tokens_per_second wasted_tokens
weight_bytes window_layers window_pages_free window_pages_total
window_ring_pages""".split())
# (cache_sublayers, window_layers, full_layers, residual_streams,
#  kv_latent_width, kv_latent_row_width, moe_layers, moe_experts_held,
#  window_ring_pages, pages_total), and beside them what is no telemetry key:
# (arena_bytes, bytes a cached token is read at in the full layers, in the
#  window layers, residual sub-layers)
PARENT = {
    "gpt2": ((2, 0, 2, 1, 0, 0, 0, 0, 0, 32), (540672, 1024, 0, 0)),
    "falcon": ((2, 0, 2, 1, 0, 0, 0, 0, 0, 32), (540672, 1024, 0, 0)),
    "glm": ((3, 0, 3, 1, 24, 128, 2, 8, 0, 32), (811008, 288, 0, 0)),
    "xing": ((5, 0, 5, 4, 24, 128, 3, 8, 0, 32), (1351680, 480, 0, 10)),
    "longcat": ((4, 0, 4, 1, 24, 128, 2, 4, 0, 32), (1081344, 384, 0, 0)),
    "mimo": ((7, 5, 2, 1, 0, 0, 6, 4, 4, 128), (876544, 640, 3200, 0)),
    # no parent: the family is PR 48's, and these are its values then (2 of
    # its 8 layers page; the keys are the other families')
    "olmo": ((2, 0, 2, 1, 0, 0, 0, 0, 0, 32), (1081344, 1536, 0, 0)),
    # no parent: the family is PR 51's (2 of its 8 layers hold a latent
    # arena, 7 route experts; the keys are the other families')
    "kimi": ((2, 0, 2, 1, 24, 128, 7, 16, 0, 32), (540672, 192, 0, 0)),
}
STATIC = ("cache_sublayers", "window_layers", "full_layers",
          "residual_streams", "kv_latent_width", "kv_latent_row_width",
          "moe_layers", "moe_experts_held", "window_ring_pages",
          "pages_total")


@pytest.mark.parametrize("name", list(FAMILIES))
def test_telemetry_is_the_parents(name):
    dec = deployed(name)
    try:
        tel = dec.telemetry()
        assert set(tel) == KEYS
        static, beside = PARENT[name]
        assert tuple(tel[k] for k in STATIC) == tuple(map(float, static))
        assert (dec.arena_bytes, dec._kv_token_bytes,
                dec._window_token_bytes, dec.stats.hc_sublayers) == beside
    finally:
        dec.close()


# --- layers without pages (PR 48) --------------------------------------------


def test_a_stack_with_layers_that_do_not_page_counts_the_ones_that_do():
    """Olmo-Hybrid's pattern: of 8 layers the 2 full-attention ones page K
    and V of 6 heads of 16, the 6 linear ones carry a state and no pages.
    Every sum an engine reads counts the 2; the state is counted beside."""
    _, module, _ = family("olmo")
    spec = cache_spec(module)
    assert module.depth == 8 and len(spec.layers) == 2
    assert all((l.kv_heads, l.k_dim, l.v_dim, l.window) == (6, 16, 16, 0)
               for l in spec.layers)
    assert (spec.sublayers, spec.full_layers, spec.window_layers) == (2, 2, 0)
    # a cached token: K and V of 6 heads of 16 in each of TWO layers
    assert spec.token_bytes() == 4 * 2 * 6 * 32
    assert spec.token_bytes(first=1) == 4 * 6 * 32
    # 192 values a token and layer stored in 256 lanes, 16 tokens a page
    assert spec.page_bytes(16) == 16 * 4 * 2 * 256
    assert spec.window_token_bytes() == spec.ring_page_bytes(16) == 0
    assert spec.ring_pages(16) == 0 and spec.latent is None
    # six layers of state: 6 heads x 8 x 16 float32 and 3 taps' tail of
    # 6 x (8 + 8 + 16) inputs, a program row
    assert spec.recurrent and spec.state_layers == 6
    assert spec.state_row_bytes == 4 * (6 * 8 * 16 + 3 * 6 * 32)
    assert spec.state_bytes(4) == 4 * 6 * spec.state_row_bytes
    assert spec.properties == {"recurrent"}
    assert spec.state_gate_width == 1     # one decay a head


def test_a_latent_stack_with_layers_that_hold_a_state():
    """Kimi-Linear's pattern: of 8 layers the 2 latent-attention ones hold a
    latent arena (24 live values a token in 128 lanes), the 6 KDA ones carry
    a state and no pages; 7 route experts. Every sum an engine reads counts
    the 2 arenas; the state and the experts are counted beside, and the
    three properties stand together."""
    _, module, _ = family("kimi")
    spec = cache_spec(module)
    assert module.depth == 8 and len(spec.layers) == 2
    assert all((l.latent_width, l.latent_row_width, l.kv_heads, l.window)
               == (24, 128, 0, 0) for l in spec.layers)
    assert spec.latent == spec.layers[0]
    assert (spec.sublayers, spec.full_layers, spec.window_layers) == (2, 2, 0)
    # a cached token: one latent of 16 + 8 in each of TWO layers
    assert spec.token_bytes() == 4 * 2 * 24
    assert spec.token_bytes(first=1) == 4 * 24
    # stored in 128 lanes a token and layer, 16 tokens a page
    assert spec.page_bytes(16) == 16 * 4 * 2 * 128
    assert spec.window_token_bytes() == spec.ring_page_bytes(16) == 0
    # six layers of state: 4 heads x 16 x 16 float32 and 3 taps' tail of
    # 3 x 64 inputs, a program row, gated by 16 values a head
    assert spec.recurrent and spec.state_layers == 6
    assert spec.state_row_bytes == 4 * (4 * 16 * 16 + 3 * 192)
    assert spec.state_bytes(4) == 4 * 6 * spec.state_row_bytes
    assert spec.state_gate_width == 16
    assert (spec.expert_layers, spec.experts_per_token,
            spec.experts_held) == (7, 4, 16)
    assert spec.properties == {"recurrent", "latent", "experts"}
    # a stack of latent attention alone still lists every layer
    assert cache_spec(family("glm")[1]).sublayers == family("glm")[1].depth
    assert cache_spec(family("glm")[1]).state_gate_width == 0


@pytest.mark.parametrize("name,layers,row", [
    # a Mamba-2 mixer beside attention in EVERY layer: 4 heads of [16, 8]
    # float32 and 3 taps' tail of 32 + 2 x 2 x 16 inputs
    ("falcon", 2, 4 * (4 * 16 * 8 + 3 * 96)),
    # a Gated DeltaNet mixer IN PLACE of attention in 6 of 8 layers
    ("olmo", 6, 4 * (6 * 8 * 16 + 3 * 6 * 32)),
    # a Kimi Delta Attention mixer in 6 of 8 layers UNDER latent attention
    ("kimi", 6, 4 * (4 * 16 * 16 + 3 * 192)),
    ("mimo", 0, 0), ("gpt2", 0, 0), ("glm", 0, 0)])
def test_the_state_beside_the_pages_is_what_it_was(name, layers, row):
    """Falcon-H1's state is counted as the slab's leaves weighed before
    (``recurrent_layers``, ``recurrent_state_bytes``); a model without one
    counts none, and its ``layers`` are its whole stack."""
    _, module, tree = family(name)
    spec = cache_spec(module)
    assert (spec.state_layers, spec.state_row_bytes) == (layers, row)
    assert spec.recurrent == (layers > 0)
    if name in ("falcon", "gpt2"):
        assert spec.sublayers == module.depth
    dec = deployed(name)
    try:
        built = init_paged_cache(dec.module, tree, dec.slots,
                                 dec.table_pages)
        state = [leaf for path, leaf
                 in jax.tree_util.tree_leaves_with_path(built)
                 if getattr(path[-1], "key", None) in (
                     "ssm_state", "gdn_state", "conv_tail")]
        assert sum(a.nbytes for a in state) == spec.state_bytes(dec.slots)
        tel = dec.telemetry()
        assert tel["recurrent_layers"] == layers
        assert tel["recurrent_state_bytes"] == spec.state_bytes(dec.slots)
    finally:
        dec.close()
