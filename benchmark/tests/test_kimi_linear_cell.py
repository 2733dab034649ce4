"""The Kimi-Linear-48B-A3B configuration and what PR 51 added to read it: the
file as ``spec.load_cell`` gives it, the catalog's numbers, the new mix's
lengths, the family's step costs on shapes counted by hand, the two new
readers on a hand-made trace and on another cell's, and a whole toy run of
the harness with its controls.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import check, layers, reduce, spec, traffic  # noqa: E402
from benchmark.costs import decode_step_kimi_linear, kda_state  # noqa: E402
from benchmark.layer_metrics import _kda  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "kimi-linear-48b-a3b.think"
OWN = ("kda_update_dev_ms", "kda_decode_roofline")
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PERIOD = ["kda"] * 3 + ["mla"]


def test_the_cell_is_in_the_benchmark_and_only_added():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) >= 10 and cells[CELL]["chips"] == 1
    assert cells[CELL] == {
        "name": CELL, "config": "kimi-linear-48b-a3b", "traffic": "think",
        "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in bench["configs"]}["kimi-linear-48b-a3b"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/kimi-linear-48b-a3b.json"
    assert all(len(e["why"]) <= 200 for e in (entry, cells[CELL]))
    # behind the accepted cells in every list it joined
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) > order.index("olmo-hybrid-7b.reason")
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []) and m["name"] not in OWN:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) > 1, (
                m["name"])
    own = [m for m in bench["per_layer"] if m["name"] in OWN]
    # first in its own metrics' lists (a later cell may join behind it)
    assert len(own) == 2 and all(
        m["workloads"][0] == CELL and m["moves"] == "output_tokens_per_s"
        for m in own)
    assert {m["name"]: (m["layer"], m["source"], m["unit"]) for m in own} == {
        "kda_update_dev_ms": ("Model step", "device_trace", "ms"),
        "kda_decode_roofline": ("Kernels", "device_trace", "%")}
    names = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]]
    assert len(names) == len(set(names))


def test_the_limits_file_names_its_readings():
    lim = json.loads((spec.HERE / "limits" / f"{CELL}.json").read_text())
    limits = lim["limits"]
    assert set(limits) == {"logit_gap_max", "logit_gap_mean",
                           "short_answers", "not_paged_engine"}
    exact = {"short_answers": 0, "not_paged_engine": 0}
    r = lim["readings"]
    sound = {k: r[k]["sound_runs_largest"]
             for k in ("logit_gap_mean", "logit_gap_max")}
    assert check.compare({**sound, **exact}, limits)[0] is True
    # the mean gap lies between the sound runs and every control, with room
    # on both sides; each control comes out as not correct by it
    mean = r["logit_gap_mean"]
    assert 2 * sound["logit_gap_mean"] <= limits["logit_gap_mean"]
    assert limits["logit_gap_mean"] <= mean["control_smallest"] / 2.5
    assert limits["logit_gap_mean"] <= mean["mechanism_controls_smallest"] / 5
    for low in (mean["control_smallest"],
                mean["mechanism_controls_smallest"]):
        assert check.compare({"logit_gap_mean": low,
                              "logit_gap_max": sound["logit_gap_max"],
                              **exact}, limits)[0] is False
    # the widest gap: a gross check between the sound runs and the faults
    widest = r["logit_gap_max"]
    assert 1.5 * sound["logit_gap_max"] <= limits["logit_gap_max"] < (
        widest["mechanism_controls_smallest"])


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell(CELL)
    c = cell.config
    lin = c["linear_attn_config"]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    assert c["num_hidden_layers"] == 8
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7]
    assert lin["full_attn_layers"] == [4, 8]
    # n_layer counts the layers that call %attn a step: the latent ones
    assert c["n_layer"] == len(lin["full_attn_layers"]) == 2
    assert c["n_head"] == c["num_attention_heads"] == lin["num_heads"] == 32
    assert c["layer_norm_epsilon"] == c["rms_norm_eps"] == 1e-5
    assert c["n_positions"] == c["deployment"]["served_length"] == 3072
    # GLM's names for what the readers read: the experts HELD, the choices
    assert c["n_routed_experts"] == c["num_experts"] == 64
    assert c["num_experts_per_tok"] == c["num_experts_per_token"] == 8
    assert c["reduced"] == REDUCED
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (27, 256, 163840)
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20
    assert pub["linear_attn_config"]["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert "four chips of one TPU v5e host" in c["deployment"]["stands_for"]
    assert c["deployment"]["serving_slots"] == 128
    assert c["deployment"]["serving_prefix_cache"] is False
    assert c["lower_precision_control"] == "fp8_e4m3"
    for key in ("block", "kda", "mla", "head_dim", "router", "state_dtype",
                "init"):
        assert c["assumed"][key]
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    # a subset, not the exact set: a later PR may add a metric to every cell
    assert set(cell.per_layer) >= {
        "prefill_pad_share", "decode_step_dev_ms.capacity", "prefill_dev_ms",
        "engine_host_ms_per_step.capacity", "idle_with_work_share.capacity",
        "decode_step_mfu.capacity", "moe_experts_dev_ms",
        "moe_decode_roofline", "moe_touched_share", "moe_held_share",
        "mla_decode_roofline", "latent_walk_live_share",
        "state_rows_live_share", "setup_restore_s", "setup_build_s",
        "setup_trace_s", "setup_lower_s", "setup_backend_s",
        "setup_cache_hit_share", *OWN}
    for silent in ("gdn_decode_roofline", "gqa_decode_roofline",
                   "prefill_admit_mfu", "moe_zero_share"):
        assert silent not in cell.per_layer
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    assert spec.plugin("costs", c["step_costs"]).decode_step
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    for other in ("gpt2-large.chat", "olmo-hybrid-7b.reason"):
        assert not set(OWN) & set(spec.load_cell(other).per_layer)


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the four in ``reduced``."""
    c = spec.load_cell(CELL).config
    if CATALOG.exists():
        published = next(
            e for e in map(json.loads, CATALOG.read_text().splitlines())
            if e["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert c["source"] == published["source_url"]
        for k, v in published["config"].items():
            if k not in REDUCED:
                assert c[k] == v, k
        assert c["published"]["linear_attn_config"] == published["config"][
            "linear_attn_config"]
        # the cut group keeps every width of the published one
        for k in ("num_heads", "head_dim", "short_conv_kernel_size"):
            assert c["linear_attn_config"][k] == published["config"][
                "linear_attn_config"][k]
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["vocab_size"]) == (
        2304, 9216, 1024, 40960)
    builder = spec.plugin("models", c["builder"])
    assert builder.layers(c) == (
        (True, False), (True, True), (True, True), (False, True),
        (True, True), (True, True), (True, True), (False, True))
    # the weights the cut keeps, by the builder's own shapes: 3.772B
    shapes = builder.shapes(c)
    total = sum(math.prod(s) for s, _ in shapes.values())
    assert total == 3_772_368_832
    e = 2304
    kda = (4 * e * 4096 + 2 * (e * 128 + 128 * 4096) + e * 32 + 3 * 4 * 4096
           + 32 + 4096 + 128)
    mla = e * 32 * 192 + e * 576 + 512 + 512 * 32 * 256 + 4096 * e
    expert = 3 * e * 1024
    ffn = 64 * expert + expert + e * 256 + 256       # held, shared, router
    assert 39.5e6 < kda < 39.52e6 and 29.1e6 < mla < 29.13e6
    assert total == (6 * kda + 2 * mla + 3 * e * 9216 + 7 * ffn + 16 * e
                     + 2 * 40960 * e + e)
    assert 103.1e6 < kda + 3 * e * 9216 + 2 * e < 103.3e6      # layer 1
    assert 500.1e6 < kda + ffn + 2 * e < 500.3e6    # a KDA expert layer
    assert 489.7e6 < mla + ffn + 2 * e < 489.9e6    # an MLA expert layer
    assert shapes["k_wfb"][0] == (6, 128, 4096)
    assert shapes["k_dt_bias"] == ((6, 4096), "dt_bias")
    assert shapes["m_wq"][0] == (2, 2304, 32 * 192)
    assert shapes["e_gate"][0] == (7, 64, 2304, 1024)
    assert shapes["w_r"][0] == (7, 2304, 256)
    source = builder.function_source(c)
    for piece in ("depth=8", "num_heads=32", "pos=\"none\"", "ln_eps=1e-05",
                  "q_lora_rank=None", "kv_lora_rank=512",
                  "qk_nope_head_dim=128", "qk_rope_head_dim=64",
                  "v_head_dim=128", "mla_use_nope=True",
                  "AttnKind(), AttnKind(linear=True)",
                  "attn_pattern=(1, 1, 1, 0, 1, 1, 1, 0)",
                  "KDAConfig(", "head_dim=128", "short_conv_kernel_size=4",
                  "mlp_dim=9216", "dense_layers=1", "n_routed_experts=256",
                  "num_experts_per_tok=8", "moe_intermediate_size=1024",
                  "routed_scaling_factor=2.446", "held=(0, 64)",
                  "max_len=3072", "vocab_size=40960"):
        assert piece in source, piece


def test_the_new_mixes_lengths():
    mix = spec.load_cell(CELL).traffic
    assert mix["clients"] == 160 and mix["check_requests"] == 8
    assert mix["block_requests"] == 160
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 520, "hi": 960}
    assert mix["new_tokens"] == {"dist": "log_uniform", "lo": 512,
                                 "hi": 2048}
    assert mix["requests_per_second_ceiling"] == 20
    assert mix["drain_seconds"] == 60
    n = traffic.n_requests(mix, 50.0)
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 40960)
    assert len(reqs) == n == 1000
    assert all(520 <= len(r["prompt"]) <= 960 and 512 <= r["max_new"] <= 2048
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 40960
               for r in reqs)
    # one prefill bucket; table widths of 64, 128 and 192 pages
    assert 512 < min(len(r["prompt"]) for r in reqs)
    assert max(len(r["prompt"]) for r in reqs) <= 1024
    assert 2048 < max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 3072
    assert 1090 < sum(r["max_new"] for r in reqs[:160]) / 160 < 1125
    sizes = lambda rs, i: sorted((len(r["prompt"]), r["max_new"])[i]
                                 for r in rs)
    assert sizes(reqs[:160], 0) == sizes(reqs[160:320], 0)
    assert sizes(reqs[:160], 1) == sizes(reqs[320:480], 1)
    warm = traffic.warmup_requests(mix, 5, 40960)
    assert [(len(w["prompt"]), w["max_new"]) for w in warm] == [
        (520, 4), (960, 96), (960, 1100)]
    assert 520 + 4 <= 1024 < 960 + 96 <= 2048 < 960 + 1100   # the widths


def test_step_costs_on_shapes_counted_by_hand():
    # a toy: one dense matrix of 8 x 8 beside a head of 8 x 5 and 3 held
    # experts of 3 x 8 x 2; 4 layers: KDA x3, MLA; 2 heads of 3; latent 4 + 2
    cfg = {"compute_dtype": "bfloat16", "hidden_size": 8,
           "moe_intermediate_size": 2, "num_attention_heads": 2,
           "kv_lora_rank": 4, "qk_rope_head_dim": 2,
           "linear_attn_config": {"num_heads": 2, "head_dim": 3,
                                  "kda_layers": [1, 2, 3],
                                  "full_attn_layers": [4]}}
    shapes = {"wte": ((5, 8), "embed"), "w": ((8, 8), "kernel"),
              "lm_head": ((8, 5), "kernel"),
              "e_gate": ((1, 3, 8, 2), "kernel"),
              "e_up": ((1, 3, 8, 2), "kernel"),
              "e_down": ((1, 3, 2, 8), "kernel")}
    assert decode_step_kimi_linear.layer_kinds(cfg) == (1, 3)
    assert decode_step_kimi_linear.weight_elements(shapes) == 64 + 40
    # one row's state in one layer: 2 x 3 x 3 float32 each way; q, k and the
    # gate of 2 x 3; v and o of 2 x 3; beta of 2
    assert kda_state.decode_step(1.0, layers=1, heads=2, key_dim=3,
                                 value_dim=3) == (
        7.0 * 18, 4.0 * (36 + 18 + 12 + 2))
    kinds = decode_step_kimi_linear.cache(cfg, 3.0, 30.0)
    assert kinds["latent"] == (2.0 * 2 * (6 + 4) * 30, 6 * 2.0 * 30)
    assert kinds["kda"] == (7.0 * 18 * 3 * 3, 4.0 * 68 * 3 * 3)
    flops, nbytes = decode_step_kimi_linear.decode_step(
        cfg, shapes, rows=3.0, depth_tokens=30.0, touched=2.0,
        assignments=4.0)
    experts = (2.0 * 48 * 4, 48 * 2 * 2 + 2.0 * 10 * 2 * 4)
    assert flops == (2 * 104 * 3 + kinds["latent"][0] + kinds["kda"][0]
                     + experts[0])
    assert nbytes == (104 * 2 + kinds["latent"][1] + kinds["kda"][1]
                      + experts[1])
    # the published step at 128 rows about 2,000 deep: 1.01 GB of weights
    # outside the embedding and the routed experts, 6.2 GB of held experts
    # (62.8 of 64 touched in each of 7 layers, 256 assignments a layer),
    # 3.28 GB of the six KDA layers' state and vectors (3.22 of it state),
    # 0.59 GB of the two arenas' latents; bytes bound it: 13.6 ms
    c = spec.load_cell(CELL).config
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    assert 1.01e9 < 2 * decode_step_kimi_linear.weight_elements(shapes) < (
        1.02e9)
    kinds = decode_step_kimi_linear.cache(c, 128.0, 128 * 2000.0)
    assert 0.58e9 < kinds["latent"][1] < 0.60e9
    assert 3.27e9 < kinds["kda"][1] < 3.29e9
    # the state alone, each way: 128 rows x 6 layers x 2.10 MB
    assert 128 * 6 * 32 * 128 * 128 * 4 * 2 == 3_221_225_472
    flops, nbytes = decode_step_kimi_linear.decode_step(
        c, shapes, 128.0, 128 * 2000.0, touched=62.8 * 7,
        assignments=256.0 * 7)
    assert 11.0e9 < nbytes < 11.2e9
    least, bound = decode_step_kimi_linear.min_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and 0.0134 < least < 0.0137


def _reading(ops, modules, cfg, counters=None, records=()):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules}, wall_zero=100.0)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=list(records),
                          counters=counters or ({}, {}))
    return layers.Reading(cell=SimpleNamespace(config=cfg), win=win,
                          trace=trace, peaks=PEAKS)


def _step_ops(steps, kda_seconds=0.0008, attn_seconds=0.0006):
    """``steps`` decode steps in one program execution: six ``%kda_update``
    and two ``%attn`` calls each, in the stack's order."""
    ops, t = [], 1.0
    for step in range(steps):
        for k, kind in enumerate(PERIOD * 2):
            name, dur = (("%kda_update", kda_seconds) if kind == "kda"
                         else ("%attn", attn_seconds))
            ops.append((f"{name}.{8 * step + k} custom-call", t, dur))
            t += dur + 0.0005
    return ops


def test_the_trace_readers_count_six_calls_a_step():
    cfg = spec.load_cell(CELL).config
    modules = [("jit__unknown(3)", 1.0, 0.040)]
    ops = _step_ops(2)
    r = _reading(ops, modules, cfg)
    assert _kda.kernel_in_steps(r) == [(2, pytest.approx(12 * 0.0008))]
    read = lambda name, at=r: spec.plugin("layer_metrics", name).read(at)
    assert read("kda_update_dev_ms") == pytest.approx(6 * 0.8)
    # every slab row's state once each way, 128 rows x 6 layers x 2 steps;
    # q, k, the gate, v and o of 4,096 each, beta of 32
    _, nbytes = kda_state.decode_step(128 * 2, layers=6, heads=32,
                                      key_dim=128, value_dim=128)
    assert nbytes == 2 * 128 * 6 * 4 * (2 * 524288 + 5 * 4096 + 32)
    want = 100.0 * nbytes / PEAKS["hbm_bytes_per_s"] / (12 * 0.0008)
    assert read("kda_decode_roofline") == pytest.approx(want, rel=1e-6)
    assert 80 < want < 90
    # a count over 105% of the roofline is refused, not clipped
    with pytest.raises(Exception):
        read("kda_decode_roofline",
             _reading(_step_ops(2, kda_seconds=0.0006), modules, cfg))
    # the two %attn calls a step are what counts a program's steps
    # (n_layer 2): a program with no kernel of ours reads nothing, and the
    # other delta rule's kernel is not ours
    only_attn = [o for o in ops if o[0].startswith("%attn")]
    other = [(n.replace("%kda_update", "%gdn_update"), s, d)
             for n, s, d in ops]
    for name in OWN:
        assert read(name, _reading(only_attn, modules, cfg)) is None
        assert read(name, _reading(other, modules, cfg)) is None
    olmo = spec.load_cell("olmo-hybrid-7b.reason").config
    for name in ("gdn_update_dev_ms", "gdn_decode_roofline"):
        assert read(name, _reading(ops, modules, olmo)) is None


def test_each_new_reader_returns_none_on_a_program_without_the_kernel():
    """The parent commit's trace (no ``%kda_update``), another family's
    cell, and nothing at all."""
    modules = [("jit__unknown(3)", 1.0, 0.030)]
    ops = [(f"%attn.{k} custom-call", 1.0 + 0.003 * k, 0.0004)
           for k in range(6)]
    for cfg in (spec.load_cell("falcon-h1-34b.turns").config,
                spec.load_cell("olmo-hybrid-7b.reason").config,
                spec.load_cell("glm-4.7-flash.rag").config,
                spec.load_cell(CELL).config):
        for r in (_reading(ops, modules, cfg), _reading([], [], cfg)):
            for name in OWN:
                assert spec.plugin("layer_metrics", name).read(r) is None


# -- a whole run at toy size: builder, hand-over, state and pages, check -----

DATA = Path(__file__).resolve().parent / "data_kimi"


def test_a_whole_toy_run_is_correct_and_counts_its_state(monkeypatch,
                                                         capsys):
    from benchmark import run
    from kubeml_tpu.serving import batcher

    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    seen = {}
    sound = batcher.PagedBatchingDecoder.telemetry

    def telemetry(self):
        tel = sound(self)
        seen.update(tel)
        return tel

    monkeypatch.setattr(batcher.PagedBatchingDecoder, "telemetry", telemetry)
    rc = run.main(["--workload", "tiny-kimi.think", "--seed",
                   str(2 ** 31 + 51), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    mean = result["check"]["logit_gap_mean"]
    assert 0.0 <= mean["value"] <= mean["limit"] == 2e-3
    assert seen["cache_sublayers"] == seen["full_layers"] == 2.0
    assert seen["recurrent_layers"] == 6.0
    assert seen["state_gate_width"] == 16.0
    assert (seen["kv_latent_width"], seen["moe_layers"],
            seen["moe_experts_held"]) == (24.0, 7.0, 16.0)
    row = 4 * (4 * 16 * 16 + 3 * 3 * 64)
    assert seen["recurrent_state_bytes"] == 4 * 6 * row
    assert seen["state_rows_moved"] == seen["chunks"] * 4
    assert 0 < seen["state_rows_live"] <= seen["state_rows_moved"]
    assert 0 < seen["moe_assignments"] < (
        seen["moe_assignments"] + seen["moe_assignments_absent"])
    # 2 bytes a parameter, by the builder's own shapes
    cfg = json.loads((DATA / "configs/tiny-kimi.json").read_text())
    shapes = spec.plugin("models", "kimi_linear").shapes(cfg)
    assert seen["param_bytes"] == 2 * sum(
        math.prod(s) for s, _ in shapes.values())


@pytest.mark.parametrize("control", ["bfloat16", "channel_gate_off",
                                     "delta_off", "nope_off", "held_zero"])
def test_the_toys_controls_fail_the_mean_gap(monkeypatch, control):
    """The toy's stated control, and the four faults planted in a mechanism
    (probe_control.py), read on a seeded sample of prompts through the
    reference alone: the sound reference's own first choices lie under each
    control's best by more than the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    cell = spec.load_cell("tiny-kimi.think")
    cfg = cell.config
    builder = spec.plugin("models", cfg["builder"])
    reference = spec.plugin("reference", cfg["reference"])
    assert control in reference.CONTROLS + reference.PRECISIONS
    weights = builder.init_weights(cfg, 7)
    ids = jnp.asarray(np.random.default_rng(7).integers(
        1, cfg["vocab_size"], 64), jnp.int32)
    at = jnp.arange(32, 64)
    kw = dict(n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        sound = np.asarray(reference.logits_at(weights, ids, at, **kw))
        low = np.asarray(reference.logits_at(weights, ids, at,
                                             precision=control, **kw))
    served = sound.argmax(-1)               # what a sound program serves
    gaps = low.max(-1) - low[np.arange(len(served)), served]
    limit = check.limits_for(cell.name)["logit_gap_mean"]
    assert float(gaps.mean()) > 2 * limit
