"""The LongCat-Flash-Omni configuration and what PR 41 added to read it: the
file as ``spec.load_cell`` gives it, the catalog's numbers, the new mix's
lengths, the family's step costs on shapes counted by hand, the two new
readers on hand-made counters and on another cell's, what the accepted
readers make of a step that walks eight arenas, and a whole toy run of the
harness.

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
from benchmark.costs import decode_step, decode_step_longcat  # noqa: E402
from benchmark.layer_metrics import _programs  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "longcat-flash-omni.agent"
OWN = ("moe_held_share", "moe_zero_share")
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]


def test_the_cell_is_in_the_benchmark_and_only_added():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) == 7 and all(w["chips"] == 1 for w in cells.values())
    assert cells[CELL] == {
        "name": CELL, "config": "longcat-flash-omni", "traffic": "agent",
        "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in bench["configs"]}["longcat-flash-omni"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/longcat-flash-omni.json"
    assert all(len(e["why"]) <= 200 for e in (entry, cells[CELL]))
    # behind the accepted cells in every list it joined
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) > order.index("xing4.0-29b-a4b.extract")
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []) and m["name"] not in OWN:
            assert m["workloads"].index(CELL) > m["workloads"].index(
                "glm-4.7-flash.rag"), m["name"]
    own = [m for m in bench["per_layer"] if m["name"] in OWN]
    assert len(own) == 2 and all(
        m["workloads"] == [CELL] and m["moves"] == "output_tokens_per_s"
        and m["layer"] == "Serving control" for m in own)
    # its count assumes every choice is computed here
    admit = {m["name"]: m for m in bench["per_layer"]}["prefill_admit_mfu"]
    assert CELL not in admit["workloads"]
    names = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]]
    assert len(names) == len(set(names))


def test_the_limits_file_names_its_readings():
    lim = json.loads((spec.HERE / "limits" / f"{CELL}.json").read_text())
    limits = lim["limits"]
    assert set(limits) == {"logit_gap_max", "logit_gap_mean",
                           "short_answers", "not_paged_engine"}
    exact = {"short_answers": 0, "not_paged_engine": 0}
    sound = {k: lim["readings"][k]["sound_runs_largest"]
             for k in ("logit_gap_mean", "logit_gap_max")}
    assert check.compare({**sound, **exact}, limits)[0] is True
    # the mean gap lies between the sound runs and BOTH controls (the whole
    # reference in fp8; the held experts' part left out: the fault planted
    # in this chip's share alone), the widest gap between the sound runs
    # and the held control, each with room on both sides; and each control
    # comes out as not correct
    for control, held_to in (("control", ("logit_gap_mean",)),
                             ("held_control", tuple(sound))):
        low = {k: lim["readings"][k][f"{control}_smallest"] for k in sound}
        for k in held_to:
            assert 4 * sound[k] <= limits[k] <= low[k] / 4, (control, k)
        assert check.compare({**low, **exact}, limits)[0] is False


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    # eight attention sub-layers hold a latent arena: two a double layer
    assert c["n_layer"] == 2 * c["num_layers"] == 8
    assert c["n_head"] == c["num_attention_heads"] == 64
    assert c["layer_norm_epsilon"] == c["rms_norm_eps"] == 1e-5
    assert c["n_positions"] == c["deployment"]["served_length"] == 2048
    assert c["moe_intermediate_size"] == c["expert_ffn_hidden_size"] == 2048
    assert c["reduced"] == REDUCED
    assert c["published"]["num_layers"] == 28
    assert c["published"]["n_routed_experts"] == 512
    assert c["published"]["vocab_size"] == 131072
    assert "32 TPU v5e chips" in c["deployment"]["stands_for"]
    assert "7 pipeline stages" in c["deployment"]["stands_for"]
    assert c["deployment"]["serving_slots"] == 64
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    # a subset, not the exact set: a later PR may add a metric to every cell
    assert set(cell.per_layer) >= {
        "prefill_pad_share", "decode_step_dev_ms.capacity", "prefill_dev_ms",
        "engine_host_ms_per_step.capacity", "idle_with_work_share.capacity",
        "decode_step_mfu.capacity", "moe_experts_dev_ms",
        "moe_decode_roofline", "mla_decode_roofline", "moe_touched_share",
        "setup_restore_s", "setup_build_s", "setup_trace_s", "setup_lower_s",
        "setup_backend_s", "setup_cache_hit_share", *OWN}
    assert "prefill_admit_mfu" not in cell.per_layer
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    assert spec.plugin("costs", c["step_costs"]).decode_step
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    for other in ("gpt2-large.chat", "glm-4.7-flash.rag"):
        assert not set(OWN) & set(spec.load_cell(other).per_layer)


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the three in ``reduced``."""
    c = spec.load_cell(CELL).config
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    for k, v in published.items():
        if k not in REDUCED:
            assert c[k] == v, k
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) == (
        4, 16, 16384)
    # the weights the cut keeps, by the builder's own shapes: 5.17B
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    total = sum(math.prod(s) for s, _ in shapes.values())
    assert total == 5_172_749_312
    held = sum(math.prod(shapes[k][0]) for k in ("e_gate", "e_up", "e_down"))
    assert held == decode_step_longcat.routed_expert_elements(c) \
        == 4 * 16 * 3 * 6144 * 2048
    assert shapes["w_r"][0] == (4, 6144, 768)
    # the function a user deploys carries the published keys
    source = spec.plugin("models", c["builder"]).function_source(c)
    for piece in ("mlp=\"shortcut\"", "depth=4", "n_routed_experts=512",
                  "held=(0, 16)", "zero_expert_num=256",
                  "num_experts_per_tok=12", "scoring_func=\"softmax\"",
                  "norm_topk_prob=False", "n_shared_experts=0",
                  "mla_scale_q_lora=True", "mla_scale_kv_lora=True",
                  "routed_scaling_factor=6.0", "rope_theta=10000000.0"):
        assert piece in source, piece


def test_the_new_mixes_lengths():
    mix = spec.load_cell(CELL).traffic
    assert mix["clients"] == 80 and mix["check_requests"] == 8
    assert mix["block_requests"] == 80
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 260, "hi": 480}
    assert mix["new_tokens"] == {"dist": "log_uniform", "lo": 256,
                                 "hi": 1024}
    assert mix["requests_per_second_ceiling"] == 16
    assert mix["drain_seconds"] == 40
    n = traffic.n_requests(mix, 50.0)
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 16384)
    assert len(reqs) == n == 800 and n % 80 == 0
    assert all(260 <= len(r["prompt"]) <= 480 and 256 <= r["max_new"] <= 1024
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 16384
               for r in reqs)
    # one prefill bucket; table widths of 32, 64 and 128 pages
    assert max(len(r["prompt"]) for r in reqs) <= 512
    assert 1024 < max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 2048
    assert 500 < sum(r["max_new"] for r in reqs) / n < 600
    sizes = lambda rs, i: sorted((len(r["prompt"]), r["max_new"])[i]
                                 for r in rs)
    assert sizes(reqs[:80], 0) == sizes(reqs[80:160], 0)
    assert sizes(reqs[:80], 1) == sizes(reqs[160:240], 1)
    warm = traffic.warmup_requests(mix, 5, 16384)
    assert [(len(w["prompt"]), w["max_new"]) for w in warm] == [
        (260, 4), (480, 64), (480, 560)]
    assert 480 + 64 > 512 and 480 + 560 > 1024     # the widths they reach


def test_step_costs_on_shapes_counted_by_hand():
    # a toy: one dense matrix of 8 x 8 beside a head of 8 x 5 and 3 held
    # experts of 3 x 8 x 4 in each of 2 double layers; 4 attention
    # sub-layers of 2 heads on a latent of 4 + 2
    cfg = {"compute_dtype": "bfloat16", "num_layers": 2,
           "n_routed_experts": 3, "hidden_size": 8,
           "expert_ffn_hidden_size": 4, "n_layer": 4,
           "num_attention_heads": 2, "kv_lora_rank": 4,
           "qk_rope_head_dim": 2}
    shapes = {"wte": ((5, 8), "embed"), "w": ((8, 8), "kernel"),
              "lm_head": ((8, 5), "kernel"),
              "e_gate": ((2, 3, 8, 4), "kernel"),
              "e_up": ((2, 3, 8, 4), "kernel"),
              "e_down": ((2, 3, 4, 8), "kernel")}
    assert decode_step_longcat.routed_expert_elements(cfg) == 2 * 3 * 96
    flops, nbytes = decode_step_longcat.decode_step(
        cfg, shapes, rows=3.0, depth_tokens=10.0, touched=2.0,
        assignments=5.0)
    dense = 64 + 40
    assert flops == (2 * dense * 3 + 2 * 2 * (6 + 4) * 10 * 4
                     + 2 * 96 * 5)
    assert nbytes == (dense * 2 + 6 * 2 * 10 * 4
                      + 96 * 2 * 2 + 2 * (8 + 4) * 2 * 5)
    # the published step at 64 rows 900 deep, 40.5 experts touched by 64
    # held assignments: 5.3 GB of weights outside the experts, 3.1 GB of
    # touched experts, 0.53 GB of latents; bytes bound it
    c = spec.load_cell(CELL).config
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    flops, nbytes = decode_step_longcat.decode_step(
        c, shapes, 64.0, 64 * 900.0, touched=40.5, assignments=64.0)
    assert 8.8e9 < nbytes < 9.0e9
    least, bound = decode_step_longcat.min_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and 0.0107 < least < 0.0110
    # the default module cannot read this family's file
    with pytest.raises(KeyError):
        decode_step.routed_expert_elements(c)


def _reading(ops, modules, cfg, counters=None):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules}, wall_zero=100.0)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=[],
                          counters=counters or ({}, {}))
    return layers.Reading(cell=SimpleNamespace(config=cfg), win=win,
                          trace=trace, peaks=PEAKS)


def test_the_new_readers_on_hand_made_counters():
    cfg = spec.load_cell(CELL).config
    c0 = {"moe_assignments": 10.0, "moe_assignments_zero": 100.0,
          "moe_assignments_absent": 200.0, "moe_experts_touched": 8.0,
          "device_steps": 1.0, "moe_layers": 4.0}
    c1 = {"moe_assignments": 10.0 + 16.0, "moe_assignments_zero": 356.0,
          "moe_assignments_absent": 200.0 + 496.0,
          "moe_experts_touched": 8.0 + 10.0, "device_steps": 2.0,
          "moe_layers": 4.0}
    r = _reading([], [], cfg, (c0, c1))
    read = lambda name: spec.plugin("layer_metrics", name).read(r)
    assert read("moe_held_share") == pytest.approx(100 * 16 / 768)
    assert read("moe_zero_share") == pytest.approx(100 * 256 / 768)
    # the accepted share counts the experts HELD: 10 touched of 4 x 16
    assert read("moe_touched_share") == pytest.approx(100 * 10 / 64)
    # nothing assigned in the window: nothing to read
    assert spec.plugin("layer_metrics", "moe_held_share").read(
        _reading([], [], cfg, (c1, c1))) is None


def test_each_new_reader_returns_none_on_a_program_without_the_counters():
    """The parent commit's telemetry (no zero, no absent), another family's
    cell, and nothing at all."""
    old = ({"moe_assignments": 0.0, "moe_experts_touched": 0.0,
            "device_steps": 0.0},
           {"moe_assignments": 128.0, "moe_experts_touched": 55.0,
            "device_steps": 1.0})
    for cfg in (spec.load_cell("glm-4.7-flash.rag").config,
                spec.load_cell("gpt2-large.docs").config):
        for r in (_reading([], [], cfg, old), _reading([], [], cfg)):
            for name in OWN:
                assert spec.plugin("layer_metrics", name).read(r) is None


def test_a_step_that_walks_eight_arenas_counts_as_one_step():
    """``_programs.step_executions`` counts a program's steps as its
    ``%attn`` calls over ``n_layer``: 8 here, two a double layer."""
    cfg = spec.load_cell(CELL).config
    modules = [("jit__unknown(3)", 1.0, 0.030)]
    ops = [(f"%attn.{k} custom-call", 1.0 + 0.003 * k, 0.0004)
           for k in range(8)]
    runs = _programs.step_executions(_reading(ops, modules, cfg))
    assert [(steps, round(sec, 6)) for _, _, steps, sec in runs] == [
        (1, 0.0032)]


# -- a whole run at toy size: builder, hand-over, two arenas a layer, check --

DATA = Path(__file__).resolve().parent / "data_longcat"


def test_a_whole_toy_run_is_correct_and_counts_its_assignments(monkeypatch,
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
    rc = run.main(["--workload", "tiny-longcat.agent", "--seed",
                   str(2 ** 31 + 41), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    mean = result["check"]["logit_gap_mean"]
    assert 0.0 <= mean["value"] <= mean["limit"] == 1e-4
    assert seen["moe_layers"] == 2.0 and seen["moe_experts_held"] == 4.0
    assert seen["cache_sublayers"] == 4.0
    made = (seen["moe_assignments"] + seen["moe_assignments_zero"]
            + seen["moe_assignments_absent"])
    assert made == seen["live_slot_steps"] * 4 * 2 > 0
    assert 0 < seen["moe_experts_touched"] <= seen["moe_assignments"]
    # 2 bytes a parameter: two attentions and two SwiGLUs a double layer,
    # 4 held experts, a router of 32 + 16 outputs
    attn = (128 * 32 + 32 + 32 * 8 * 24 + 128 * 24 + 16 + 16 * 8 * 32
            + 128 * 128)
    sub = attn + 2 * 128 + 3 * 128 * 96
    params = 211 * 128 * 2 + 128 + 2 * (
        2 * sub + 128 * 48 + 48 + 4 * 3 * 128 * 32)
    assert seen["param_bytes"] == 2 * params


@pytest.mark.parametrize("control", ["fp8_e4m3", "held_zero"])
def test_the_toys_controls_fail_the_mean_gap(monkeypatch, control):
    """The stated control, and the fault planted in the held experts alone
    (their part of the sum left out: probe_control.py)."""
    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    cell = spec.load_cell("tiny-longcat.agent")
    builder = spec.plugin("models", cell.config["builder"])
    limits = check.limits_for(cell.name)
    vocab = cell.config["vocab_size"]
    for seed in (4, 5):
        weights = builder.init_weights(cell.config, seed)
        reqs = traffic.requests(cell.traffic, seed, 2.0, vocab)[:60]
        toks = traffic.rng(seed, "check")
        sampled = [{"id": r["id"], "tokens": toks.integers(
            1, vocab, size=r["max_new"]).tolist()} for r in reqs]
        got = check.gaps(cell, weights, {r["id"]: r["prompt"] for r in reqs},
                         sampled, control=control)
        mean = sum(got["control"]) / len(got["control"])
        assert mean > 5 * limits["logit_gap_mean"], (seed, mean)
    assert cell.config["lower_precision_control"] == "fp8_e4m3"


def test_probe_control_puts_its_control_in_the_stated_ones_place(monkeypatch):
    from benchmark import probe, probe_control

    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    stated = lambda: spec.load_cell(
        "tiny-longcat.agent").config["lower_precision_control"]
    seen = []
    monkeypatch.setattr(probe, "main",
                        lambda argv: seen.append((argv, stated())) or 0)
    argv = ["--workload", "tiny-longcat.agent", "--seconds", "2", "--control"]
    assert probe_control.main(["held_zero"] + argv) == 0
    assert seen == [(argv, "held_zero")] and stated() == "fp8_e4m3"
