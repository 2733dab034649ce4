"""The GLM-4.7-Flash configuration and what PR 32 added to read it: the file
as ``spec.load_cell`` gives it, the catalog's numbers, the new mix's lengths,
the two cost functions on shapes counted by hand, the four readers on a
hand-made trace and on an empty one, and a whole toy run of the harness.

The cell ``glm-4.7-flash.rag`` is held to the MEAN gap of its served tokens
(``logit_gap_mean``, PR 36): its widest gap is a flipped expert choice in the
sound program and in the fp8 control alike and stays as the gross check it is.
The lower-precision control is kept here as a test at toy size.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import check, layers, reduce, spec, traffic  # noqa: E402
from benchmark.costs import mla_latent, moe_experts  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "glm-4.7-flash.rag"


def test_the_cell_is_in_the_benchmark_at_the_end_of_its_lists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "glm-4.7-flash", "traffic": "rag",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    entry = bench["configs"][-1]
    assert entry["name"] == "glm-4.7-flash" and entry["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert entry["file"] == "benchmark/configs/glm-4.7-flash.json"
    assert all(len(e["why"]) <= 200 for e in (entry, bench["workloads"][-1]))
    # appended to the capacity cells' metrics and to nothing else
    listed = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])]
    assert all(m["workloads"][-1] == CELL
               for m in bench["per_layer"] + bench["end_to_end"]
               if m["name"] in listed)
    own = [m for m in bench["per_layer"] if m["name"] in (
        "moe_experts_dev_ms", "moe_decode_roofline", "mla_decode_roofline",
        "moe_touched_share")]
    assert len(own) == 4 and all(m["workloads"] == [CELL] for m in own)
    names = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]]
    assert len(names) == len(set(names))


def test_the_limits_file_holds_the_cell_to_the_mean_gap():
    """Where experts route, a window's widest gap is a flipped expert choice
    in the sound program and in the fp8 control alike; the mean gap is the
    reading that decides (PR 36), and the widest stays as the gross check."""
    lim = json.loads((spec.HERE / "limits" / f"{CELL}.json").read_text())
    limits = lim["limits"]
    assert set(limits) == {"logit_gap_max", "logit_gap_mean",
                           "short_answers", "not_paged_engine"}
    mean, widest = (lim["readings"][k] for k in ("logit_gap_mean",
                                                 "logit_gap_max"))
    # room on both sides: 2.5 x the sound runs' largest, under half the
    # control's smallest
    assert 2.5 * mean["sound_runs_largest"] <= limits["logit_gap_mean"] \
        <= 0.5 * mean["control_smallest"]
    # the widest gap passes the control, which is why it does not decide
    assert widest["control_smallest"] < limits["logit_gap_max"]
    exact = {"short_answers": 0, "not_paged_engine": 0}
    sound = {"logit_gap_max": widest["sound_runs_largest"],
             "logit_gap_mean": mean["sound_runs_largest"], **exact}
    control = {"logit_gap_max": widest["control_smallest"],
               "logit_gap_mean": mean["control_smallest"], **exact}
    assert check.compare(sound, limits)[0] is True
    ok, lines, compared = check.compare(control, limits)
    assert ok is False
    assert [l.split(":")[0] for l in lines if l.endswith("NOT OK")] == [
        "check logit_gap_mean"]
    assert compared["logit_gap_mean"] == {
        "value": mean["control_smallest"], "limit": limits["logit_gap_mean"]}
    # a window that served nothing has no reading, and is not correct
    assert check.compare({**sound, "logit_gap_mean": None}, limits)[0] is False
    win = SimpleNamespace(engine={"paged": True, "only_decoder": True,
                                  "open": True, "page_walk_kernel": True,
                                  "snapshot_replayed": 0.0})
    got = check.serving_readings([0.0, 0.25, 0.5], win, [])
    assert got["logit_gap_max"] == 0.5 and got["logit_gap_mean"] == 0.25
    assert check.serving_readings([], win, [])["logit_gap_mean"] is None


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    assert c["n_layer"] == c["num_hidden_layers"] == 6
    assert c["n_head"] == c["num_attention_heads"] == 20
    assert c["layer_norm_epsilon"] == c["rms_norm_eps"]
    assert c["n_positions"] == c["deployment"]["served_length"] == 4096
    assert sorted(c["reduced"]) == ["num_hidden_layers",
                                    "num_nextn_predict_layers"]
    assert c["published"]["num_hidden_layers"] == 47
    assert c["published"]["num_nextn_predict_layers"] == 1
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    assert set(cell.per_layer) == {
        "prefill_pad_share", "decode_step_dev_ms.capacity", "prefill_dev_ms",
        "engine_host_ms_per_step.capacity", "idle_with_work_share.capacity",
        "decode_step_mfu.capacity", "moe_experts_dev_ms", "moe_decode_roofline", "mla_decode_roofline",
        "moe_touched_share"}
    # the builder, the reference and the readers: found by name
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    # no other cell reads the new metrics
    for other in ("gpt2-large.chat", "falcon-h1-34b.turns"):
        assert not {"moe_touched_share", "mla_decode_roofline"} & set(
            spec.load_cell(other).per_layer)


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the two in ``reduced``."""
    c = spec.load_cell(CELL).config
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    for k, v in published.items():
        assert c[k] == v, k
    assert c["num_hidden_layers"] == 6 and c["num_nextn_predict_layers"] == 0
    # the weights the cut keeps, by the builder's own shapes: 3.896B
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    count = lambda s: __import__("math").prod(s)
    total = sum(count(s) for s, _ in shapes.values())
    assert 3.89e9 < total < 3.90e9
    experts = sum(count(shapes[n][0]) for n in ("e_gate", "e_up", "e_down"))
    assert experts == 5 * 64 * 3 * 2048 * 1536


def test_the_new_mixes_lengths():
    mix = spec.load_cell(CELL).traffic
    assert mix["clients"] == 40 and mix["drain_seconds"] == 40
    assert mix["check_requests"] == 8 and mix["block_requests"] == 40
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 154880)
    assert len(reqs) == 800 and reqs[:3] == traffic.requests(
        mix, 2 ** 31 + 99, 50.0, 154880)[:3]
    assert all(1100 <= len(r["prompt"]) <= 2000
               and 128 <= r["max_new"] <= 384
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 154880
               for r in reqs)
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 2384
    # token ids over the whole vocabulary, not a slice of it
    assert max(max(r["prompt"]) for r in reqs[:40]) > 150000
    # every block of 40 holds the same lengths
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sorted(x for x, _ in sizes(reqs[:40])) == sorted(
        x for x, _ in sizes(reqs[40:80]))
    assert sorted(y for _, y in sizes(reqs[:40])) == sorted(
        y for _, y in sizes(reqs[80:120]))
    # the warm-up reaches the one prefill bucket and both table widths
    warm = traffic.warmup_requests(mix, 5, 154880)
    assert [len(w["prompt"]) for w in warm] == [1100, 2000]
    assert len(warm[1]["prompt"]) + warm[1]["max_new"] - 1 > 2048


def test_costs_on_shapes_counted_by_hand():
    # 3 experts touched by 5 assignments, hidden 8, width 4: an expert is
    # 3 * 8 * 4 = 96 weights of 2 B; an assignment moves 2 * (8 + 4) values
    # of 2 B and multiplies one row by the three matrices, 2 * 96 operations
    assert moe_experts.decode_steps(3, 5, hidden=8, width=4) == (
        960.0, 3 * 192.0 + 5 * 48.0)
    # the published layer: 18.87 MB an expert, memory-bound at 128 rows
    flops, nbytes = moe_experts.decode_steps(
        55.5 * 5, 128 * 5, hidden=2048, width=1536)
    assert 5.2e9 < nbytes < 5.3e9
    assert moe_experts.min_seconds(flops, nbytes, PEAKS)[1] == "memory"
    # 10 live tokens, 3 layers, 4 heads, latent 24 with 16 of value:
    # 24 * 2 B a token and layer; 2 * 4 * (24 + 16) operations
    assert mla_latent.decode_step(10, layers=3, heads=4, latent=24,
                                  value=16) == (9600.0, 1440.0)
    # the published walk: 1,152 B and 43,520 operations a token and layer
    flops, nbytes = mla_latent.decode_step(1, layers=1, heads=20, latent=576,
                                           value=512)
    assert (flops, nbytes) == (43520.0, 1152.0)
    # 37.8 operations a byte against the chip's 240: still memory-bound
    assert mla_latent.min_seconds(flops, nbytes, PEAKS)[1] == "memory"


def _reading(ops, modules, cfg, records=(), counters=None, wall_zero=100.0):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules},
        wall_zero=wall_zero)
    cell = SimpleNamespace(config=cfg)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=list(records),
                          counters=counters or ({}, {}))
    return layers.Reading(cell=cell, win=win, trace=trace, peaks=PEAKS)


CFG = {"n_layer": 2, "num_attention_heads": 4, "kv_lora_rank": 16,
       "qk_rope_head_dim": 8, "hidden_size": 64,
       "moe_intermediate_size": 32, "n_routed_experts": 8}
COUNTS = ({"moe_experts_touched": 100.0, "moe_assignments": 200.0,
           "device_steps": 10.0, "moe_layers": 1.0},
          {"moe_experts_touched": 160.0, "moe_assignments": 360.0,
           "device_steps": 30.0, "moe_layers": 1.0})


def test_the_new_readers_on_a_hand_made_trace():
    """Two decode programs of one step over two layers (one of them an
    expert layer); in each, the page walk takes 2 x 10 us and the expert
    layer's two grouped products 30 + 20 us."""
    ops, modules = [], []
    for t in (1.0, 2.0):
        modules.append(("jit__unknown(123)", t, 0.001))
        for layer in range(2):
            at = t + 0.0001 + 0.0004 * layer
            ops.append((f"%attn.{layer} custom-call", at, 10e-6))
        ops.append(("%moe_experts.0 custom-call", t + 0.0006, 30e-6))
        ops.append(("%moe_experts.1 custom-call", t + 0.0007, 20e-6))
    records = [{"error": None, "first": 0.0, "last": 10.0,
                "prompt_tokens": 90, "tokens": list(range(100))}]
    r = _reading(ops, modules, CFG, records, COUNTS)
    read = lambda name: spec.plugin("layer_metrics", name).read(r)
    assert read("moe_experts_dev_ms") == pytest.approx(0.050)
    # over the window 60 experts touched and 160 assignments in 20 steps:
    # 3 and 8 a step, 2 traced steps; an expert is 3 * 64 * 32 * 2 B
    nbytes = 2 * (3 * 12288 + 8 * 2 * 96 * 2)
    assert read("moe_decode_roofline") == pytest.approx(
        100 * (nbytes / 819e9) / 100e-6)
    assert read("moe_touched_share") == pytest.approx(100 * 3 / 8)
    # depth 90 + 10 tokens/s at the two steps' middles; 24 * 2 B * 2 layers
    depth = (90 + 10 * 1.0005) + (90 + 10 * 2.0005)
    assert read("mla_decode_roofline") == pytest.approx(
        100 * (96 * depth / 819e9) / 40e-6)


def test_each_new_reader_returns_none_on_an_empty_reading():
    # a trace with the decode programs but no such kernel and no such
    # counter (the parent commit, or another family), and nothing at all
    ops = [("%attn.0 custom-call", 1.0001, 10e-6),
           ("%attn.1 custom-call", 1.0005, 10e-6)]
    old = ({"device_steps": 1.0}, {"device_steps": 5.0})
    parent = _reading(ops, [("jit__unknown(1)", 1.0, 0.001)], CFG,
                      counters=old)
    empty = _reading([], [], CFG, counters=old)
    gpt2 = _reading(ops, [("jit__unknown(1)", 1.0, 0.001)],
                    {"n_layer": 2, "n_head": 4, "n_embd": 64}, counters=old)
    for name in ("moe_experts_dev_ms", "moe_decode_roofline",
                 "moe_touched_share"):
        for r in (parent, empty, gpt2):
            assert spec.plugin("layer_metrics", name).read(r) is None
    mla = spec.plugin("layer_metrics", "mla_decode_roofline").read
    assert mla(empty) is None and mla(gpt2) is None


# -- a whole run at toy size: builder, hand-over, latent pages, engine, check --

DATA = Path(__file__).resolve().parent / "data_glm"


def test_a_whole_toy_run_is_correct_and_counts_its_experts(monkeypatch,
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
    rc = run.main(["--workload", "tiny-glm.rag", "--seed",
                   str(2 ** 31 + 32), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert any(l.startswith("check logit_gap_max:")
               for l in io.err.splitlines())
    mean = result["check"]["logit_gap_mean"]
    assert 0.0 <= mean["value"] <= mean["limit"] == 1e-4
    assert seen["kv_latent_width"] == 24.0 and seen["moe_layers"] == 2.0
    assert seen["moe_experts_touched"] > 0
    assert seen["moe_assignments"] % (2 * 2) == 0
    # 2 bytes a parameter: the builder hands bfloat16 leaves over and the
    # store gives them back as bfloat16
    attn = 64 * 24 + 24 + 24 * 64 + 64 * 24 + 16 + 16 * 4 * 24 + 64 * 64
    params = 211 * 64 * 2 + 64 + 3 * (attn + 2 * 64) + 3 * 64 * 96 + 2 * (
        64 * 8 + 8 + 8 * 3 * 64 * 32 + 3 * 64 * 32)
    assert seen["param_bytes"] == 2 * params
    assert seen["expert_param_bytes"] == 2 * 2 * 8 * 3 * 64 * 32


def test_the_toys_lower_precision_control_fails_the_mean_gap(monkeypatch):
    """The reference in bfloat16 (the precision under the toy's float32) put
    in the program's place: over 480 positions a seed, the token it puts
    first lies below the float32 reference's best by more than the mean's
    limit on average."""
    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    cell = spec.load_cell("tiny-glm.rag")
    builder = spec.plugin("models", cell.config["builder"])
    limits = check.limits_for(cell.name)
    vocab = cell.config["vocab_size"]
    for seed in (4, 5, 6):
        weights = builder.init_weights(cell.config, seed)
        reqs = traffic.requests(cell.traffic, seed, 2.0, vocab)[:60]
        toks = traffic.rng(seed, "check")
        sampled = [{"id": r["id"], "tokens": toks.integers(
            1, vocab, size=r["max_new"]).tolist()} for r in reqs]
        got = check.gaps(cell, weights, {r["id"]: r["prompt"] for r in reqs},
                         sampled,
                         control=cell.config["lower_precision_control"])
        mean = sum(got["control"]) / len(got["control"])
        assert mean > 5 * limits["logit_gap_mean"], (seed, mean)
