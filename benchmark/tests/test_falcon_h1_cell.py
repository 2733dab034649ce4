"""The Falcon-H1 configuration and what PR 27 added to read it: the file as
``spec.load_cell`` gives it, the harness's aliases against the published
keys, the two cost functions on shapes counted by hand, the three readers on
a hand-made trace and on an empty one.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import layers, reduce, spec, traffic  # noqa: E402
from benchmark.costs import paged_attention, paged_attention_gqa, ssm_state  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell("falcon-h1-34b.turns")
    c = cell.config
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    assert c["n_layer"] == c["num_hidden_layers"] == 6
    assert c["n_head"] == c["num_attention_heads"] == 20
    assert c["layer_norm_epsilon"] == c["rms_norm_eps"]
    assert c["n_positions"] == c["deployment"]["served_length"] == 1024
    assert c["mamba_d_ssm"] == c["mamba_n_heads"] * c["mamba_d_head"] == 4096
    assert sorted(c["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert c["published"]["num_hidden_layers"] == 72
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    assert {"ssm_update_dev_ms", "ssm_decode_roofline",
            "gqa_decode_roofline", "decode_step_dev_ms.capacity",
            "prefill_dev_ms", "prefill_pad_share"} <= set(cell.per_layer)
    assert "paged_decode_roofline" not in cell.per_layer
    # the builder, the reference and the limits are found by name
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    assert json.loads((spec.HERE / "limits" / f"{cell.name}.json")
                      .read_text())["limits"]["logit_gap_max"] > 0


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the two in ``reduced``."""
    c = spec.load_cell("falcon-h1-34b.turns").config
    published = {
        "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_size": 5120, "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
        "mamba_n_heads": 32, "max_position_embeddings": 262144,
        "mlp_expansion_factor": 8, "num_attention_heads": 20,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.08838834764831845}
    for k, v in published.items():
        assert c[k] == v, k
    assert c["ssm_multipliers"] == [0.3535533905932738, 0.25,
                                    0.1767766952966369, 0.5,
                                    0.3535533905932738]
    assert c["mlp_multipliers"] == [0.1767766952966369, 0.011160714285714284]


def test_the_new_mixes_lengths():
    mix = spec.load_cell("falcon-h1-34b.turns").traffic
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 32640)
    assert len(reqs) == 2000 and reqs == traffic.requests(
        mix, 2 ** 31 + 99, 50.0, 32640)
    assert all(72 <= len(r["prompt"]) <= 120 and 112 <= r["max_new"] <= 288
               and len(r["prompt"]) + r["max_new"] <= 408
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 32640
               for r in reqs)
    # every block of 40 holds the same lengths
    sizes = lambda rs: sorted(r["max_new"] for r in rs)
    assert sizes(reqs[:40]) == sizes(reqs[40:80])
    docs = spec.load_cell("gpt2-large.docs")
    assert docs.config["n_layer"] == 36 and docs.traffic["clients"] == 16
    assert "output_tokens_per_s" in docs.end_to_end


def test_costs_on_shapes_counted_by_hand():
    # one row, one layer, 2 heads of 4 channels, state 8, one group:
    # state 2*4*8 = 64 elements in and out, x.dt, decay and y 8 each,
    # B and C 8 each -> (128 + 24 + 16) * 4 bytes; 5 operations an element
    assert ssm_state.decode_step(1, layers=1, heads=2, head_dim=4, state=8,
                                 groups=1) == (320.0, 672.0)
    # the published layer, 32 rows, 6 layers: 1.61 GB of state traffic
    flops, nbytes = ssm_state.decode_step(
        32, layers=6, heads=32, head_dim=128, state=256, groups=2)
    assert nbytes == 4.0 * (2 * 1048576 + 3 * 4096 + 2 * 512) * 32 * 6
    assert flops * 8 < nbytes * 197e12 / 819e9       # memory-bound
    # 10 live tokens, 3 layers, 6 query heads on 2 K/V heads of 16:
    # scores + sum 4 * 6 * 16 * 10 * 3; K and V 2 * 2 * 16 * 2 B * 10 * 3
    assert paged_attention_gqa.decode_step(
        10, layers=3, q_heads=6, kv_heads=2, head_dim=16) == (11520.0, 3840.0)
    # one K/V head a query head: the count the accepted kernel's reader uses
    assert paged_attention_gqa.decode_step(
        7, layers=2, q_heads=4, kv_heads=4, head_dim=8) == \
        paged_attention.decode_step(7, layers=2, heads=4, head_dim=8)


def _reading(ops, modules, cfg, records=(), wall_zero=100.0):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules},
        wall_zero=wall_zero)
    cell = SimpleNamespace(config=cfg)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=list(records))
    return layers.Reading(cell=cell, win=win, trace=trace, peaks=PEAKS)


CFG = {"n_layer": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 128, "mamba_n_heads": 2, "mamba_d_head": 128,
       "mamba_d_state": 256, "mamba_n_groups": 1,
       "deployment": {"serving_slots": 4}}


def test_the_new_readers_on_a_hand_made_trace():
    """Two decode programs of one step over two layers; in each, the page
    walk takes 2 x 10 us and the state update 2 x 40 us."""
    ops, modules = [], []
    for t in (1.0, 2.0):
        modules.append(("jit__unknown(123)", t, 0.001))
        for layer in range(2):
            at = t + 0.0001 + 0.0004 * layer
            ops.append((f"%attn.{layer} custom-call", at, 10e-6))
            ops.append((f"%ssm_update.{layer} custom-call", at + 0.0001,
                        40e-6))
    # one request decoding through the whole trace, 100 tokens deep at 1 s
    records = [{"error": None, "first": 0.0, "last": 10.0,
                "prompt_tokens": 90, "tokens": list(range(100))}]
    r = _reading(ops, modules, CFG, records)
    read = lambda name: spec.plugin("layer_metrics", name).read(r)
    assert read("ssm_update_dev_ms") == pytest.approx(0.080)
    # 4 rows x 2 layers x 2 steps of (2 * 65536 + 3 * 256 + 2 * 256) * 4 B
    # = 8.47 MB at 819 GB/s = 10.34 us over 160 us of kernel
    least = 4 * 2 * 2 * (2 * 65536 + 5 * 256) * 4 / 819e9
    assert read("ssm_decode_roofline") == pytest.approx(
        100 * least / 160e-6)
    # depth 90 + 10 tokens/s: 100.005 and 110.005 tokens at the two steps'
    # middles; 2 * 2 K/V heads * 128 * 2 B * 2 layers a token
    depth = (90 + 10 * 1.0005) + (90 + 10 * 2.0005)
    assert read("gqa_decode_roofline") == pytest.approx(
        100 * (2048 * depth / 819e9) / 40e-6)


def test_each_new_reader_returns_none_on_an_empty_reading():
    # a trace with the decode programs but no such kernel (the parent
    # commit, or another family), and a trace with nothing at all
    ops = [("%attn.0 custom-call", 1.0001, 10e-6),
           ("%attn.1 custom-call", 1.0005, 10e-6)]
    no_kernel = _reading(ops, [("jit__unknown(1)", 1.0, 0.001)], CFG)
    empty = _reading([], [], CFG)
    gpt2 = _reading(ops, [("jit__unknown(1)", 1.0, 0.001)],
                    {"n_layer": 2, "n_head": 4, "n_embd": 64})
    for name in ("ssm_update_dev_ms", "ssm_decode_roofline"):
        assert spec.plugin("layer_metrics", name).read(no_kernel) is None
        assert spec.plugin("layer_metrics", name).read(empty) is None
    gqa = spec.plugin("layer_metrics", "gqa_decode_roofline").read
    assert gqa(empty) is None and gqa(gpt2) is None


# -- a whole run at toy size: builder, hand-over, param_dtype, engine, check ----

DATA = Path(__file__).resolve().parent / "data_falcon"


def test_a_whole_toy_run_is_correct_and_holds_bfloat16(monkeypatch, capsys):
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
    rc = run.main(["--workload", "tiny-falcon.turns", "--seed",
                   str(2 ** 31 + 27), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert any(l.startswith("check logit_gap_max:")
               for l in io.err.splitlines())
    assert seen["recurrent_layers"] == 2.0
    # 2 bytes a parameter: the checkpoint's float32 files were cast
    params = 211 * 64 * 2 + 64 + 2 * (
        64 * 2 + 64 * (128 + 64 + 64) + 128 * 64 + 64 * (32 + 96 + 4)
        + 4 * 96 + 96 + 3 * 4 + 32 + 32 * 64 + 3 * 64 * 96)
    assert seen["param_bytes"] == 2 * params


def test_step_budget_splits_operations_by_program():
    from benchmark import step_budget

    ops = [("%attn.0 custom-call", 1.0001, 10e-6),
           ("%ssm_update.0 custom-call", 1.0002, 40e-6),
           ("%fusion.7 fusion", 2.0001, 300e-6),
           ("%attn.1 custom-call", 2.0005, 100e-6),
           ("%copy.3 copy", 3.0, 1e-3)]               # in no program
    trace = reduce.Trace(lines={
        (PLANE, reduce.OPS_LINE): ops,
        (PLANE, reduce.MODULES_LINE): [
            ("jit__unknown(1)", 1.0, 0.001),
            ("jit__prefill_admit_impl(2)", 2.0, 0.002)]})
    step = step_budget.by_program(trace, PLANE, ("jit__unknown(",))
    assert step["executions"] == 1 and step["median_ms"] == pytest.approx(1.0)
    assert step["ops_ms_per_execution"] == [
        ["%ssm_update custom-call", pytest.approx(0.04)],
        ["%attn custom-call", pytest.approx(0.01)]]
    pre = step_budget.by_program(trace, PLANE,
                                 ("jit__prefill_admit_impl(",))
    assert [k for k, _ in pre["ops_ms_per_execution"]] == [
        "%fusion fusion", "%attn custom-call"]
    assert step_budget.by_program(trace, PLANE, ("jit_none(",)) == {
        "executions": 0}
