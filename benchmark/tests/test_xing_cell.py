"""The Xing4.0-29B-A4B configuration and what PR 37 added to read it: the
file as ``spec.load_cell`` gives it, the catalog's numbers, the new mix's
lengths, the two cost functions on shapes counted by hand, the three readers
on a hand-made trace and on another cell's reading, and a whole toy run of
the harness.

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
from benchmark.costs import hc_streams, prefill_admit  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "xing4.0-29b-a4b.extract"
OWN = ("hc_mix_dev_ms", "hc_stream_roofline", "prefill_admit_mfu")


def test_the_cell_is_in_the_benchmark_and_only_added():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "xing4.0-29b-a4b", "traffic": "extract",
        "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in bench["configs"]}["xing4.0-29b-a4b"]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers"]
    assert entry["file"] == "benchmark/configs/xing4.0-29b-a4b.json"
    assert all(len(e["why"]) <= 200 for e in (entry, cells[CELL]))
    # behind the accepted cells in every list it joined
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) > order.index("glm-4.7-flash.rag")
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []) and m["name"] not in OWN:
            assert m["workloads"].index(CELL) > m["workloads"].index(
                "glm-4.7-flash.rag"), m["name"]
    own = [m for m in bench["per_layer"] if m["name"] in OWN]
    assert len(own) == 3 and all(
        m["workloads"] == [CELL] and m["moves"] == "output_tokens_per_s"
        for m in own)
    names = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]]
    assert len(names) == len(set(names))


def test_the_limits_file_names_its_readings():
    lim = json.loads((spec.HERE / "limits" / f"{CELL}.json").read_text())
    limits = lim["limits"]
    assert set(limits) == {"logit_gap_max", "logit_gap_mean",
                           "short_answers", "not_paged_engine"}
    mean, widest = (lim["readings"][k] for k in ("logit_gap_mean",
                                                 "logit_gap_max"))
    # room on both sides of the reading that decides
    assert 2 * mean["sound_runs_largest"] <= limits["logit_gap_mean"] \
        <= 0.5 * mean["control_smallest"]
    assert widest["sound_runs_largest"] < limits["logit_gap_max"]
    exact = {"short_answers": 0, "not_paged_engine": 0}
    sound = {"logit_gap_max": widest["sound_runs_largest"],
             "logit_gap_mean": mean["sound_runs_largest"], **exact}
    control = {"logit_gap_max": widest["control_smallest"],
               "logit_gap_mean": mean["control_smallest"], **exact}
    assert check.compare(sound, limits)[0] is True
    ok, lines, _ = check.compare(control, limits)
    assert ok is False
    # by one of the cell's limits, not by each
    assert [l.split(":")[0] for l in lines if l.endswith("NOT OK")] == [
        "check logit_gap_mean"]


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    assert c["n_layer"] == c["num_hidden_layers"] == 6
    assert c["n_head"] == c["num_attention_heads"] == 32
    assert c["layer_norm_epsilon"] == c["rms_norm_eps"] == 1e-6
    assert c["n_positions"] == c["deployment"]["served_length"] == 4096
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "num_nextn_predict_layers"]
    assert c["published"]["num_hidden_layers"] == 40
    assert c["published"]["first_k_dense_replace"] == 2
    assert c["published"]["num_nextn_predict_layers"] == 1
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    assert set(cell.per_layer) == {
        "prefill_pad_share", "decode_step_dev_ms.capacity", "prefill_dev_ms",
        "engine_host_ms_per_step.capacity", "idle_with_work_share.capacity",
        "decode_step_mfu.capacity", "moe_experts_dev_ms",
        "moe_decode_roofline", "mla_decode_roofline", "moe_touched_share",
        *OWN}
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    for other in ("gpt2-large.chat", "glm-4.7-flash.rag"):
        assert not set(OWN) & set(spec.load_cell(other).per_layer)


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the three in ``reduced``."""
    c = spec.load_cell(CELL).config
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 32, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    for k, v in published.items():
        assert c[k] == v, k
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_nextn_predict_layers"]) == (6, 1, 0)
    # the weights the cut keeps, by the builder's own shapes: 4.79B
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    total = sum(math.prod(s) for s, _ in shapes.values())
    assert 4.79e9 < total < 4.80e9
    maps = sum(math.prod(shapes[f"hc{k}_phi"][0]) for k in (1, 2))
    assert maps == 6 * 2 * 14336 * 24
    # the function a user deploys carries the published keys
    source = spec.plugin("models", c["builder"]).function_source(c)
    for piece in ("hc_mult=4", "hc_sinkhorn_iters=20", "hc_clamp=30.0",
                  "factor=64", "original_max_position_embeddings=4096",
                  "dense_layers=1", "depth=6"):
        assert piece in source, piece


def test_the_new_mixes_lengths():
    mix = spec.load_cell(CELL).traffic
    assert mix["clients"] == 40 and mix["check_requests"] == 24
    assert mix["block_requests"] == 40
    n = traffic.n_requests(mix, 50.0)
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 131072)
    assert len(reqs) == n and n % 40 == 0
    assert all(1100 <= len(r["prompt"]) <= 2000 and 16 <= r["max_new"] <= 48
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 131072
               for r in reqs)
    # one prefill bucket and one table width: never past 2,048 positions
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 2048
    assert max(max(r["prompt"]) for r in reqs[:40]) > 128000
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sorted(x for x, _ in sizes(reqs[:40])) == sorted(
        x for x, _ in sizes(reqs[40:80]))
    assert sorted(y for _, y in sizes(reqs[:40])) == sorted(
        y for _, y in sizes(reqs[80:120]))
    warm = traffic.warmup_requests(mix, 5, 131072)
    assert [(len(w["prompt"]), w["max_new"]) for w in warm] == [
        (1100, 4), (2000, 48)]


def test_costs_on_shapes_counted_by_hand():
    # 10 (position, sub-layer) pairs, 2 streams of 8: (2 + 2 + 1 + 1) x 8
    # values of 2 B each; 8 maps' columns: 2 x 16 x 8 + 3 x 2 x 16 + 2 x 4 x 8
    assert hc_streams.mixed(10, streams=2, hidden=8) == (
        10 * (256.0 + 32.0 + 64.0 + 32.0), 10 * 6 * 8 * 2.0)
    # the published path: 71,680 B a position and sub-layer, memory-bound
    flops, nbytes = hc_streams.mixed(1, streams=4, hidden=3584)
    assert nbytes == 71680.0
    assert hc_streams.min_seconds(flops, nbytes, PEAKS)[1] == "memory"
    # a toy admit: one dense matrix of 8 x 8 beside a head of 8 x 5, no
    # experts, no streams; 4 positions, 3 real; 1 layer, 2 heads of 2 + 2 / 2
    cfg = {"compute_dtype": "bfloat16", "num_hidden_layers": 1,
           "num_attention_heads": 2, "qk_nope_head_dim": 2,
           "qk_rope_head_dim": 2, "v_head_dim": 2, "kv_lora_rank": 4}
    shapes = {"wte": ((5, 8), "embed"), "w": ((8, 8), "kernel"),
              "lm_head": ((8, 5), "kernel")}
    flops, nbytes = prefill_admit.admit(cfg, shapes, 4, 3)
    assert flops == 2 * 64 * 4 + 2 * 40 + 2 * 2 * 6 * 10 * 1
    assert nbytes == (64 + 40) * 2 + 6 * 2 * 4
    # the published admit: 2.3 TFLOP and 10.4 GB at 2,048 positions
    c = spec.load_cell(CELL).config
    flops, nbytes = prefill_admit.admit(
        c, spec.plugin("models", c["builder"]).shapes(c), 2048, 1550)
    assert 2.2e12 < flops < 2.4e12 and 10.2e9 < nbytes < 10.6e9


def _reading(ops, modules, cfg, counters=None):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules}, wall_zero=100.0)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=[],
                          counters=counters or ({}, {}))
    return layers.Reading(cell=SimpleNamespace(config=cfg), win=win,
                          trace=trace, peaks=PEAKS)


COUNTS = ({"hc_positions_admit": 0.0, "admission_waves": 0.0,
           "prefill_tokens": 0.0, "prefill_pad_tokens": 0.0},
          {"hc_positions_admit": 4 * 64 * 4.0, "admission_waves": 4.0,
           "prefill_tokens": 4 * 50.0, "prefill_pad_tokens": 4 * 14.0})


def test_the_new_readers_on_a_hand_made_trace():
    """Two admission programs of two layers; in each, four ``hc_pre`` of 10
    us and four ``hc_post`` of 15 us; a decode program's are not counted."""
    cfg = spec.load_cell(CELL).config
    ops, modules = [], []
    for t in (1.0, 2.0):
        modules.append(("jit__prefill_admit_impl(7)", t, 0.05))
        for k in range(4):
            ops.append((f"%hc_pre.{k} custom-call", t + 0.0004 * k, 10e-6))
            ops.append((f"%hc_post.{k} custom-call",
                        t + 0.0004 * k + 0.0002, 15e-6))
    modules.append(("jit__unknown(9)", 3.0, 0.001))
    ops.append(("%hc_pre.9 custom-call", 3.0001, 10e-6))
    r = _reading(ops, modules, cfg, COUNTS)
    read = lambda name: spec.plugin("layer_metrics", name).read(r)
    assert read("hc_mix_dev_ms") == pytest.approx(0.1)
    # 64 x 4 (position, sub-layer) pairs an admit, 71,680 B each
    assert read("hc_stream_roofline") == pytest.approx(
        100 * (2 * 256 * 71680 / 819e9) / 200e-6)
    shapes = spec.plugin("models", cfg["builder"]).shapes(cfg)
    flops, nbytes = prefill_admit.admit(cfg, shapes, 64.0, 50.0)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("prefill_admit_mfu") == pytest.approx(100 * least / 0.05)


def test_each_new_reader_returns_none_on_another_cells_reading():
    """A trace with admission programs but no such kernel and no such
    counter (the parent commit, or another family), and nothing at all."""
    modules = [("jit__prefill_admit_impl(1)", 1.0, 0.001)]
    ops = [("%attn.0 custom-call", 1.0001, 10e-6)]
    old = ({"admission_waves": 1.0, "prefill_tokens": 10.0,
            "prefill_pad_tokens": 6.0},
           {"admission_waves": 5.0, "prefill_tokens": 90.0,
            "prefill_pad_tokens": 38.0})
    glm = spec.load_cell("glm-4.7-flash.rag").config
    gpt2 = spec.load_cell("gpt2-large.docs").config
    for cfg in (glm, gpt2):
        for r in (_reading(ops, modules, cfg, old), _reading([], [], cfg, old),
                  _reading(ops, modules, cfg)):
            for name in ("hc_mix_dev_ms", "hc_stream_roofline"):
                assert spec.plugin("layer_metrics", name).read(r) is None
    mfu = spec.plugin("layer_metrics", "prefill_admit_mfu").read
    assert mfu(_reading(ops, modules, gpt2, old)) is None
    assert mfu(_reading([], [], glm, old)) is None
    assert mfu(_reading(ops, modules, glm)) is None


# -- a whole run at toy size: builder, hand-over, four streams, engine, check --

DATA = Path(__file__).resolve().parent / "data_xing"


def test_a_whole_toy_run_is_correct_and_counts_its_mixing(monkeypatch,
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
    rc = run.main(["--workload", "tiny-xing.extract", "--seed",
                   str(2 ** 31 + 37), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    mean = result["check"]["logit_gap_mean"]
    assert 0.0 <= mean["value"] <= mean["limit"] == 1e-4
    assert seen["residual_streams"] == 4.0 and seen["moe_layers"] == 3.0
    assert seen["hc_positions_admit"] == 10 * (
        seen["prefill_tokens"] + seen["prefill_pad_tokens"]) > 0
    assert seen["hc_positions_step"] == 10 * 4 * seen["device_steps"] > 0
    # 2 bytes a parameter, the residual maps among them
    hc = 2 * (512 * 24 + 3 + 24)
    attn = (128 * 24 + 24 + 24 * 4 * 24 + 128 * 24 + 16 + 16 * 4 * 32
            + 64 * 128)
    params = 211 * 128 * 2 + 128 + 5 * (attn + hc + 2 * 128) + 2 * (
        3 * 128 * 96) + 3 * (128 * 8 + 8 + 8 * 3 * 128 * 32 + 3 * 128 * 32)
    assert seen["param_bytes"] == 2 * params


def test_the_toys_lower_precision_control_fails_the_mean_gap(monkeypatch):
    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    cell = spec.load_cell("tiny-xing.extract")
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
                         sampled,
                         control=cell.config["lower_precision_control"])
        mean = sum(got["control"]) / len(got["control"])
        assert mean > 5 * limits["logit_gap_mean"], (seed, mean)
