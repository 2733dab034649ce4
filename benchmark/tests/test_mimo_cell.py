"""The MiMo-V2-Flash configuration and what PR 44 added to read it: the file
as ``spec.load_cell`` gives it, the catalog's numbers, the new mix's lengths,
the family's step costs on shapes counted by hand, the five new readers on a
hand-made trace and counters and on another cell's, and a whole toy run of
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
from benchmark.costs import decode_step_mimo  # noqa: E402
from benchmark.costs import paged_attention_window  # noqa: E402
from benchmark.layer_metrics import _kinds  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "mimo-v2-flash.longdoc"
OWN = ("window_attn_dev_ms", "window_decode_roofline", "full_decode_roofline",
       "window_live_chunk_share", "window_pages_share")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_cell_is_in_the_benchmark_and_only_added():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) >= 8 and all(w["chips"] == 1 for w in cells.values())
    assert cells[CELL] == {
        "name": CELL, "config": "mimo-v2-flash", "traffic": "longdoc",
        "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in bench["configs"]}["mimo-v2-flash"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/mimo-v2-flash.json"
    assert all(len(e["why"]) <= 200 for e in (entry, cells[CELL]))
    # behind the accepted cells in every list it joined
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) > order.index("longcat-flash-omni.agent")
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []) and m["name"] not in OWN:
            assert m["workloads"][-1] == CELL or m["workloads"].index(
                CELL) > m["workloads"].index("gpt2-xl.docs"), m["name"]
    own = [m for m in bench["per_layer"] if m["name"] in OWN]
    assert len(own) == 5 and all(
        m["workloads"] == [CELL] and m["moves"] == "output_tokens_per_s"
        for m in own)
    assert {m["name"]: m["layer"] for m in own} == {
        "window_attn_dev_ms": "Model step",
        "window_decode_roofline": "Kernels",
        "full_decode_roofline": "Kernels",
        "window_live_chunk_share": "Kernels",
        "window_pages_share": "Serving control"}
    # one kind of K/V layer; every choice counted as computed here
    by = {m["name"]: m for m in bench["per_layer"]}
    assert CELL not in by["gqa_decode_roofline"]["workloads"]
    assert CELL not in by["prefill_admit_mfu"]["workloads"]
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
    # on both sides: three times over the sound runs' largest and under the
    # stated control's smallest, five times under the smallest reading of a
    # mechanism left out; each control comes out as not correct by it. The
    # widest gap is a gross check that every control passes
    mean = r["logit_gap_mean"]
    assert 3 * sound["logit_gap_mean"] <= limits["logit_gap_mean"]
    assert limits["logit_gap_mean"] <= mean["control_smallest"] / 3
    assert limits["logit_gap_mean"] <= mean["mechanism_controls_smallest"] / 5
    for low in (mean["control_smallest"],
                mean["mechanism_controls_smallest"]):
        assert check.compare({"logit_gap_mean": low,
                              "logit_gap_max": sound["logit_gap_max"],
                              **exact}, limits)[0] is False
    assert 4 * sound["logit_gap_max"] <= limits["logit_gap_max"]
    assert r["logit_gap_max"]["control_smallest"] < limits["logit_gap_max"]


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    assert c["n_layer"] == c["num_hidden_layers"] == 7
    assert c["n_head"] == c["num_attention_heads"] == 64
    assert c["layer_norm_epsilon"] == c["layernorm_epsilon"] == 1e-5
    assert c["n_positions"] == c["deployment"]["served_length"] == 4608
    assert c["reduced"] == REDUCED
    assert c["published"]["num_hidden_layers"] == 48
    assert c["published"]["n_routed_experts"] == 256
    assert c["published"]["vocab_size"] == 152576
    assert "16 TPU v5e chips" in c["deployment"]["stands_for"]
    assert "8 pipeline stages" in c["deployment"]["stands_for"]
    assert c["deployment"]["serving_slots"] == 64
    assert c["deployment"]["serving_prefix_cache"] is False
    for key in ("window", "rope", "sink", "router", "init"):
        assert c["assumed"][key]
    assert any("multi-token-prediction" in d for d in c["departures"])
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    # a subset, not the exact set: a later PR may add a metric to every cell
    assert set(cell.per_layer) >= {
        "prefill_pad_share", "decode_step_dev_ms.capacity", "prefill_dev_ms",
        "engine_host_ms_per_step.capacity", "idle_with_work_share.capacity",
        "decode_step_mfu.capacity", "moe_experts_dev_ms",
        "moe_decode_roofline", "moe_touched_share", "moe_held_share",
        "admit_attn_dev_ms", "setup_restore_s", "setup_build_s",
        "setup_trace_s", "setup_lower_s", "setup_backend_s",
        "setup_cache_hit_share", *OWN}
    assert "mla_decode_roofline" not in cell.per_layer
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    assert spec.plugin("costs", c["step_costs"]).decode_step
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    for other in ("gpt2-large.chat", "longcat-flash-omni.agent"):
        assert not set(OWN) & set(spec.load_cell(other).per_layer)


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the three in ``reduced``, the two patterns
    whole."""
    c = spec.load_cell(CELL).config
    if CATALOG.exists():
        published = next(
            e for e in map(json.loads, CATALOG.read_text().splitlines())
            if e["name"] == "MiMo-V2-Flash")
        assert c["source"] == published["source_url"]
        for k, v in published["config"].items():
            if k not in REDUCED:
                assert c[k] == v, k
    assert len(c["hybrid_layer_pattern"]) == len(c["moe_layer_freq"]) == 48
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (7, 16, 19072) == (
                7, 256 // 16, 152576 // 8)
    builder = spec.plugin("models", c["builder"])
    assert builder.layers(c) == (
        (False, False), (True, True), (True, True), (True, True),
        (True, True), (False, True), (True, True))
    # the weights the cut keeps, by the builder's own shapes: 3.430B
    shapes = builder.shapes(c)
    total = sum(math.prod(s) for s, _ in shapes.values())
    assert total == 3_429_955_392
    held = decode_step_mimo.routed_expert_elements(shapes)
    assert held == 6 * 16 * 3 * 4096 * 2048
    window_layer = (4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096)
    assert window_layer == 94_371_840
    assert shapes["w_r"][0] == (6, 4096, 256)
    assert shapes["s_sink"] == ((5, 64), "sink")
    source = builder.function_source(c)
    for piece in ("depth=7", "num_heads=64", "head_dim=192",
                  "v_head_dim=128", "partial_rotary_factor=0.334",
                  "value_scale=0.707", "AttnKind(num_kv_heads=4",
                  "rope_theta=5000000.0", "AttnKind(num_kv_heads=8",
                  "rope_theta=10000.0", "window=128, sink=True",
                  "attn_pattern=(0, 1, 1, 1, 1, 0, 1)", "dense_layers=1",
                  "n_routed_experts=256", "held=(0, 16)",
                  "num_experts_per_tok=8", "scoring_func=\"sigmoid\"",
                  "norm_topk_prob=True", "n_shared_experts=0",
                  "mlp_dim=16384", "max_len=4608"):
        assert piece in source, piece


def test_the_new_mixes_lengths():
    mix = spec.load_cell(CELL).traffic
    assert mix["clients"] == 80 and mix["check_requests"] == 8
    assert mix["block_requests"] == 80
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 3000,
                                    "hi": 4000}
    assert mix["new_tokens"] == {"dist": "log_uniform", "lo": 128, "hi": 384}
    assert mix["requests_per_second_ceiling"] == 16
    assert mix["drain_seconds"] == 40
    n = traffic.n_requests(mix, 50.0)
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 19072)
    assert len(reqs) == n == 800 and n % 80 == 0
    assert all(3000 <= len(r["prompt"]) <= 4000
               and 128 <= r["max_new"] <= 384
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 19072
               for r in reqs)
    # one prefill bucket; table widths of 256 and 288 pages
    assert 2048 < min(len(r["prompt"]) for r in reqs)
    assert max(len(r["prompt"]) for r in reqs) <= 4096
    assert 4096 < max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 4608
    assert 225 < sum(r["max_new"] for r in reqs) / n < 240
    sizes = lambda rs, i: sorted((len(r["prompt"]), r["max_new"])[i]
                                 for r in rs)
    assert sizes(reqs[:80], 0) == sizes(reqs[80:160], 0)
    assert sizes(reqs[:80], 1) == sizes(reqs[160:240], 1)
    warm = traffic.warmup_requests(mix, 5, 19072)
    assert [(len(w["prompt"]), w["max_new"]) for w in warm] == [
        (3000, 4), (4000, 128)]
    assert 3000 + 4 <= 4096 < 4000 + 128           # the widths they reach


def test_step_costs_on_shapes_counted_by_hand():
    # a toy: one dense matrix of 8 x 8 beside a head of 8 x 5 and 3 held
    # experts of 3 x 8 x 4 in each of 2 expert layers; 3 layers: full,
    # window, window; 4 query heads of 6 + 2 on 1 and 2 K/V heads, window 5
    cfg = {"compute_dtype": "bfloat16", "num_hidden_layers": 3,
           "hybrid_layer_pattern": [0, 1, 1, 1, 0], "hidden_size": 8,
           "moe_intermediate_size": 4, "num_attention_heads": 4,
           "head_dim": 6, "v_head_dim": 2, "num_key_value_heads": 1,
           "swa_num_key_value_heads": 2, "sliding_window": 5}
    shapes = {"wte": ((5, 8), "embed"), "w": ((8, 8), "kernel"),
              "lm_head": ((8, 5), "kernel"),
              "e_gate": ((2, 3, 8, 4), "kernel"),
              "e_up": ((2, 3, 8, 4), "kernel"),
              "e_down": ((2, 3, 4, 8), "kernel")}
    assert decode_step_mimo.layer_kinds(cfg) == (1, 2)
    assert decode_step_mimo.routed_expert_elements(shapes) == 2 * 3 * 96
    assert decode_step_mimo.weight_elements(shapes) == 64 + 40
    # 3 rows 30 deep between them: the full layer sees 30 keys, a window
    # layer 3 x min(10, 5) = 15
    assert paged_attention_window.seen_keys(3.0, 30.0, 5) == 15.0
    assert paged_attention_window.seen_keys(3.0, 9.0, 5) == 9.0
    assert paged_attention_window.seen_keys(3.0, 30.0) == 30.0
    kinds = decode_step_mimo.cache(cfg, 3.0, 30.0)
    assert kinds["full"] == (2 * 4 * 8 * 30 * 1, 1 * 8 * 2 * 30 * 1)
    assert kinds["window"] == (2 * 4 * 8 * 15 * 2, 2 * 8 * 2 * 15 * 2)
    flops, nbytes = decode_step_mimo.decode_step(
        cfg, shapes, rows=3.0, depth_tokens=30.0, touched=2.0,
        assignments=5.0)
    dense = 64 + 40
    assert flops == (2 * dense * 3 + kinds["full"][0] + kinds["window"][0]
                     + 2 * 96 * 5)
    assert nbytes == (dense * 2 + kinds["full"][1] + kinds["window"][1]
                      + 96 * 2 * 2 + 2 * (8 + 4) * 2 * 5)
    # the published step at 64 rows 3,800 deep, 83 experts touched by 192
    # held assignments (6 layers): 1.87 GB of weights outside the experts,
    # 4.2 GB of touched experts, 1.25 GB of full-layer keys and values, 0.21
    # GB of window ones; bytes bound it
    c = spec.load_cell(CELL).config
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    assert 1.86e9 < 2 * decode_step_mimo.weight_elements(shapes) < 1.88e9
    kinds = decode_step_mimo.cache(c, 64.0, 64 * 3800.0)
    assert 1.24e9 < kinds["full"][1] < 1.25e9
    assert 0.20e9 < kinds["window"][1] < 0.22e9
    flops, nbytes = decode_step_mimo.decode_step(
        c, shapes, 64.0, 64 * 3800.0, touched=83.4, assignments=192.0)
    assert 7.4e9 < nbytes < 7.6e9
    least, bound = decode_step_mimo.min_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and 0.0090 < least < 0.0093


def _reading(ops, modules, cfg, counters=None, records=()):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules}, wall_zero=100.0)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=list(records),
                          counters=counters or ({}, {}))
    return layers.Reading(cell=SimpleNamespace(config=cfg), win=win,
                          trace=trace, peaks=PEAKS)


def test_the_trace_readers_tell_the_kinds_apart_by_order():
    """Two steps of seven ``%attn`` calls each in one program execution:
    the second to fifth and the seventh of a step are window layers'."""
    cfg = spec.load_cell(CELL).config
    assert _kinds.pattern(cfg) == [False, True, True, True, True, False, True]
    modules = [("jit__unknown(3)", 1.0, 0.040)]
    ops = []
    for step in range(2):
        for k in range(7):
            full = k in (0, 5)
            ops.append((f"%attn.{7 * step + k} custom-call",
                        1.0 + 0.015 * step + 0.002 * k,
                        0.0008 if full else 0.0001))
    # 64 rows decoding at the execution's middle, 3,800 deep each
    records = [{"error": None, "first": 0.5, "last": 1.54,
                "prompt_tokens": 3700, "tokens": [0] * 200}] * 64
    r = _reading(ops, modules, cfg, records=records)
    runs = _kinds.step_runs(r)
    assert [(steps, round(w, 6), round(f, 6))
            for _, _, steps, w, f in runs] == [(2, 0.001, 0.0032)]
    read = lambda name: spec.plugin("layer_metrics", name).read(r)
    assert read("window_attn_dev_ms") == pytest.approx(0.5)
    rows, depth = 64.0, 64 * 3800.0
    kinds = decode_step_mimo.cache(cfg, rows, depth)
    want = lambda kind, seconds: 100.0 * 2 * (
        kinds[kind][1] / PEAKS["hbm_bytes_per_s"]) / seconds
    assert read("full_decode_roofline") == pytest.approx(
        want("full", 0.0032), rel=1e-6)
    assert read("window_decode_roofline") == pytest.approx(
        want("window", 0.001), rel=1e-6)
    assert 90 < read("full_decode_roofline") < 100
    # a count over 105% of the roofline is refused, not clipped
    fast = [(n, s, d / 2) for n, s, d in ops]
    with pytest.raises(Exception):
        spec.plugin("layer_metrics", "full_decode_roofline").read(
            _reading(fast, modules, cfg, records=records))
    # calls that are no whole steps of seven: nothing to read
    assert _kinds.step_runs(_reading(ops[:-1], modules, cfg)) == []


def test_the_counter_readers_on_hand_made_counters():
    cfg = spec.load_cell(CELL).config
    c0 = {"walk_chunks_live_window": 10.0, "walk_chunks_grid_window": 100.0,
          "window_pages_live": 5.0, "window_pages_held": 50.0}
    c1 = {"walk_chunks_live_window": 10.0 + 300.0,
          "walk_chunks_grid_window": 100.0 + 320.0,
          "window_pages_live": 5.0 + 2700.0,
          "window_pages_held": 50.0 + 3000.0}
    r = _reading([], [], cfg, (c0, c1))
    read = lambda name: spec.plugin("layer_metrics", name).read(r)
    assert read("window_live_chunk_share") == pytest.approx(100 * 300 / 320)
    assert read("window_pages_share") == pytest.approx(90.0)
    for name in ("window_live_chunk_share", "window_pages_share"):
        assert spec.plugin("layer_metrics", name).read(
            _reading([], [], cfg, (c1, c1))) is None


def test_each_new_reader_returns_none_on_a_program_without_the_kinds():
    """The parent commit's telemetry (no ``*_window`` counters), another
    family's cell (no pattern in its file), and nothing at all."""
    old = ({"walk_chunks_live": 0.0, "walk_chunks_grid": 0.0},
           {"walk_chunks_live": 128.0, "walk_chunks_grid": 512.0})
    modules = [("jit__unknown(3)", 1.0, 0.030)]
    ops = [(f"%attn.{k} custom-call", 1.0 + 0.003 * k, 0.0004)
           for k in range(6)]
    for cfg in (spec.load_cell("falcon-h1-34b.turns").config,
                spec.load_cell("gpt2-large.docs").config):
        for r in (_reading(ops, modules, cfg, old), _reading([], [], cfg)):
            for name in OWN:
                assert spec.plugin("layer_metrics", name).read(r) is None
    mine = spec.load_cell(CELL).config
    for name in OWN:
        assert spec.plugin("layer_metrics", name).read(
            _reading([], [], mine, old)) is None


# -- a whole run at toy size: builder, hand-over, both leases, check ---------

DATA = Path(__file__).resolve().parent / "data_mimo"


def test_a_whole_toy_run_is_correct_and_leases_both_kinds(monkeypatch,
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
    rc = run.main(["--workload", "tiny-mimo.longdoc", "--seed",
                   str(2 ** 31 + 44), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    mean = result["check"]["logit_gap_mean"]
    assert 0.0 <= mean["value"] <= mean["limit"] == 1e-4
    assert seen["window_layers"] == 5.0 and seen["full_layers"] == 2.0
    assert seen["moe_layers"] == 6.0 and seen["moe_experts_held"] == 4.0
    # a ring of 8 / 4 + 2 pages a row, 4 rows
    assert seen["window_ring_pages"] == 4.0
    assert seen["window_pages_total"] == 16.0
    assert (seen["moe_assignments"] + seen["moe_assignments_absent"]
            == seen["live_slot_steps"] * 4 * 6) and seen[
                "moe_assignments"] > 0
    # 2 bytes a parameter, by the builder's own shapes
    cfg = json.loads((DATA / "configs/tiny-mimo.json").read_text())
    shapes = spec.plugin("models", "mimo_v2").shapes(cfg)
    assert seen["param_bytes"] == 2 * sum(
        math.prod(s) for s, _ in shapes.values())


@pytest.mark.parametrize("control", ["fp8_e4m3", "window_off", "sink_off",
                                     "held_zero"])
def test_the_toys_controls_fail_the_mean_gap(monkeypatch, control):
    """The stated control, and the three mechanisms left out (a window
    layer that attends to everything, no sink, no held experts:
    probe_control.py)."""
    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    cell = spec.load_cell("tiny-mimo.longdoc")
    builder = spec.plugin("models", cell.config["builder"])
    limits = check.limits_for(cell.name)
    vocab = cell.config["vocab_size"]
    for seed in (4, 5):
        weights = builder.init_weights(cell.config, seed)
        reqs = traffic.requests(cell.traffic, seed, 2.0, vocab)[:40]
        toks = traffic.rng(seed, "check")
        sampled = [{"id": r["id"], "tokens": toks.integers(
            1, vocab, size=r["max_new"]).tolist()} for r in reqs]
        got = check.gaps(cell, weights, {r["id"]: r["prompt"] for r in reqs},
                         sampled, control=control)
        mean = sum(got["control"]) / len(got["control"])
        assert mean > 5 * limits["logit_gap_mean"], (seed, mean)
    assert cell.config["lower_precision_control"] == "fp8_e4m3"


def test_probe_control_reaches_the_three_controls(monkeypatch):
    from benchmark import probe, probe_control
    from benchmark.reference import mimo_v2

    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    stated = lambda: spec.load_cell(
        "tiny-mimo.longdoc").config["lower_precision_control"]
    seen = []
    monkeypatch.setattr(probe, "main",
                        lambda argv: seen.append((argv, stated())) or 0)
    argv = ["--workload", "tiny-mimo.longdoc", "--seconds", "2", "--control"]
    for control in mimo_v2.CONTROLS:
        assert probe_control.main([control] + argv) == 0
    assert seen == [(argv, c) for c in ("window_off", "sink_off",
                                        "held_zero")]
    assert stated() == "fp8_e4m3"
