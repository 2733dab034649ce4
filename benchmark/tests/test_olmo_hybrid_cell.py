"""The Olmo-Hybrid-7B configuration and what PR 48 added to read it: the file
as ``spec.load_cell`` gives it, the catalog's numbers, the new mix's lengths,
the family's step costs on shapes counted by hand, the three new readers on a
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
from benchmark.costs import decode_step_olmo_hybrid, gdn_state  # noqa: E402
from benchmark.layer_metrics import _gdn  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "olmo-hybrid-7b.reason"
OWN = ("gdn_update_dev_ms", "gdn_decode_roofline", "state_rows_live_share")
REDUCED = ["num_hidden_layers", "layer_types"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def test_the_cell_is_in_the_benchmark_and_only_added():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) >= 9 and all(w["chips"] == 1 for w in cells.values())
    assert cells[CELL] == {
        "name": CELL, "config": "olmo-hybrid-7b", "traffic": "reason",
        "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in bench["configs"]}["olmo-hybrid-7b"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/olmo-hybrid-7b.json"
    assert all(len(e["why"]) <= 200 for e in (entry, cells[CELL]))
    # behind the accepted cells in every list it joined
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) > order.index("mimo-v2-flash.longdoc")
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []) and m["name"] not in OWN:
            assert m["workloads"].index(CELL) > m["workloads"].index(
                "falcon-h1-34b.turns"), m["name"]
    own = [m for m in bench["per_layer"] if m["name"] in OWN]
    assert len(own) == 3 and all(
        m["workloads"] == [CELL] and m["moves"] == "output_tokens_per_s"
        for m in own)
    assert {m["name"]: (m["layer"], m["source"]) for m in own} == {
        "gdn_update_dev_ms": ("Model step", "device_trace"),
        "gdn_decode_roofline": ("Kernels", "device_trace"),
        "state_rows_live_share": ("Serving control", "program_counter")}
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
    assert 2.5 * sound["logit_gap_mean"] <= limits["logit_gap_mean"]
    assert limits["logit_gap_mean"] <= mean["control_smallest"] / 2.5
    assert limits["logit_gap_mean"] <= mean["mechanism_controls_smallest"] / 5
    for low in (mean["control_smallest"],
                mean["mechanism_controls_smallest"]):
        assert check.compare({"logit_gap_mean": low,
                              "logit_gap_max": sound["logit_gap_max"],
                              **exact}, limits)[0] is False
    assert 2 * sound["logit_gap_max"] <= limits["logit_gap_max"]


def test_the_configuration_loads_and_its_aliases_agree():
    cell = spec.load_cell(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop"
    assert c["num_hidden_layers"] == 8 and c["layer_types"] == PERIOD * 2
    # n_layer counts the layers that call %attn a step: the full ones
    assert c["n_layer"] == c["layer_types"].count("full_attention") == 2
    assert c["n_head"] == c["num_attention_heads"] == 30
    assert c["n_head"] == c["linear_num_key_heads"] == c[
        "linear_num_value_heads"] == c["num_key_value_heads"]
    assert c["layer_norm_epsilon"] == c["rms_norm_eps"] == 1e-6
    assert c["n_positions"] == c["deployment"]["served_length"] == 1536
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert c["reduced"] == REDUCED
    assert c["published"]["num_hidden_layers"] == 32
    assert c["published"]["layer_types"] == PERIOD * 8
    assert "4 pipeline stages of 8 layers" in c["deployment"]["stands_for"]
    assert c["deployment"]["serving_slots"] == 96
    assert c["deployment"]["serving_prefix_cache"] is False
    assert c["lower_precision_control"] == "fp8_e4m3"
    for key in ("block", "qk_norm", "rope", "head_dim", "linear_attention",
                "state_dtype", "init"):
        assert c["assumed"][key]
    assert set(cell.end_to_end) == {"output_tokens_per_s", "setup_s"}
    # a subset, not the exact set: a later PR may add a metric to every cell
    assert set(cell.per_layer) >= {
        "prefill_pad_share", "decode_step_dev_ms.capacity", "prefill_dev_ms",
        "engine_host_ms_per_step.capacity", "idle_with_work_share.capacity",
        "decode_step_mfu.capacity", "gqa_decode_roofline",
        "setup_restore_s", "setup_build_s", "setup_trace_s", "setup_lower_s",
        "setup_backend_s", "setup_cache_hit_share", *OWN}
    assert "ssm_decode_roofline" not in cell.per_layer
    assert spec.plugin("models", c["builder"]).FUNCTION_NAME
    assert spec.plugin("reference", c["reference"]).logits_at
    assert spec.plugin("costs", c["step_costs"]).decode_step
    for name in cell.per_layer:
        assert spec.plugin("layer_metrics", name).read
    for other in ("gpt2-large.chat", "falcon-h1-34b.turns"):
        assert not set(OWN) & set(spec.load_cell(other).per_layer)


def test_every_published_number_is_in_the_file():
    """The catalog's ``config`` for the model, as the driver compares it:
    every key as published but the two in ``reduced``."""
    c = spec.load_cell(CELL).config
    if CATALOG.exists():
        published = next(
            e for e in map(json.loads, CATALOG.read_text().splitlines())
            if e["name"] == "Olmo-Hybrid-7B")
        assert c["source"] == published["source_url"]
        for k, v in published["config"].items():
            if k not in REDUCED:
                assert c[k] == v, k
        assert c["published"]["layer_types"] == published["config"][
            "layer_types"]
    assert (c["vocab_size"], c["hidden_size"], c["intermediate_size"]) == (
        100352, 3840, 11008)
    builder = spec.plugin("models", c["builder"])
    assert builder.layers(c) == (True, True, True, False) * 2
    # the weights the cut keeps, by the builder's own shapes: 2.436B
    shapes = builder.shapes(c)
    total = sum(math.prod(s) for s, _ in shapes.values())
    assert total == 2_435_748_072
    swiglu = 3 * 3840 * 11008
    linear = (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
              + 4 * (2 * 2880 + 5760) + 2 * 30 + 192 + swiglu + 2 * 3840)
    full = 4 * 3840 * 3840 + 2 * 3840 + swiglu + 2 * 3840
    assert total == 6 * linear + 2 * full + 2 * 100352 * 3840 + 3840
    assert 215.5e6 < linear < 215.7e6 and 185.7e6 < full < 185.9e6
    assert shapes["l_wq"][0] == (6, 3840, 2880)
    assert shapes["l_wv"][0] == (6, 3840, 5760)
    assert shapes["f_wk"][0] == (2, 3840, 3840)
    assert shapes["l_on_g"] == ((6, 192), "scale")
    source = builder.function_source(c)
    for piece in ("depth=8", "num_heads=30", "head_dim=128", "pos=\"none\"",
                  "norm_at=\"output\"", "qk_norm=True", "ln_eps=1e-06",
                  "AttnKind(num_kv_heads=30)", "AttnKind(linear=True)",
                  "attn_pattern=(1, 1, 1, 0, 1, 1, 1, 0)", "num_heads=30",
                  "key_dim=96", "value_dim=192", "d_conv=4",
                  "neg_eigval=True", "mlp_dim=11008", "max_len=1536",
                  "vocab_size=100352"):
        assert piece in source, piece


def test_the_new_mixes_lengths():
    mix = spec.load_cell(CELL).traffic
    assert mix["clients"] == 120 and mix["check_requests"] == 8
    assert mix["block_requests"] == 120
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 260, "hi": 480}
    assert mix["new_tokens"] == {"dist": "log_uniform", "lo": 256,
                                 "hi": 1024}
    assert mix["requests_per_second_ceiling"] == 30
    assert mix["drain_seconds"] == 40
    n = traffic.n_requests(mix, 50.0)
    reqs = traffic.requests(mix, 2 ** 31 + 99, 50.0, 100352)
    assert len(reqs) == n == 1500 and n % 120 == 60
    assert all(260 <= len(r["prompt"]) <= 480 and 256 <= r["max_new"] <= 1024
               and 1 <= min(r["prompt"]) and max(r["prompt"]) < 100352
               for r in reqs)
    # one prefill bucket; table widths of 32, 64 and 96 pages
    assert 256 < min(len(r["prompt"]) for r in reqs)
    assert max(len(r["prompt"]) for r in reqs) <= 512
    assert 1024 < max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 1536
    assert 545 < sum(r["max_new"] for r in reqs[:120]) / 120 < 560
    sizes = lambda rs, i: sorted((len(r["prompt"]), r["max_new"])[i]
                                 for r in rs)
    assert sizes(reqs[:120], 0) == sizes(reqs[120:240], 0)
    assert sizes(reqs[:120], 1) == sizes(reqs[240:360], 1)
    warm = traffic.warmup_requests(mix, 5, 100352)
    assert [(len(w["prompt"]), w["max_new"]) for w in warm] == [
        (260, 4), (480, 64), (480, 560)]
    assert 260 + 4 <= 512 < 480 + 64 <= 1024 < 480 + 560   # the widths


def test_step_costs_on_shapes_counted_by_hand():
    # a toy: one dense matrix of 8 x 8 beside a head of 8 x 5; 4 layers:
    # linear x3, full; 2 heads: attention of 4 wide, keys of 3, values of 6
    cfg = {"compute_dtype": "bfloat16", "num_hidden_layers": 4,
           "layer_types": PERIOD * 2, "num_attention_heads": 2,
           "num_key_value_heads": 2, "head_dim": 4,
           "linear_num_value_heads": 2, "linear_key_head_dim": 3,
           "linear_value_head_dim": 6}
    shapes = {"wte": ((5, 8), "embed"), "w": ((8, 8), "kernel"),
              "lm_head": ((8, 5), "kernel")}
    assert decode_step_olmo_hybrid.layer_kinds(cfg) == (1, 3)
    assert decode_step_olmo_hybrid.weight_elements(shapes) == 64 + 40
    # one row's state in one layer: 2 x 3 x 6 float32 each way, q and k of
    # 2 x 3, v and o of 2 x 6, g and beta of 2
    assert gdn_state.decode_step(1.0, layers=1, heads=2, key_dim=3,
                                 value_dim=6) == (
        7.0 * 36, 4.0 * (72 + 12 + 24 + 4))
    kinds = decode_step_olmo_hybrid.cache(cfg, 3.0, 30.0)
    assert kinds["full"] == (4.0 * 2 * 4 * 30 * 1, 2.0 * 2 * 4 * 2 * 30 * 1)
    assert kinds["linear"] == (7.0 * 36 * 3 * 3, 4.0 * 112 * 3 * 3)
    flops, nbytes = decode_step_olmo_hybrid.decode_step(
        cfg, shapes, rows=3.0, depth_tokens=30.0)
    assert flops == 2 * 104 * 3 + kinds["full"][0] + kinds["linear"][0]
    assert nbytes == 104 * 2 + kinds["full"][1] + kinds["linear"][1]
    # the published step at 96 rows about 700 deep: 4.10 GB of weights
    # outside the embedding, 2.06 GB of the two full layers' keys and
    # values, 2.59 GB of the six linear layers' state and vectors; bytes
    # bound it: 10.7 ms at 819 GB/s
    c = spec.load_cell(CELL).config
    shapes = spec.plugin("models", c["builder"]).shapes(c)
    assert 4.09e9 < 2 * decode_step_olmo_hybrid.weight_elements(shapes) < (
        4.11e9)
    kinds = decode_step_olmo_hybrid.cache(c, 96.0, 96 * 700.0)
    assert 2.06e9 < kinds["full"][1] < 2.07e9
    assert 2.58e9 < kinds["linear"][1] < 2.60e9
    # the state alone, each way: 96 rows x 6 layers x 2.21 MB
    assert 96 * 6 * 30 * 96 * 192 * 4 * 2 == 2_548_039_680
    flops, nbytes = decode_step_olmo_hybrid.decode_step(
        c, shapes, 96.0, 96 * 700.0)
    assert 8.7e9 < nbytes < 8.8e9
    least, bound = decode_step_olmo_hybrid.min_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and 0.0106 < least < 0.0108


def _reading(ops, modules, cfg, counters=None, records=()):
    trace = reduce.Trace(
        lines={(PLANE, reduce.OPS_LINE): ops,
               (PLANE, reduce.MODULES_LINE): modules}, wall_zero=100.0)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=list(records),
                          counters=counters or ({}, {}))
    return layers.Reading(cell=SimpleNamespace(config=cfg), win=win,
                          trace=trace, peaks=PEAKS)


def _step_ops(steps, gdn_seconds=0.0006, attn_seconds=0.0015):
    """``steps`` decode steps in one program execution: six ``%gdn_update``
    and two ``%attn`` calls each, in the stack's order."""
    ops, t = [], 1.0
    for step in range(steps):
        for k, kind in enumerate(PERIOD * 2):
            name, dur = (("%gdn_update", gdn_seconds)
                         if kind == "linear_attention"
                         else ("%attn", attn_seconds))
            ops.append((f"{name}.{8 * step + k} custom-call", t, dur))
            t += dur + 0.0005
    return ops


def test_the_trace_readers_count_six_calls_a_step():
    cfg = spec.load_cell(CELL).config
    modules = [("jit__unknown(3)", 1.0, 0.040)]
    ops = _step_ops(2)
    r = _reading(ops, modules, cfg)
    assert _gdn.kernel_in_steps(r) == [(2, pytest.approx(12 * 0.0006))]
    read = lambda name, at=r: spec.plugin("layer_metrics", name).read(at)
    assert read("gdn_update_dev_ms") == pytest.approx(6 * 0.6)
    # every slab row's state once each way, 96 rows x 6 layers x 2 steps
    _, nbytes = gdn_state.decode_step(96 * 2, layers=6, heads=30, key_dim=96,
                                      value_dim=192)
    assert nbytes == 2 * 96 * 6 * 4 * (2 * 552960 + 2 * 2880 + 2 * 5760 + 60)
    want = 100.0 * nbytes / PEAKS["hbm_bytes_per_s"] / (12 * 0.0006)
    assert read("gdn_decode_roofline") == pytest.approx(want, rel=1e-6)
    assert 85 < want < 95
    # a count over 105% of the roofline is refused, not clipped
    with pytest.raises(Exception):
        read("gdn_decode_roofline",
             _reading(_step_ops(2, gdn_seconds=0.0004), modules, cfg))
    # the two %attn calls a step are what counts a program's steps
    # (n_layer 2): a program with no kernel of ours reads nothing
    only_attn = [o for o in ops if o[0].startswith("%attn")]
    for name in ("gdn_update_dev_ms", "gdn_decode_roofline"):
        assert read(name, _reading(only_attn, modules, cfg)) is None


def test_the_counter_reader_on_hand_made_counters():
    cfg = spec.load_cell(CELL).config
    c0 = {"state_rows_moved": 576.0, "state_rows_live": 500.0}
    c1 = {"state_rows_moved": 576.0 + 57600.0,
          "state_rows_live": 500.0 + 56000.0}
    read = lambda at: spec.plugin(
        "layer_metrics", "state_rows_live_share").read(at)
    assert read(_reading([], [], cfg, (c0, c1))) == pytest.approx(
        100 * 56000 / 57600)
    assert read(_reading([], [], cfg, (c1, c1))) is None


def test_each_new_reader_returns_none_on_a_program_without_the_kernel():
    """The parent commit's telemetry (no ``state_rows_*`` counters) and
    trace (no ``%gdn_update``), another family's cell, and nothing at
    all."""
    old = ({"walk_chunks_live": 0.0, "walk_chunks_grid": 0.0},
           {"walk_chunks_live": 128.0, "walk_chunks_grid": 512.0})
    modules = [("jit__unknown(3)", 1.0, 0.030)]
    ops = [(f"%attn.{k} custom-call", 1.0 + 0.003 * k, 0.0004)
           for k in range(6)]
    for cfg in (spec.load_cell("falcon-h1-34b.turns").config,
                spec.load_cell("gpt2-large.docs").config,
                spec.load_cell(CELL).config):
        for r in (_reading(ops, modules, cfg, old), _reading([], [], cfg)):
            for name in OWN:
                assert spec.plugin("layer_metrics", name).read(r) is None


# -- a whole run at toy size: builder, hand-over, state and pages, check -----

DATA = Path(__file__).resolve().parent / "data_olmo"


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
    rc = run.main(["--workload", "tiny-olmo.reason", "--seed",
                   str(2 ** 31 + 48), "--seconds", "2", "--trace", "0"],
                  require_tpu=False)
    io = capsys.readouterr()
    result = json.loads(io.out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    mean = result["check"]["logit_gap_mean"]
    assert 0.0 <= mean["value"] <= mean["limit"] == 5e-4
    assert seen["cache_sublayers"] == seen["full_layers"] == 2.0
    assert seen["recurrent_layers"] == 6.0
    row = 4 * (6 * 8 * 16 + 3 * 6 * 32)
    assert seen["recurrent_state_bytes"] == 4 * 6 * row
    assert seen["state_rows_moved"] == seen["chunks"] * 4
    assert 0 < seen["state_rows_live"] <= seen["state_rows_moved"]
    # 2 bytes a parameter, by the builder's own shapes
    cfg = json.loads((DATA / "configs/tiny-olmo.json").read_text())
    shapes = spec.plugin("models", "olmo_hybrid").shapes(cfg)
    assert seen["param_bytes"] == 2 * sum(
        math.prod(s) for s, _ in shapes.values())


@pytest.mark.parametrize("control", ["bfloat16", "delta_off", "decay_off",
                                     "qknorm_off"])
def test_the_toys_controls_fail_the_mean_gap(monkeypatch, control):
    """The toy's stated control, and the three mechanisms left out (no
    delta term, no decay, no QK norm: probe_control.py), read on a seeded
    sample of prompts through the reference alone: the sound reference's own
    first choices lie under each control's best by more than the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)
    cell = spec.load_cell("tiny-olmo.reason")
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
