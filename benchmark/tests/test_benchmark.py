"""Tests of the benchmark itself, at a toy size on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are outside ``tests/``: the repo's tier-1 run neither gains nor loses
by them. The last two are the ones "How correct is decided" asks for: the
lower-precision control kept as a test, and a whole run with the served path
broken underneath that has to come out ``correct: false``."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import check, reduce, spec, traffic  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


# -- the reduction -----------------------------------------------------------

def test_union_gaps_and_names_on_rows():
    rows = [("%while.6 while", 0.0, 1.5), ("%attn.1 custom-call", 0.1, 0.3),
            ("%attn.2 custom-call", 0.5, 0.4), ("%copy.9 copy", 3.0, 0.5)]
    assert reduce.union_seconds(rows) == pytest.approx(2.0)
    # a while is charged its own time, the layers' copies of an op add up
    assert reduce.top(rows, 2) == [["%while while", pytest.approx(0.8)],
                                   ["%attn custom-call", pytest.approx(0.7)]]
    gaps = reduce.idle_gaps(rows, (0.0, 4.0))
    assert gaps == [(1.5, 1.5), (3.5, 0.5)]
    spans = [{"name": "outer", "start": 100.0, "duration": 10.0},
             {"name": "inner", "start": 101.6, "duration": 1.0}]
    named = reduce.name_gaps(gaps, spans, wall_zero=100.0)
    assert named == [["inner", 1.5], ["outer", 0.5]]
    assert reduce.name_gaps(gaps, spans, None) == [
        ["host, no span open", 2.0]]
    long = ("%attn.394 = bf16[8,20,16,64]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[8,64]{1,0:T(8,128)S(1)} %get-tuple-element.2)")
    assert reduce.short_name(long) == "%attn.394 custom-call"
    assert reduce.family("%attn.394 custom-call") == "%attn custom-call"


def test_reduction_of_the_recorded_trace():
    """A quarter second of a gpt2-large.chat window on the v5e (PR 23),
    reduced to rows: the numbers below were read off it by hand."""
    trace = reduce.Trace.from_json((DATA / "trace_rows.json").read_text())
    expect = json.loads((DATA / "trace_rows.expect.json").read_text())
    plane = trace.device_planes()[0]
    ops = trace.rows(plane, reduce.OPS_LINE)
    assert len(ops) == expect["ops"]
    assert reduce.busy_seconds(trace) == pytest.approx(expect["busy_s"],
                                                       rel=1e-9)
    assert reduce.top(ops, 1)[0][0] == expect["top_op"]
    lo = min(r[1] for r in ops)
    hi = max(r[1] + r[2] for r in ops)
    gaps = reduce.idle_gaps(ops, (lo, hi))
    assert sum(g[1] for g in gaps) + reduce.union_seconds(ops) == \
        pytest.approx(hi - lo, rel=1e-9)


def test_a_share_over_105_percent_is_refused():
    assert reduce.checked_share("x_roofline", 104.0) == 104.0
    with pytest.raises(ValueError, match="over 105%"):
        reduce.checked_share("x_roofline", 106.0)


# -- the table of peaks --------------------------------------------------------

def test_an_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="not in benchmark/peaks.json"):
        spec.peaks_for("TPU v9")


# -- the generator --------------------------------------------------------------

@pytest.mark.parametrize("mix_name", ["chat", "docs"])
def test_traffic_is_the_seeds_and_every_seed_offers_the_same_work(mix_name):
    mix = json.loads((spec.HERE / "traffic" / f"{mix_name}.json").read_text())
    big = 2 ** 31 + 12345
    a = traffic.requests(mix, big, 20.0, 50257)
    assert a == traffic.requests(mix, big, 20.0, 50257)
    b = traffic.requests(mix, 7, 20.0, 50257)
    assert a != b
    sizes = lambda rs: (sorted(len(r["prompt"]) for r in rs),
                        sorted(r["max_new"] for r in rs))
    assert sizes(a) == sizes(b)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert all(lo <= len(r["prompt"]) <= hi and min(r["prompt"]) >= 1
               for r in a)
    if mix["kind"] == "open_loop":
        due = [r["due_s"] for r in a]
        assert due == sorted(due) and 0.0 < due[0] and due[-1] < 20.0
        assert len(a) == round(mix["rate_per_s"] * 20.0)
        assert not np.allclose(due, [r["due_s"] for r in b])
    assert traffic.warmup_requests(mix, big, 50257) == \
        traffic.warmup_requests(mix, big, 50257)


# -- no chip, no result -----------------------------------------------------------

def test_a_cpu_run_exits_non_zero_naming_the_platform():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2-large.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin:/usr/local/bin"})
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


# -- correct: the control, and a broken served path --------------------------------

@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(spec, "BENCH_FILE", DATA / "BENCHMARK.json")
    monkeypatch.setattr(spec, "DATA", DATA)


def _run_tiny(capsys, workload="tiny.open", seed=2 ** 31 + 7):
    from benchmark import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0"], require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_a_sound_run_is_correct_and_prints_each_number_beside_its_limit(
        tiny, capsys):
    rc, result, out = _run_tiny(capsys)
    assert rc == 0 and result["correct"] is True
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["failed"] == 0 and result["attempted"] == 8
    assert set(result["metrics"]) == {"ttft_p50_ms", "tpot_mean_ms",
                                      "output_tokens_per_s", "setup_s"}
    assert any(l.startswith("check logit_gap_max:") and "limit" in l
               for l in out)


def test_the_lower_precision_control_is_not_correct(tiny):
    """The reference in the next precision down (bfloat16 under the toy's
    float32) put in the program's place: the token it puts first lies below
    the float32 reference's best by more than the limit somewhere."""
    cell = spec.load_cell("tiny.open")
    builder = spec.plugin("models", cell.config["builder"])
    limit = check.limits_for(cell.name)["logit_gap_max"]
    for seed in (3, 4, 5):
        weights = builder.init_weights(cell.config, seed)
        # positions enough for bfloat16's rare flips at toy width to show
        reqs = traffic.requests(cell.traffic, seed, 10.0,
                                cell.config["vocab_size"])
        toks = traffic.rng(seed, "check")
        sampled = [{"id": r["id"], "tokens": toks.integers(
            1, cell.config["vocab_size"], size=r["max_new"]).tolist()}
            for r in reqs]
        got = check.gaps(cell, weights, {r["id"]: r["prompt"] for r in reqs},
                         sampled,
                         control=cell.config["lower_precision_control"])
        assert max(got["control"]) > limit


def test_a_run_whose_served_tokens_are_altered_is_not_correct(
        tiny, capsys, monkeypatch):
    """Drives a whole run (set-up, window through /generate, reference)
    with the served path broken underneath: the engine's stream hands out
    one wrong token per request."""
    from kubeml_tpu.serving import batcher

    sound = batcher.PagedBatchingDecoder.stream

    def altered(self, entry):
        hit = False
        for item in sound(self, entry):
            if not hit and item.get("tokens"):
                item = {**item, "tokens": [(item["tokens"][0] + 1) % 211]
                        + list(item["tokens"][1:])}
                hit = True
            yield item

    monkeypatch.setattr(batcher.PagedBatchingDecoder, "stream", altered)
    rc, result, out = _run_tiny(capsys)
    assert rc == 0 and result["correct"] is False
    assert any("logit_gap_max" in l and "NOT OK" in l for l in out)
