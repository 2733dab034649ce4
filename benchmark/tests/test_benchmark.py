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


def _name_gaps_by_scan(gaps, spans, wall_zero,
                       default="host, no span open"):
    """``reduce.name_gaps`` as it stood until PR 36: every span scanned for
    every gap. The reference the sweep is held to."""
    named = {}
    for start, dur in gaps:
        name = default
        if wall_zero is not None:
            mid = wall_zero + start + 0.5 * dur
            cover = [s for s in spans
                     if s["start"] <= mid <= s["start"] + s["duration"]]
            if cover:
                name = min(cover, key=lambda s: s["duration"])["name"]
        named[name] = named.get(name, 0.0) + dur
    return [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])]


def _gaps_and_nested_spans(seed, n_gaps, n_spans, seconds=4.0):
    """Seeded gaps of a ``seconds`` trace, longest first as ``idle_gaps``
    gives them, and spans nested three deep over a window that starts before
    the trace and ends after it, with stretches no span covers, spans of
    equal duration on one start, and spans that touch end to start."""
    rng = np.random.default_rng(seed)
    edges = np.sort(rng.uniform(0.0, seconds, 2 * n_gaps))
    gaps = sorted(((float(a), float(b - a)) for a, b
                   in zip(edges[::2], edges[1::2])), key=lambda g: -g[1])
    wall_zero = 1000.0
    spans, t = [], wall_zero - 2.0
    names = ["engine.wait_work", "engine.dispatch", "engine.process",
             "engine.wait_result", "serving.request"]
    while len(spans) < n_spans:
        dur = float(rng.choice([0.25, 0.5, 1.0])) * 16.0 * seconds / n_spans
        spans.append({"name": names[len(spans) % 5], "start": t,
                      "duration": dur})
        # children: two of equal duration on one start, then one that
        # touches the second's end
        spans.append({"name": "child.a", "start": t + 0.1 * dur,
                      "duration": 0.3 * dur})
        spans.append({"name": "child.b", "start": t + 0.1 * dur,
                      "duration": 0.3 * dur})
        spans.append({"name": "grandchild", "start": t + 0.2 * dur,
                      "duration": 0.1 * dur})
        # every third outer span is followed by a stretch with no span open
        t += dur * (1.5 if len(spans) % 3 == 0 else 1.0)
    order = rng.permutation(len(spans))    # the tracer's list is not sorted
    return gaps, [spans[i] for i in order], wall_zero


@pytest.mark.parametrize("seed", [1, 2, 3, 2 ** 31 + 5])
def test_the_sweep_names_gaps_as_the_scan_did(seed):
    gaps, spans, zero = _gaps_and_nested_spans(seed, 400, 300)
    want = _name_gaps_by_scan(gaps, spans, zero)
    got = reduce.name_gaps(gaps, spans, zero)
    assert got == want            # names, order and sums, to the last bit
    # child.a and child.b tie; the list's order (permuted) picks between them
    assert {"host, no span open", "grandchild", "child.a", "child.b"} <= {
        n for n, _ in got}
    # a trace without the wall-clock tie, no spans, no gaps
    assert reduce.name_gaps(gaps, spans, None) == _name_gaps_by_scan(
        gaps, spans, None) == [["host, no span open",
                                pytest.approx(sum(g[1] for g in gaps))]]
    assert reduce.name_gaps(gaps, [], zero) == _name_gaps_by_scan(
        gaps, [], zero)
    assert reduce.name_gaps([], spans, zero) == []
    # a gap whose middle is a span's very end or start is inside it
    edge = [{"name": "a", "start": zero + 1.0, "duration": 1.0}]
    for g in ([(0.5, 1.0)], [(1.5, 1.0)], [(1.5, 1.0 + 1e-9)]):
        assert reduce.name_gaps(g, edge, zero) == _name_gaps_by_scan(
            g, edge, zero)


def test_naming_a_traced_windows_gaps_takes_seconds_not_minutes():
    """200,000 gaps against 16,000 spans: a turns window (PR 31), where the
    scan took 210 s on the chip's host."""
    import time

    gaps, spans, zero = _gaps_and_nested_spans(7, 200_000, 16_000)
    t0 = time.perf_counter()
    named = reduce.name_gaps(gaps, spans, zero)
    assert time.perf_counter() - t0 < 5.0
    assert sum(v for _, v in named) == pytest.approx(sum(g[1] for g in gaps))


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


def test_every_cells_mix_says_where_its_load_comes_from():
    """An open loop's rate is a number somebody swept for: its file says how
    (``rate_note``); a closed loop's load is its ``clients``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        mix = json.loads((spec.HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        if mix["kind"] == "open_loop":
            assert mix["rate_per_s"] > 0 and len(mix["rate_note"]) > 40, w
            # the cell's line names the rate it runs at
            assert f"{mix['rate_per_s']:g}/s" in w["why"], w
        else:
            assert mix["clients"] > 0 and "rate_per_s" not in mix, w


def test_perf_md_gives_the_bounds_the_benchmark_has():
    """PERF.md section 2's table, one row an end-to-end metric: the bound in
    its fourth column is ``BENCHMARK.json``'s."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    text = (ROOT / "PERF.md").read_text()
    section = text[text.index("\n## 2."):text.index("\n## 3.")]
    rows = [[c.strip() for c in line.strip().strip("|").split("|")]
            for line in section.splitlines() if line.startswith("| `")]
    assert {r[0].strip("`"): float(r[3]) for r in rows} == {
        m["name"]: m["bound"] for m in bench["end_to_end"]}
    # and the window's length
    assert f"--seconds {bench['run_seconds']} " in section


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
    io = capsys.readouterr()
    return (rc, json.loads(io.out.strip().splitlines()[-1]),
            io.err.strip().splitlines())


def test_a_sound_run_is_correct_and_prints_each_number_beside_its_limit(
        tiny, capsys):
    rc, result, err = _run_tiny(capsys)
    assert rc == 0 and result["correct"] is True
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["failed"] == 0 and result["attempted"] == 8
    assert set(result["metrics"]) == {"ttft_p50_ms", "tpot_mean_ms",
                                      "output_tokens_per_s", "setup_s"}
    # each number compared beside its limit: the last lines of standard
    # error, and the last key of the result's line
    limits = check.limits_for("tiny.open")
    assert [l.split(":")[0] for l in err[-len(limits):]] == [
        f"check {name}" for name in limits]
    assert all("limit" in l and l.endswith(" ok") for l in err[-len(limits):])
    assert {k: v["limit"] for k, v in result["check"].items()} == limits
    assert 0.0 <= result["check"]["logit_gap_max"]["value"] <= \
        limits["logit_gap_max"]


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
    rc, result, err = _run_tiny(capsys)
    assert rc == 0 and result["correct"] is False
    assert any("logit_gap_max" in l and "NOT OK" in l for l in err)
    gap = result["check"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]
