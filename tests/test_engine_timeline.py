"""The dispatch-level timeline of the serving engines (ISSUE 24): every
dispatched program leaves engine.dispatch / engine.fetch / engine.process
spans that share its sequence number, on the clock the request-level
serving.* spans use; with the tracer off nothing is recorded; and a
program's service time is taken where it completes, not over the pipe.
And where a replica's start goes (ISSUE 39): a program's first call carries
the compile clock's bracket of it, the slab's program likewise, and the
decoder's telemetry holds the seconds whether the tracer is on or not."""

import threading
import time

import numpy as np
import pytest

import jax

from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.serving import PagedBatchingDecoder
from kubeml_tpu.serving.batcher import BatchingDecoder, service_interval
from kubeml_tpu.utils import tracing

VOCAB = 101
PROGRAM_SPANS = ("engine.dispatch", "engine.fetch", "engine.process")


@pytest.fixture(scope="module")
def served():
    m = CausalTransformer(vocab_size=VOCAB, max_len=64, embed_dim=64,
                          depth=2, num_heads=4)
    return m, m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


@pytest.fixture
def tracer():
    t = tracing.get_tracer()
    was_on = t.enabled
    t.clear()
    yield t
    t.enabled = was_on
    t.clear()


def _settle(tracer, timeout=30.0):
    """The stream's last item reaches the client from inside the engine's
    processing of the last program, whose engine.process span is recorded
    after it: wait until every dispatched program has one."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = tracer.spans()
        done = {s.attrs["seq"] for s in spans if s.name == "engine.process"}
        if {s.attrs["seq"] for s in spans
                if s.name == "engine.dispatch"} <= done:
            return
        time.sleep(0.01)
    raise AssertionError("a dispatched program was never processed")


def _stream_one(dec, n=6):
    entry = dec.submit(GenerateRequest(prompts=[[1, 2, 3, 4, 5]],
                                       max_new_tokens=n, stream=True))
    tokens = []
    for item in dec.stream(entry):
        if "tokens" in item:
            tokens += item["tokens"]
        else:
            return tokens, item["request_id"]
    raise AssertionError("the stream ended without its result")


ENGINES = [
    pytest.param(lambda m, v: PagedBatchingDecoder(
        m, v, slots=2, chunk_steps=1, page_tokens=4), id="paged"),
    pytest.param(lambda m, v: BatchingDecoder(
        m, v, slots=2, chunk_steps=1), id="dense"),
]


@pytest.mark.parametrize("build", ENGINES)
def test_traced_request_leaves_a_program_timeline(served, tracer, build):
    tracer.enabled = True
    dec = build(*served)
    try:
        tokens, req_id = _stream_one(dec)
        _settle(tracer)
    finally:
        dec.close()
    assert len(tokens) == 6
    spans = tracer.spans()
    by_seq = {}
    for s in spans:
        if s.name in PROGRAM_SPANS:
            by_seq.setdefault(s.attrs["seq"], {})[s.name] = s
    # one admit program, then the five one-step programs of the answer
    seqs = sorted(by_seq)
    assert seqs == list(range(len(seqs))) and len(seqs) >= 6
    for seq in seqs:
        trio = by_seq[seq]
        assert set(trio) == set(PROGRAM_SPANS), (seq, set(trio))
        d, f, p = (trio[n] for n in PROGRAM_SPANS)
        assert d.attrs["program"] == f.attrs["program"] == p.attrs["program"]
        # dispatched, then complete on the host, then routed
        assert d.start <= d.start + d.duration <= f.start + f.duration + 1e-6
        assert f.start + f.duration <= p.start + 1e-6
        assert f.attrs["svc_s"] >= 0.0 and f.attrs["wait_s"] >= 0.0
        assert f.thread != p.thread == d.thread
    programs = [by_seq[q]["engine.dispatch"].attrs["program"] for q in seqs]
    assert programs[0] == "admit" and set(programs[1:]) == {"step"}
    first = by_seq[0]
    assert first["engine.dispatch"].attrs["requests"] == req_id
    assert first["engine.process"].attrs["tokens"] == 1
    steps = [by_seq[q]["engine.dispatch"] for q in seqs[1:]]
    assert all(s.attrs["steps"] == 1 and s.attrs["rows_live"] >= 1
               for s in steps)
    assert sum(by_seq[q]["engine.process"].attrs["tokens"]
               for q in seqs) == 6
    # the admission's host work ends where its jitted call begins
    (admit,) = [s for s in spans if s.name == "engine.admit"]
    assert admit.attrs == {"rows": 1, "requests": req_id, "state_rows": 0,
                           "moe_layers": 0}
    assert admit.start + admit.duration == pytest.approx(
        first["engine.dispatch"].start, abs=1e-6)
    # the request-level tree is what it was, on the same clock
    mine = tracer.task_spans(req_id)
    tree = {s.name: s for s in mine if s.name.startswith("serving.")}
    assert set(tree) == {"serving.request", "serving.queue_wait",
                         "serving.prefill", "serving.decode"}
    req = tree["serving.request"]
    assert req.attrs["tokens"] == 6 and req.attrs["outcome"] == "completed"
    assert set(req.attrs) == {"job", "model", "rows", "tokens", "outcome",
                              "itl_p99", "hol_stall_seconds"}
    for name, s in tree.items():
        if s is not req:
            assert s.parent_id == req.span_id and s.attrs == {"job": req_id}
    assert req.start <= admit.start + admit.duration
    assert tree["serving.prefill"].start <= first["engine.fetch"].start + 1e-6
    # `kubeml trace <request-id>` finds the admit program through `requests`
    assert {s.name for s in mine} >= {"engine.admit", *PROGRAM_SPANS}
    assert all(s.attrs.get("program", "admit") == "admit"
               for s in mine if s.name.startswith("engine."))


def test_untraced_request_records_no_span(served, tracer):
    tracer.enabled = False
    dec = PagedBatchingDecoder(*served, slots=2, chunk_steps=1,
                               page_tokens=4)
    try:
        tokens, _ = _stream_one(dec)
        stats = dec.telemetry()
    finally:
        dec.close()
    assert len(tokens) == 6 and tracer.spans() == []
    # the series the service time feeds are fed without the tracer: every
    # step program of so short a run is a first execution (cold_start)
    fed = sum(stats["hist"].get(k, {}).get("count", 0)
              for k in ("decode_step", "decode_step_colocated", "cold_start"))
    assert fed >= 6


def test_engine_waits_for_work_inside_a_span(served, tracer):
    """Between two requests the loop has nothing pending, no live row and
    nothing in flight: that wait is an engine.wait_work span."""
    tracer.enabled = True
    dec = PagedBatchingDecoder(*served, slots=2, chunk_steps=1,
                               page_tokens=4)
    try:
        _stream_one(dec, n=2)
        # the first answer's last token reaches us from inside the engine's
        # processing: let the loop come round to its wait before the second
        _settle(tracer)
        time.sleep(0.3)
        _stream_one(dec, n=2)
        _settle(tracer)
    finally:
        dec.close()
    waits = tracer.spans("engine.wait_work")
    admits = tracer.spans("engine.admit")
    assert len(admits) == 2 and waits
    # the wait between the requests ends where the second is taken up
    gap = [w for w in waits if admits[0].start < w.start < admits[1].start]
    assert gap and max(w.start + w.duration for w in gap) <= (
        admits[1].start + admits[1].duration)


MS = 0.001


@pytest.mark.parametrize("dispatched, done, expect", [
    pytest.param([0], [40], [(40, 0)], id="depth-1"),
    # six steps dispatched at once, each 40 ms on the device: the sixth
    # fetch blocks 240 ms and its step still reads 40
    pytest.param([0, 1, 2, 3, 4, 5], [40, 80, 120, 160, 200, 240],
                 [(40, 0), (40, 39), (40, 78), (40, 117), (40, 156),
                  (40, 195)], id="depth-6-constant-40ms"),
    # step, a 230 ms admit, step: each is charged its own interval
    pytest.param([0, 2, 4], [40, 270, 310],
                 [(40, 0), (230, 38), (40, 266)], id="admit-between-steps"),
    # the device idles between two programs: the second starts when it is
    # dispatched, not when the first ended
    pytest.param([0, 100], [40, 140], [(40, 0), (40, 0)], id="idle-between"),
    # two fetchers stamp a little out of order: a running maximum, so the
    # later program reads 0 and never a negative time
    pytest.param([0, 1, 2], [40, 81, 80.5, ],
                 [(40, 0), (41, 39), (0, 79)], id="out-of-order-done"),
])
def test_service_interval(dispatched, done, expect):
    prev, got = 0.0, []
    for d, t in zip(dispatched, done):
        svc, wait, prev = service_interval(d * MS, t * MS, prev)
        got.append((svc / MS, wait / MS))
    assert got == [pytest.approx(e) for e in expect]


def test_one_clock(tracer):
    """span(), record() and a monotonic stamp through at() lie on one
    monotonic-derived wall clock."""
    tracer.enabled = True
    t0 = tracer.now()
    with tracer.span("a"):
        pass
    tracer.record("b", 0.0)
    mono = time.monotonic()
    t1 = tracer.now()
    a, b = tracer.spans()
    assert t0 <= a.start <= b.start <= tracer.at(mono) <= t1
    assert abs(t1 - time.time()) < 5.0   # a wall clock, not a bare counter


# --- where a replica's start goes (ISSUE 39) ---

PHASES = ("trace_s", "lower_s", "backend_s", "cache_hits", "cache_misses")
START_KEYS = ("startup_restore_seconds", "startup_hold_seconds",
              "startup_decoder_seconds", "startup_slab_seconds",
              "compile_trace_seconds", "compile_lower_seconds",
              "compile_backend_seconds", "compile_wall_seconds",
              "compile_cache_hits", "compile_cache_misses")
TRACE, LOWER, BACKEND = tracing._COMPILE_PHASES
HIT, MISS = tracing._COMPILE_COUNTS


def _compiled_seconds(stats):
    return (stats["compile_trace_seconds"] + stats["compile_lower_seconds"]
            + stats["compile_backend_seconds"])


@pytest.mark.parametrize("build", ENGINES)
def test_first_calls_carry_their_compile_phases(served, tracer, build):
    tracer.enabled = True
    dec = build(*served)
    try:
        _stream_one(dec)
        _settle(tracer)
        stats = dec.telemetry()
    finally:
        dec.close()
    dispatches = tracer.spans("engine.dispatch")
    cold = [s for s in dispatches if s.attrs["cold"]]
    warm = [s for s in dispatches if not s.attrs["cold"]]
    # the admit program and the one-step program, each compiled once; the
    # answer's other steps run what is compiled
    assert len(cold) == stats["compiled_programs"] == 2 and len(warm) >= 4
    assert {s.attrs["program"] for s in cold} == {"admit", "step"}
    for s in cold:
        assert s.attrs["sig"].startswith(("prefill(", "step(", "admit("))
        assert all(s.attrs[k] >= 0 for k in PHASES)
        spent = s.attrs["trace_s"] + s.attrs["lower_s"] + s.attrs["backend_s"]
        assert 0.0 < spent <= s.duration
    for s in warm:
        assert "sig" not in s.attrs and not set(PHASES) & set(s.attrs)
    # the same seconds reached the stats, beside the walls they lie inside
    assert set(START_KEYS) <= set(stats)
    assert _compiled_seconds(stats) == pytest.approx(sum(
        s.attrs["trace_s"] + s.attrs["lower_s"] + s.attrs["backend_s"]
        for s in cold))
    assert stats["compile_wall_seconds"] == pytest.approx(
        sum(s.duration for s in cold), abs=1e-3)
    assert stats["compile_wall_seconds"] >= _compiled_seconds(stats) > 0.0
    # the slab's own program: a span with its bracket, seconds in the stats,
    # and nothing of it among the engine programs' sums
    (slab,) = tracer.spans("engine.init_slab")
    assert slab.attrs["slots"] == 2 and slab.attrs["bytes"] > 0
    assert set(PHASES) <= set(slab.attrs)
    assert stats["startup_slab_seconds"] == pytest.approx(slab.duration)
    assert slab.start + slab.duration <= min(s.start for s in cold) + 1e-6
    # built by hand, not by the parameter server: no load path to time
    assert stats["startup_restore_seconds"] == 0.0
    assert stats["startup_hold_seconds"] == 0.0


def test_the_start_is_in_the_telemetry_with_the_tracer_off(served, tracer):
    tracer.enabled = False
    dec = PagedBatchingDecoder(*served, slots=2, chunk_steps=1,
                               page_tokens=4)
    try:
        _stream_one(dec)
        first = dec.telemetry()
        _stream_one(dec)
        second = dec.telemetry()
    finally:
        dec.close()
    assert tracer.spans() == []
    assert set(START_KEYS) <= set(first)
    assert first["compile_wall_seconds"] >= _compiled_seconds(first) > 0.0
    assert first["startup_slab_seconds"] > 0.0
    # they grow at a first call and nowhere else
    assert first["compiled_programs"] == second["compiled_programs"]
    assert all(first[k] == second[k] for k in START_KEYS)


def _compile_something(tag: float):
    # a new function object each call: never in jax's in-memory caches
    return jax.block_until_ready(
        jax.jit(lambda x: x * tag + 1.0)(np.ones((3,), np.float32)))


def test_bracket_leaves_out_another_threads_compile():
    clock = tracing.compile_clock()
    assert clock is tracing.compile_clock()   # one a process
    before, total = clock.read(), clock.totals()
    other = threading.Thread(target=_compile_something, args=(2.0,))
    other.start()
    other.join(60.0)
    assert not other.is_alive()
    assert clock.since(before) == dict.fromkeys(before, 0)
    grown = clock.totals()
    assert grown["programs"] >= total["programs"] + 1
    assert grown["backend_s"] > total["backend_s"]
    _compile_something(3.0)
    mine = clock.since(before)
    assert mine["programs"] >= 1
    assert min(mine["trace_s"], mine["lower_s"], mine["backend_s"]) > 0.0


def test_cache_counters_follow_the_listener():
    """A CPU box may have no persistent cache: the two events by hand,
    through jax.monitoring, as jax's compiler records them."""
    clock = tracing.compile_clock()
    before, total = clock.read(), clock.totals()
    seen = {}

    def elsewhere():
        at = clock.read()
        jax.monitoring.record_event(HIT)
        seen.update(clock.since(at))

    for event in (HIT, HIT, MISS, "/jax/some/other_event"):
        jax.monitoring.record_event(event)
    other = threading.Thread(target=elsewhere)
    other.start()
    other.join(60.0)
    mine = clock.since(before)
    assert (mine["cache_hits"], mine["cache_misses"]) == (2, 1)
    assert (seen["cache_hits"], seen["cache_misses"]) == (1, 0)
    grown = clock.totals()
    assert grown["cache_hits"] - total["cache_hits"] == 3
    assert grown["cache_misses"] - total["cache_misses"] == 1


def test_a_phase_is_the_union_of_its_intervals():
    """jax reports a jit traced inside another's trace as an event of its
    own inside its caller's interval: an interval that contains earlier
    ones replaces them, so the phases never add up to more than the wall."""
    clock = tracing.CompileClock()   # fed by hand, on no listener
    clock.span(TRACE, 10.1, 10.2)          # an inner jit's trace
    clock.span(TRACE, 10.3, 10.4)          # another
    clock.span(TRACE, 10.0, 10.5)          # their caller's: holds both
    assert clock.read()["trace_s"] == pytest.approx(0.5)
    clock.span(TRACE, 10.6, 10.7)          # a helper traced while lowering
    clock.span(LOWER, 10.5, 11.0)          # the lowering that holds it
    clock.span(BACKEND, 11.0, 13.0)        # begins where the lowering ends
    clock.span("/jax/core/compile/something_else", 0.0, 99.0)
    got = clock.read()
    assert got["trace_s"] == pytest.approx(0.5)
    assert got["lower_s"] == pytest.approx(0.5)
    assert got["backend_s"] == pytest.approx(2.0) and got["programs"] == 1
    assert clock.totals() == got           # one thread fed it
    # a later program on the same thread adds to each phase
    clock.span(TRACE, 20.0, 21.0)
    clock.span(LOWER, 21.0, 21.25)
    clock.span(BACKEND, 21.25, 21.5)
    assert clock.since(got) == pytest.approx({
        "trace_s": 1.0, "lower_s": 0.25, "backend_s": 0.25, "programs": 1,
        "cache_hits": 0, "cache_misses": 0})


def test_a_trace_of_thousands_of_small_jits_is_still_its_own_length():
    """One program's trace holds thousands of jits side by side (3,200 in
    the toy hyper-connected model, more at a published depth): each is kept
    until its caller's interval arrives, however many there are, and only
    what ended an hour before is forgotten."""
    clock = tracing.CompileClock()
    n = 5000
    for i in range(n):
        clock.span(TRACE, 100.0 + i * 0.002, 100.001 + i * 0.002)
    assert clock.read()["trace_s"] == pytest.approx(n * 0.001)
    clock.span(TRACE, 99.0, 111.0)         # the program's own trace
    assert clock.read()["trace_s"] == pytest.approx(12.0)
    # a day later: the marks behind the horizon go, their seconds stay
    clock.span(LOWER, 86500.0, 86501.0)
    clock.span(BACKEND, 86501.0, 86503.0)
    assert clock.read() == pytest.approx({
        "trace_s": 12.0, "lower_s": 1.0, "backend_s": 2.0, "programs": 1,
        "cache_hits": 0, "cache_misses": 0})
    assert len(clock._mine().marks) == 2
