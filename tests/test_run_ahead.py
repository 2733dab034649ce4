"""Run-ahead that follows the step (ISSUE 26): the paged engine keeps only
as many programs in flight as its host's turnaround needs.

* THE RULE — ``run_ahead_depth`` is a pure function of the two estimates
  and the ceiling: the benchmark's ratios give 2, a host as slow as its
  step gives the ceiling, a ceiling of 1 stays 1, no sample is the ceiling.
* THE ENGINE — behind a device that takes 100 ms a program the tiny model's
  engine settles at 2, an admit submitted mid-stream is dispatched with at
  most one program ahead of it, and the depth climbs back when the device
  is fast again.
* EXACTNESS — the depth is scheduling only: greedy and sampled streams,
  beside a canceled row and a chunked prefill, are the same tokens at depth
  2 and at the ceiling.
"""

import threading
import time

import numpy as np
import pytest

import jax

from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import generate
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.serving import PagedBatchingDecoder
from kubeml_tpu.serving import batcher
from kubeml_tpu.serving.batcher import _Recent, run_ahead_depth
from kubeml_tpu.utils import tracing

VOCAB = 101


@pytest.fixture(scope="module")
def served():
    m = CausalTransformer(vocab_size=VOCAB, max_len=96, embed_dim=64,
                          depth=2, num_heads=4)
    return m, m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


# --- the rule (no device work) ---

RULE = [
    # the benchmark's cells (ledger, PR 24): spans of 2.1 and 3.6 ms a step
    # and the same again for the thread's wake, at 49.4 and 59.5 ms a step
    pytest.param(0.0021, 0.0494, 6, 2, id="chat-spans"),
    pytest.param(0.0045, 0.0494, 6, 2, id="chat-with-wake"),
    pytest.param(0.0036, 0.0595, 6, 2, id="docs-spans"),
    pytest.param(0.0075, 0.0595, 6, 2, id="docs-with-wake"),
    # the default chunk ladder: 16 steps a program
    pytest.param(0.004, 16 * 0.0494, 6, 2, id="chunk-of-16"),
    # between the floor and the ceiling it is 1 + ceil(5 host / svc)
    pytest.param(0.012, 0.050, 6, 3, id="a-quarter"),
    pytest.param(0.025, 0.050, 6, 4, id="a-half"),
    pytest.param(0.040, 0.050, 8, 5, id="a-chip-far-away"),
    # a host as slow as its program, or slower: the ceiling
    pytest.param(0.050, 0.050, 6, 6, id="ratio-1"),
    pytest.param(0.0007, 0.0005, 6, 6, id="tiny-model-on-a-cpu"),
    pytest.param(0.200, 0.050, 6, 6, id="ratio-4"),
    pytest.param(0.050, 0.050, 4, 4, id="ratio-1-cap-4"),
    pytest.param(0.050, 0.050, 2, 2, id="ratio-1-cap-2"),
    # a ceiling of 1 is a caller's choice of no run-ahead at all
    pytest.param(0.003, 0.050, 1, 1, id="cap-1"),
    pytest.param(0.050, 0.050, 1, 1, id="cap-1-slow-host"),
    pytest.param(None, None, 1, 1, id="cap-1-no-sample"),
    # no sample of either kind yet: today's depth
    pytest.param(None, None, 6, 6, id="no-sample"),
    pytest.param(None, 0.050, 6, 6, id="no-host-sample"),
    pytest.param(0.003, None, 6, 6, id="no-svc-sample"),
    pytest.param(0.003, 0.0, 6, 6, id="svc-of-zero"),
    pytest.param(0.0, 0.050, 6, 2, id="host-of-zero"),
]


@pytest.mark.parametrize("host_s, svc_s, cap, want", RULE)
def test_run_ahead_depth_rule(host_s, svc_s, cap, want):
    assert run_ahead_depth(host_s, svc_s, cap) == want


def test_recent_is_the_median_of_the_last_eight():
    est = _Recent()
    assert est.value() is None
    est.add(0.050)
    assert est.value() == 0.050          # the first sample is the estimate
    for _ in range(6):
        est.add(0.050)
    est.add(30.0)                        # one slow turn moves nothing
    assert est.value() == 0.050
    for _ in range(5):                   # a changed regime shows after five
        est.add(0.001)
    assert est.value() == 0.001


# --- the engine behind a slow device ---


class SlowDevice:
    """Stands in for ``_materialize``: a device that runs one program at a
    time, ``seconds`` each, in dispatch order. A fetch returns where its
    program would have completed, which is all the engine sees of one."""

    def __init__(self, dec, seconds):
        self.seconds = seconds
        self._real = dec._materialize
        self._lock = threading.Lock()
        self._free_at = 0.0
        dec._materialize = self

    def __call__(self, rec):
        out = self._real(rec)
        with self._lock:
            done = max(time.monotonic(), self._free_at) + self.seconds
            self._free_at = done
        time.sleep(max(0.0, done - time.monotonic()))
        return out


def _until(cond, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting until {what}")


def _prompt(first, n=5):
    return [[first] + list(range(2, n + 1))]


@pytest.fixture
def tracer():
    t = tracing.get_tracer()
    was_on = t.enabled
    t.clear()
    t.enabled = True
    yield t
    t.enabled = was_on
    t.clear()


def test_depth_follows_the_step_and_climbs_back(served, tracer):
    dec = PagedBatchingDecoder(*served, slots=2, chunk_steps=1,
                               page_tokens=4)
    cap = dec.pipeline_depth
    assert cap == 6 and dec.telemetry()["run_ahead_depth"] == cap
    device = SlowDevice(dec, 0.1)
    depth = lambda: dec.telemetry()["run_ahead_depth"]
    try:
        first = dec.submit(GenerateRequest(prompts=_prompt(1),
                                           max_new_tokens=28))
        # the first warm step program is sample enough
        _until(lambda: depth() == 2, "the depth fell to 2")
        late = dec.submit(GenerateRequest(prompts=_prompt(7),
                                          max_new_tokens=4))
        assert len(dec.wait(late, timeout=120)["tokens"][0]) == 4
        assert len(dec.wait(first, timeout=120)["tokens"][0]) == 28
        admits = {s.attrs["requests"]: s.attrs for s in tracer.spans()
                  if s.name == "engine.dispatch"
                  and s.attrs["program"] == "admit"}
        # onto an idle engine, at the ceiling: nothing is ahead of it
        assert admits[first.request_id]["ahead"] == 0
        assert admits[first.request_id]["depth"] == cap
        # mid-stream: behind the one running step, and no other
        assert admits[late.request_id]["depth"] == 2
        assert 0 <= admits[late.request_id]["ahead"] <= 1
        steps = [s.attrs for s in tracer.spans()
                 if s.name == "engine.dispatch"
                 and s.attrs["program"] == "step"]
        assert all(s["ahead"] < s["depth"] <= cap for s in steps)
        assert all(s["ahead"] <= 1 for s in steps if s["depth"] == 2)
        # a fast device again: a step is about the host's own time here,
        # and the depth leaves the floor
        device.seconds = 0.0
        again = dec.submit(GenerateRequest(prompts=_prompt(9),
                                           max_new_tokens=60))
        _until(lambda: depth() > 2, "the depth climbed back")
        assert len(dec.wait(again, timeout=120)["tokens"][0]) == 60
    finally:
        dec.close()


def test_cap_of_one_stays_one(served):
    dec = PagedBatchingDecoder(*served, slots=2, chunk_steps=1,
                               page_tokens=4, pipeline_depth=1)
    SlowDevice(dec, 0.02)
    try:
        out = dec.wait(dec.submit(GenerateRequest(
            prompts=_prompt(1), max_new_tokens=8)), timeout=120)
        assert len(out["tokens"][0]) == 8
        assert dec._svc_s.value() is not None       # the estimates are warm
        assert dec.telemetry()["run_ahead_depth"] == 1
    finally:
        dec.close()


# --- exactness: the depth is scheduling only ---

SAMPLING = [
    pytest.param({}, id="greedy"),
    pytest.param({"temperature": 0.8, "top_k": 7, "seed": 42}, id="sampled"),
]


def _scenario(served, forced, chunk_steps, kw, monkeypatch):
    """Four requests on two slots at one forced depth: a long answer, a
    44-token prompt prefilled in chunks of 8, a request canceled after its
    first token, and one that queues for the slot the cancel frees."""
    monkeypatch.setattr(batcher, "run_ahead_depth",
                        lambda host_s, svc_s, cap: min(cap, forced))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=(1, n)).astype(np.int32)
               for n in (6, 44, 9, 7)]
    dec = PagedBatchingDecoder(*served, slots=2, chunk_steps=chunk_steps,
                               page_tokens=4, prefill_chunk_tokens=8)
    depths = set()
    try:
        doomed = dec.submit(GenerateRequest(
            prompts=prompts[2].tolist(), max_new_tokens=40, stream=True,
            **kw))
        kept = [dec.submit(GenerateRequest(prompts=p.tolist(),
                                           max_new_tokens=n, **kw))
                for p, n in zip((prompts[0], prompts[1], prompts[3]),
                                (24, 9, 12))]
        for item in dec.stream(doomed):
            if "tokens" in item:
                dec.cancel(doomed)
                break
        outs = []
        for e in kept:
            outs.append(dec.wait(e, timeout=600))
            depths.add(dec.telemetry()["run_ahead_depth"])
        _until(lambda: not dec._busy() and not dec._draining,
               "the engine drained")
        assert dec._prefill_pending == []
        dec._pool.check()     # raises on a leaked or twice-freed page
    finally:
        dec.close()
    assert depths == {float(forced)}
    return prompts, outs


@pytest.mark.parametrize("chunk_steps", [1, 4])
@pytest.mark.parametrize("kw", SAMPLING)
def test_streams_identical_at_depth_two_and_ceiling(served, monkeypatch, kw,
                                                    chunk_steps):
    prompts, shallow = _scenario(served, 2, chunk_steps, kw, monkeypatch)
    _, deep = _scenario(served, 6, chunk_steps, kw, monkeypatch)
    for a, b in zip(shallow, deep):
        assert a["tokens"] == b["tokens"] and a["lengths"] == b["lengths"]
    assert [o["lengths"] for o in shallow] == [[24], [9], [12]]
    assert shallow[1]["prefill_chunks"] >= 2     # the 44 tokens were chunked
    if not kw:
        # greedy has a truth outside the engine: the one-shot program
        for p, o in zip((prompts[0], prompts[1], prompts[3]), shallow):
            ref = generate(served[0], served[1], p,
                           max_new_tokens=len(o["tokens"][0]))
            assert o["tokens"][0] == np.asarray(ref.tokens)[0].tolist()
