"""Control-plane resilience layer (utils.resilience + its wiring).

Covers: retry policy/budget units, circuit breaker state machine, the
traced_http retry loop against a live httpd (flaky 503s, idempotency replay,
breaker fast-fail), deadline propagation and server-side 504 rejection,
network-level chaos injection (delay/error/reset, route scoping), serving
overload protection (429 + Retry-After, shed-oldest, queued-deadline expiry),
and the acceptance scenarios: a full K-AVG train completing under 10%
injected faults on every internal hop, and journal resume across a PS
restart with chaos enabled.
"""

import threading
import time

import numpy as np
import pytest

from kubeml_tpu.api.errors import KubeMLError, OverloadedError
from kubeml_tpu.utils import resilience
from kubeml_tpu.utils import traced_http
from kubeml_tpu.utils.httpd import Router, Service

from conftest import make_blobs, wait_job_done


@pytest.fixture(autouse=True)
def _fresh_resilience():
    """Breakers/budgets/counters are process-global: isolate every test."""
    resilience.reset_state()
    yield
    resilience.reset_state()


@pytest.fixture
def service():
    """A live httpd with recording routes; yields (url, state dict)."""
    state = {"calls": {}, "headers": {}}

    def record(req):
        name = req.params["name"]
        state["calls"][name] = state["calls"].get(name, 0) + 1
        state["headers"][name] = dict(req.headers)
        return {"name": name, "calls": state["calls"][name]}

    def flaky(req):
        n = state["calls"]["flaky"] = state["calls"].get("flaky", 0) + 1
        if n < int(req.params["succeed_on"]):
            raise KubeMLError("transient", 503)
        return {"calls": n}

    def slow(req):
        time.sleep(0.5)
        return record(req)

    router = Router("resilience-test")
    router.route("GET", "/echo/{name}", record)
    router.route("POST", "/echo/{name}", record)
    router.route("GET", "/flaky/{succeed_on}", flaky)
    router.route("POST", "/flaky/{succeed_on}", flaky)
    router.route("POST", "/slow/{name}", slow)
    svc = Service(router, "127.0.0.1", 0).start()
    try:
        yield svc.url, state
    finally:
        svc.stop()


# --- RetryPolicy / RetryBudget ---


def test_retry_policy_backoff_bounds():
    import random

    p = resilience.RetryPolicy(attempts=5, backoff=0.1, backoff_max=0.4)
    rng = random.Random(0)
    for attempt in range(6):
        d = p.delay(attempt, rng)
        cap = min(0.1 * 2 ** attempt, 0.4)
        assert 0.5 * cap <= d <= cap  # full-jitter in [0.5, 1.0] x base


def test_retry_policy_from_config(monkeypatch):
    monkeypatch.setenv("KUBEML_RETRY_ATTEMPTS", "7")
    monkeypatch.setenv("KUBEML_RETRY_BACKOFF", "0.25")
    from kubeml_tpu.api.config import Config, set_config

    set_config(Config())
    try:
        p = resilience.RetryPolicy.from_config()
        assert p.attempts == 7 and p.backoff == 0.25
    finally:
        monkeypatch.undo()
        set_config(Config())


def test_retry_budget_throttles():
    b = resilience.RetryBudget(ratio=0.5, cap=3.0, initial=1.0)
    assert b.withdraw()          # spends the initial token
    assert not b.withdraw()      # empty
    for _ in range(2):
        b.deposit()              # 2 * 0.5 = 1 token earned
    assert b.withdraw()
    for _ in range(100):
        b.deposit()
    assert b.tokens == 3.0       # capped


# --- CircuitBreaker ---


def test_breaker_opens_half_opens_and_recovers():
    br = resilience.CircuitBreaker(threshold=3, cooldown=0.1, dest="d")
    for _ in range(2):
        br.record_failure()
    assert br.state == "closed"
    br.record_failure()          # third consecutive: open
    assert br.state == "open"
    assert not br.allow()        # cooling down: fail fast
    time.sleep(0.12)
    assert br.allow()            # half-open probe admitted
    assert br.state == "half-open"
    assert not br.allow()        # a second concurrent probe is not
    br.record_success()          # probe succeeded: closed
    assert br.state == "closed"
    assert br.allow()


def test_breaker_failed_probe_reopens():
    br = resilience.CircuitBreaker(threshold=1, cooldown=0.05, dest="d")
    br.record_failure()
    assert br.state == "open"
    time.sleep(0.06)
    assert br.allow()
    br.record_failure()          # probe failed: back to open, fresh cooldown
    assert br.state == "open"
    assert not br.allow()


def test_breaker_success_resets_consecutive_count():
    br = resilience.CircuitBreaker(threshold=3, cooldown=1.0, dest="d")
    for _ in range(2):
        br.record_failure()
    br.record_success()
    for _ in range(2):
        br.record_failure()
    assert br.state == "closed"  # never 3 CONSECUTIVE failures


# --- traced_http retry loop against a live server ---


def test_idempotent_get_retries_through_503(service):
    url, state = service
    r = traced_http.get(f"{url}/flaky/3", timeout=5)
    assert r.status_code == 200 and r.json()["calls"] == 3
    dest = resilience.destination(url)
    assert resilience.counter_value("kubeml_http_retries_total", dest) == 2


def test_unkeyed_post_is_not_retried(service):
    url, state = service
    r = traced_http.post(f"{url}/flaky/3", json={}, timeout=5)
    assert r.status_code == 503          # single shot: the 503 surfaces
    assert state["calls"]["flaky"] == 1
    assert resilience.counter_value(
        "kubeml_http_retries_total", resilience.destination(url)) == 0


def test_keyed_post_retries_and_replays(service):
    url, state = service
    r = traced_http.post(f"{url}/flaky/3", json={}, timeout=5,
                         idempotency_key="abc123")
    assert r.status_code == 200          # retried through the 503s
    assert state["calls"]["flaky"] == 3
    # redelivery of the SAME key answers from the replay cache: the handler
    # must not run again
    r2 = traced_http.post(f"{url}/flaky/3", json={}, timeout=5,
                          idempotency_key="abc123")
    assert r2.status_code == 200 and r2.json() == r.json()
    assert state["calls"]["flaky"] == 3
    assert resilience.counter_value(
        "kubeml_http_idempotent_replays_total", "resilience-test") >= 1
    # a FRESH key executes again
    r3 = traced_http.post(f"{url}/echo/a", json={}, timeout=5,
                          idempotency_key="k2")
    assert r3.json()["calls"] == 1


def test_breaker_opens_on_dead_destination_and_fails_fast():
    dead = "http://127.0.0.1:9"  # discard port: nothing listens
    for _ in range(6):
        with pytest.raises(traced_http.RequestException):
            traced_http.get(f"{dead}/x", timeout=0.5)
    br = resilience.get_breaker("127.0.0.1:9")
    assert br.state == "open"
    assert resilience.counter_value("kubeml_http_breaker_open_total",
                                    "127.0.0.1:9") == 1
    t0 = time.monotonic()
    with pytest.raises(resilience.CircuitOpenError):
        traced_http.get(f"{dead}/x", timeout=5)
    assert time.monotonic() - t0 < 0.5   # no dial, no timeout burn
    assert resilience.counter_value("kubeml_http_breaker_rejected_total",
                                    "127.0.0.1:9") >= 1


def test_breaker_closes_via_half_open_probe_on_recovery(service, monkeypatch):
    """End-to-end recovery: consecutive TRANSPORT failures (injected
    client-side connection errors) open the circuit for a LIVE destination;
    after the cooldown one probe goes through and closes it (the acceptance
    criterion's open → half-open → closed path)."""
    url, state = service
    dest = resilience.destination(url)
    br = resilience.get_breaker(dest)
    monkeypatch.setattr(br, "cooldown", 0.1)
    monkeypatch.setenv("KUBEML_CHAOS_CLIENT", "1.0")
    for _ in range(br.threshold):
        with pytest.raises(traced_http.ConnectionError):
            traced_http.post(f"{url}/echo/down", json={}, timeout=5)
    assert br.state == "open"
    monkeypatch.setenv("KUBEML_CHAOS_CLIENT", "0")  # "network" recovers
    with pytest.raises(resilience.CircuitOpenError):
        traced_http.get(f"{url}/echo/ping", timeout=5)
    time.sleep(0.12)
    r = traced_http.get(f"{url}/echo/ping", timeout=5)  # the half-open probe
    assert r.status_code == 200
    assert br.state == "closed"


def test_unexpected_transport_exception_settles_the_breaker(monkeypatch):
    """An exception outside (ConnectionError, Timeout) — e.g. a mid-body
    drop raising ChunkedEncodingError — must still record a breaker failure:
    a half-open probe that neither succeeds nor fails would otherwise leave
    the probe flag set and wedge the destination forever."""
    import requests as raw

    def boom(*a, **k):
        raise raw.exceptions.ChunkedEncodingError("mid-body drop")

    monkeypatch.setattr(raw, "request", boom)
    br = resilience.CircuitBreaker(threshold=1, cooldown=30.0, dest="d")
    monkeypatch.setitem(resilience._breakers, "127.0.0.1:9", br)
    # drive the breaker to half-open, then probe into the unexpected error
    br.record_failure()
    br._opened_at -= 60  # cooldown elapsed
    with pytest.raises(raw.exceptions.ChunkedEncodingError):
        resilience.resilient_request("GET", "http://127.0.0.1:9/x",
                                     retryable=False, timeout=1)
    assert br.state == "open"          # probe settled as a failure...
    br._opened_at -= 60
    assert br.allow()                  # ...so a later probe is still possible


# --- deadlines ---


def test_deadline_header_round_trip():
    d = time.time() + 3.5
    assert resilience.parse_deadline(resilience.format_deadline(d)) == pytest.approx(d)
    for bad in (None, "", "garbage", "-5"):
        assert resilience.parse_deadline(bad) is None


def test_clamp_timeout_caps_read_not_connect():
    assert resilience.clamp_timeout(10.0, 2.0) == 2.0
    assert resilience.clamp_timeout((3.0, 10.0), 2.0) == (3.0, 2.0)
    assert resilience.clamp_timeout(None, 2.0) == 2.0
    assert resilience.clamp_timeout(1.0, 5.0) == 1.0


def test_server_rejects_expired_deadline_with_504(service):
    url, state = service
    r = traced_http.request(
        "POST", f"{url}/echo/dead", json={},
        headers={resilience.DEADLINE_HEADER: str(time.time() - 1)}, timeout=5)
    assert r.status_code == 504
    assert "dead" not in state["calls"]  # the handler never ran
    assert resilience.counter_value("kubeml_http_deadline_rejected_total",
                                    "resilience-test") >= 1


def test_bound_deadline_propagates_and_binds_downstream(service):
    url, state = service
    d = time.time() + 30
    with resilience.bind_deadline(d):
        traced_http.get(f"{url}/echo/p", timeout=5)
    sent = state["headers"]["p"].get(resilience.DEADLINE_HEADER)
    assert sent is not None and float(sent) == pytest.approx(d)


def test_origin_stamps_deadline_from_timeout(service):
    url, state = service
    before = time.time()
    traced_http.get(f"{url}/echo/q", timeout=7)
    sent = float(state["headers"]["q"][resilience.DEADLINE_HEADER])
    assert before + 6 < sent < time.time() + 8


def test_expired_bound_deadline_fails_before_sending(service):
    url, state = service
    with resilience.bind_deadline(time.time() - 1):
        with pytest.raises(resilience.DeadlineExpiredError):
            traced_http.get(f"{url}/echo/never", timeout=5)
    assert "never" not in state["calls"]


# --- chaos injection ---


def test_chaos_seeded_determinism():
    a = resilience.ChaosConfig(server_p=0.5, seed=42)
    b = resilience.ChaosConfig(server_p=0.5, seed=42)
    fa = [a.server_fault("/x") for _ in range(50)]
    fb = [b.server_fault("/x") for _ in range(50)]
    assert fa == fb
    assert any(f is not None for f in fa)
    assert any(f is None for f in fa)


def test_chaos_route_scoping_and_exemptions():
    c = resilience.ChaosConfig(server_p=1.0, routes="^/train", modes="error")
    assert c.server_fault("/train")[0] == "error"
    assert c.server_fault("/generate") is None
    # health/metrics stay observable even under a match-everything regex
    c2 = resilience.ChaosConfig(server_p=1.0, modes="error")
    assert c2.server_fault("/health") is None
    assert c2.server_fault("/metrics") is None
    assert c2.client_fault("http://h:1/health") is False


def test_chaos_server_error_mode(service, monkeypatch):
    url, state = service
    monkeypatch.setenv("KUBEML_CHAOS", "1.0")
    monkeypatch.setenv("KUBEML_CHAOS_MODES", "error")
    r = traced_http.post(f"{url}/echo/x", json={}, timeout=5)
    assert r.status_code == 500 and "chaos" in r.json()["error"]
    assert "x" not in state["calls"]  # injected BEFORE dispatch: no side effects
    assert resilience.counter_value("kubeml_chaos_injected_total", "error") >= 1


def test_chaos_server_reset_mode_then_retry_recovers(service, monkeypatch):
    url, state = service
    monkeypatch.setenv("KUBEML_CHAOS", "1.0")
    monkeypatch.setenv("KUBEML_CHAOS_MODES", "reset")
    with pytest.raises(traced_http.RequestException):
        traced_http.post(f"{url}/echo/y", json={}, timeout=5)
    monkeypatch.setenv("KUBEML_CHAOS", "0.4")
    monkeypatch.setenv("KUBEML_CHAOS_SEED", "3")
    # idempotent call: retries ride through the probabilistic resets
    r = traced_http.get(f"{url}/echo/z", timeout=5)
    assert r.status_code == 200


def test_chaos_client_injection(service, monkeypatch):
    url, state = service
    monkeypatch.setenv("KUBEML_CHAOS_CLIENT", "1.0")
    with pytest.raises(traced_http.ConnectionError):
        traced_http.post(f"{url}/echo/c", json={}, timeout=5)
    assert "c" not in state["calls"]
    assert resilience.counter_value("kubeml_chaos_injected_total",
                                    "client") >= 1


def test_use_breaker_false_bypasses_the_breaker():
    """A caller owning its own retry schedule (the PS /start boot loop) can
    opt out: transport failures neither gate on nor feed the breaker."""
    for _ in range(8):
        with pytest.raises(traced_http.RequestException):
            traced_http.get("http://127.0.0.1:9/x", timeout=0.5,
                            use_breaker=False)
    assert resilience.get_breaker("127.0.0.1:9").state == "closed"


def test_registries_and_counter_labels_are_bounded():
    """Ephemeral runner destinations must not grow the breaker/budget
    registries or the /metrics label set forever."""
    for i in range(resilience.MAX_DESTINATIONS + 10):
        resilience.get_breaker(f"h:{i}")
        resilience.get_budget(f"h:{i}")
    assert len(resilience._breakers) <= resilience.MAX_DESTINATIONS
    assert len(resilience._budgets) <= resilience.MAX_DESTINATIONS
    for i in range(resilience.MAX_LABELS_PER_METRIC + 10):
        resilience.incr("kubeml_http_retries_total", f"d{i}")
    labels = [k for k, _ in resilience.counters_snapshot().items()
              if k[0] == "kubeml_http_retries_total"]
    assert len(labels) <= resilience.MAX_LABELS_PER_METRIC
    # the newest label survived the eviction
    assert resilience.counter_value(
        "kubeml_http_retries_total",
        f"d{resilience.MAX_LABELS_PER_METRIC + 9}") == 1


def test_origin_read_timeout_still_retries(monkeypatch):
    """At the ORIGIN (no bound deadline) a read timeout must not consume the
    retry schedule: the per-attempt deadline header is re-stamped instead of
    gating the loop, so the most common transient still gets its attempts."""
    import requests as raw

    calls = {"n": 0, "deadlines": []}

    def always_timeout(method, url, timeout=None, headers=None, **kw):
        calls["n"] += 1
        calls["deadlines"].append(float(headers[resilience.DEADLINE_HEADER]))
        raise raw.Timeout("read timed out")

    monkeypatch.setattr(raw, "request", always_timeout)
    with pytest.raises(raw.Timeout):
        traced_http.get("http://127.0.0.1:9/x", timeout=0.2)
    assert calls["n"] == 3  # full schedule, not one-and-done
    # each attempt stamped a FRESH deadline (monotonically non-decreasing)
    assert calls["deadlines"] == sorted(calls["deadlines"])


def test_retry_after_survives_the_envelope_across_hops():
    """A proxied 429 rebuilds as OverloadedError with its retry_after — the
    hint rides IN the envelope, not just the (dropped) header."""
    from kubeml_tpu.api.errors import error_from_envelope

    e = OverloadedError("queue full", retry_after=12.0)
    rebuilt = error_from_envelope(e.to_json(), 429)
    assert isinstance(rebuilt, OverloadedError)
    assert rebuilt.status_code == 429 and rebuilt.retry_after == 12.0
    # and a second proxy hop keeps it intact
    again = error_from_envelope(rebuilt.to_json(), 429)
    assert again.retry_after == 12.0


def test_http_statuses_do_not_feed_the_breaker(service):
    """Any RESPONSE proves reachability: a deterministically-broken handler
    (500) or an application 503 ("job still starting") must not blackhole
    the whole destination — only transport failures trip the breaker."""
    url, state = service
    dest = resilience.destination(url)
    br = resilience.get_breaker(dest)
    # int("notanumber") blows up inside the handler -> generic 500 envelope
    for _ in range(br.threshold + 2):
        r = traced_http.post(f"{url}/flaky/notanumber", json={}, timeout=5)
        assert r.status_code == 500
    for _ in range(br.threshold + 2):
        r = traced_http.post(f"{url}/flaky/100", json={}, timeout=5)
        assert r.status_code == 503
    assert br.state == "closed"


def test_concurrent_duplicate_keyed_post_executes_once(service):
    """The in-flight replay marker: a duplicate keyed POST racing the slow
    original waits for it and replays its record — one execution total,
    whatever the interleaving."""
    url, state = service
    results = []

    def send():
        r = traced_http.post(f"{url}/slow/racekey", json={}, timeout=10,
                             idempotency_key="race-1")
        results.append(r.json())

    t1 = threading.Thread(target=send)
    t2 = threading.Thread(target=send)
    t1.start()
    time.sleep(0.1)  # t2 arrives while t1's handler is mid-sleep
    t2.start()
    t1.join(30)
    t2.join(30)
    assert len(results) == 2
    assert state["calls"]["racekey"] == 1, "duplicate executed the handler"
    assert results[0] == results[1]


# --- ReplayCache ---


def test_replay_cache_ttl_and_bound():
    rc = resilience.ReplayCache(max_entries=2, ttl=0.05)
    rc.put("POST", "/a", "k", "ra")
    assert rc.get("POST", "/a", "k") == "ra"
    assert rc.get("POST", "/a", "other") is None
    time.sleep(0.06)
    assert rc.get("POST", "/a", "k") is None  # expired
    rc.put("POST", "/a", "1", "r1")
    rc.put("POST", "/a", "2", "r2")
    rc.put("POST", "/a", "3", "r3")  # evicts oldest
    assert rc.get("POST", "/a", "1") is None
    assert rc.get("POST", "/a", "3") == "r3"


# --- serving overload protection ---


def _idle_decoder(**kw):
    """A BatchingDecoder whose engine loop never starts (a dummy thread
    sentinel), so queue/admission semantics are tested deterministically."""
    import jax

    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.serving.batcher import BatchingDecoder

    m = CausalTransformer(vocab_size=61, max_len=64, embed_dim=32, depth=1,
                          num_heads=2)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    dec = BatchingDecoder(m, variables, **kw)
    dec._thread = threading.Thread(target=lambda: None)  # never started
    return dec


def _gen_req(**kw):
    from kubeml_tpu.api.types import GenerateRequest

    kw.setdefault("prompts", [[1, 2, 3]])
    kw.setdefault("max_new_tokens", 4)
    return GenerateRequest(**kw)


def test_queue_limit_rejects_with_429_and_retry_after():
    dec = _idle_decoder(slots=1, queue_limit=2, shed_policy="reject")
    dec.submit(_gen_req())
    dec.submit(_gen_req())
    with pytest.raises(OverloadedError) as ei:
        dec.submit(_gen_req())
    assert ei.value.status_code == 429
    assert ei.value.retry_after >= 1.0
    snap = dec.stats.snapshot()
    assert snap["requests_overload"] == 1.0
    assert snap["requests_submitted"] == 2.0  # the refused one never queued
    assert dec.telemetry()["queue_limit"] == 2.0


def test_batch_wider_than_limit_admits_into_empty_queue():
    """The limit bounds QUEUE pressure, not batch width: a request with more
    rows than queue_limit must still admit when nothing is queued (rejecting
    it would be permanent — no retry could ever succeed)."""
    dec = _idle_decoder(slots=1, queue_limit=2, shed_policy="reject")
    wide = dec.submit(_gen_req(prompts=[[1, 2], [3, 4], [5, 6], [7, 8]]))
    assert len(dec._pending) == 4
    assert not wide.done_evt.is_set()
    # but with the queue non-empty the limit applies again
    with pytest.raises(OverloadedError):
        dec.submit(_gen_req())


def test_shed_oldest_policy_frees_room_for_fresh_work():
    dec = _idle_decoder(slots=1, queue_limit=2, shed_policy="oldest")
    e1 = dec.submit(_gen_req())
    e2 = dec.submit(_gen_req())
    e3 = dec.submit(_gen_req())      # sheds e1, admits e3
    assert e1.done_evt.is_set()
    assert isinstance(e1.error, OverloadedError)
    assert not e2.done_evt.is_set() and not e3.done_evt.is_set()
    with pytest.raises(OverloadedError):
        dec.wait(e1, timeout=1)
    assert dec.stats.snapshot()["requests_shed"] == 1.0
    # queue still holds exactly the limit
    assert len(dec._pending) == 2


def test_queued_rows_expire_on_deadline():
    dec = _idle_decoder(slots=1, queue_limit=0)
    dec._warmed = True  # no cold-compile allowance
    with resilience.bind_deadline(time.time() - 1):
        expired = dec.submit(_gen_req())
    with resilience.bind_deadline(time.time() + 60):
        alive = dec.submit(_gen_req())
    dec._sweep_expired()
    assert expired.done_evt.is_set()
    assert isinstance(expired.error, KubeMLError)
    assert expired.error.status_code == 504
    assert not alive.done_evt.is_set()
    assert dec.stats.snapshot()["requests_deadline_expired"] == 1.0
    assert len(dec._pending) == 1


def test_batcher_serves_normally_under_limit():
    """A real engine run with the limit configured: traffic under the limit
    is completely unaffected (tier-1 parity guard for the admission path)."""
    import jax

    from kubeml_tpu.api.types import GenerateRequest
    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.serving.batcher import BatchingDecoder

    m = CausalTransformer(vocab_size=61, max_len=32, embed_dim=32, depth=1,
                          num_heads=2)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    dec = BatchingDecoder(m, variables, slots=2, chunk_steps=4, queue_limit=64)
    try:
        entries = [dec.submit(_gen_req(max_new_tokens=5)) for _ in range(4)]
        for e in entries:
            out = dec.wait(e, timeout=300)
            assert out["lengths"] == [5]
        assert dec.stats.snapshot()["requests_completed"] == 4.0
    finally:
        dec.close()


# --- /metrics exposition carries the resilience counters ---


def test_metrics_render_includes_resilience_series():
    from kubeml_tpu.ps.metrics import MetricsRegistry

    resilience.incr("kubeml_http_retries_total", "h:1")
    resilience.get_breaker("h:1")
    text = MetricsRegistry().render()
    assert 'kubeml_http_retries_total{dest="h:1"} 1' in text
    assert 'kubeml_http_breaker_state{dest="h:1"} 0' in text
    assert "kubeml_serving_requests_overload_total" in text
    assert "kubeml_serving_requests_shed_total" in text
    assert "kubeml_serving_deadline_expired_total" in text


def test_update_timeout_knob(monkeypatch):
    monkeypatch.setenv("KUBEML_UPDATE_TIMEOUT", "7.5")
    from kubeml_tpu.api.config import Config

    assert Config().update_timeout == 7.5


def test_timeouts_helper_builds_connect_read_tuple():
    t = traced_http.timeouts(30)
    assert isinstance(t, tuple) and t[1] == 30 and 0 < t[0] < 30
    assert traced_http.timeouts(10, connect=2.0) == (2.0, 10)


# --- acceptance: the control plane under 10% chaos on every hop ---


@pytest.fixture
def chaos_cluster(tmp_config, monkeypatch):
    """A LocalCluster with 10% injected transport faults on every internal
    hop (server delay/500/reset + client-side connection errors), retries
    sized so the job survives."""
    monkeypatch.setenv("KUBEML_CHAOS", "0.1")
    monkeypatch.setenv("KUBEML_CHAOS_CLIENT", "0.05")
    monkeypatch.setenv("KUBEML_CHAOS_SEED", "1234")
    monkeypatch.setenv("KUBEML_CHAOS_DELAY", "0.05")
    monkeypatch.setenv("KUBEML_RETRY_ATTEMPTS", "5")
    monkeypatch.setenv("KUBEML_RETRY_BUDGET", "10")
    # under sustained 10% chaos a run of 5 consecutive injected faults is
    # statistically reachable; the breaker's job is proven by its own tests,
    # here it must not open mid-poll and flake the acceptance scenario
    monkeypatch.setenv("KUBEML_BREAKER_THRESHOLD", "100")
    from kubeml_tpu.api.config import Config, set_config
    from kubeml_tpu.cluster import LocalCluster

    cfg = Config(
        data_root=tmp_config.data_root,
        controller_port=tmp_config.controller_port,
        scheduler_port=tmp_config.scheduler_port,
        ps_port=tmp_config.ps_port,
        storage_port=tmp_config.storage_port,
    )
    set_config(cfg)
    with LocalCluster(config=cfg) as c:
        yield c


@pytest.mark.chaos
def test_train_completes_under_injected_network_faults(chaos_cluster):
    """Acceptance: with chaos injecting ~10% transient failures on every
    internal hop, a full K-AVG train job completes without manual
    intervention, and the retry counters are visible on /metrics."""
    from kubeml_tpu.api.types import TrainOptions, TrainRequest
    from kubeml_tpu.controller.client import KubemlClient

    from test_controlplane import FN_SOURCE

    client = KubemlClient(chaos_cluster.controller_url)
    x, y = make_blobs(256, shape=(8, 8, 1))
    client.datasets().create("blobs", x, y, x[:64], y[:64])
    client.functions().create("ctiny", FN_SOURCE)
    req = TrainRequest(
        model_type="ctiny", batch_size=16, epochs=2, dataset="blobs",
        lr=0.05, function_name="ctiny",
        options=TrainOptions(default_parallelism=2, k=2,
                             static_parallelism=True))
    job_id = client.networks().train(req)
    wait_job_done(client, job_id, timeout=240)
    hist = client.histories().get(job_id)
    assert len(hist.train_loss) == 2
    assert all(np.isfinite(l) for l in hist.train_loss)
    # faults were actually injected, and the metrics surface shows the layer
    metrics = traced_http.get(
        f"{chaos_cluster.ps_api.url}/metrics", timeout=10).text
    assert "kubeml_chaos_injected_total" in metrics
    assert "kubeml_http_retries_total" in metrics
    injected = sum(v for (m, _), v in resilience.counters_snapshot().items()
                   if m == "kubeml_chaos_injected_total")
    assert injected > 0, "chaos never fired — the test proved nothing"


@pytest.mark.chaos
def test_journal_resume_across_ps_restart_under_chaos(tmp_config, monkeypatch):
    """Satellite: a checkpointing job interrupted by a control-plane restart
    (the threaded-mode PS dies with the process) is resubmitted from the
    journal on the next boot WITH chaos enabled on every hop, resumes from
    its newest checkpoint, and converges."""
    from kubeml_tpu.api.types import TrainOptions, TrainRequest
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.controller.client import KubemlClient

    from test_controlplane import FN_SOURCE

    # many more epochs than can complete between the first checkpoint and
    # the kill below — the interruption must land MID-JOB even on a warm
    # process where each epoch is fast (XLA cache primed by earlier tests)
    req = TrainRequest(
        model_type="rtiny", batch_size=16, epochs=40, dataset="blobs",
        lr=0.05, function_name="rtiny",
        options=TrainOptions(default_parallelism=2, k=2,
                             static_parallelism=True, checkpoint_every=1))

    with LocalCluster(config=tmp_config) as cluster:
        client = KubemlClient(cluster.controller_url)
        x, y = make_blobs(256, shape=(8, 8, 1))
        client.datasets().create("blobs", x, y, x[:64], y[:64])
        client.functions().create("rtiny", FN_SOURCE)
        job_id = client.networks().train(req)
        # wait for the first epoch checkpoint, then "kill" the control plane
        ckpt_dir = tmp_config.checkpoints_dir / job_id
        deadline = time.time() + 120
        while time.time() < deadline:
            if ckpt_dir.exists() and any(ckpt_dir.iterdir()):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("no checkpoint appeared before the kill")
    # the stop() path keeps journals (supervised-restart semantics)
    from kubeml_tpu.ps.journal import JobJournal

    assert [e["job_id"] for e in JobJournal(config=tmp_config).pending()] == [job_id]

    # second life: chaos on every hop while the journaled job resumes.
    # The config is REBUILT after the env flips so the bumped retry knobs
    # actually apply (Config reads the environment at construction).
    monkeypatch.setenv("KUBEML_CHAOS", "0.1")
    monkeypatch.setenv("KUBEML_CHAOS_SEED", "7")
    monkeypatch.setenv("KUBEML_RETRY_ATTEMPTS", "6")
    monkeypatch.setenv("KUBEML_RETRY_BUDGET", "10")
    monkeypatch.setenv("KUBEML_BREAKER_THRESHOLD", "100")
    from kubeml_tpu.api.config import Config, set_config

    cfg2 = Config(
        data_root=tmp_config.data_root,
        controller_port=tmp_config.controller_port,
        scheduler_port=tmp_config.scheduler_port,
        ps_port=tmp_config.ps_port,
        storage_port=tmp_config.storage_port,
    )
    set_config(cfg2)
    # phase 1 already built breakers for these ports under the default
    # threshold; the restart must pick up the phase-2 knobs
    resilience.reset_state()
    with LocalCluster(config=cfg2) as cluster2:
        client2 = KubemlClient(cluster2.controller_url)
        deadline = time.time() + 240
        while time.time() < deadline:
            if all(t.job_id != job_id for t in client2.tasks().list()):
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("resumed job did not finish under chaos")
        hist = client2.histories().get(job_id)
        losses = [l for l in hist.train_loss if np.isfinite(l)]
        assert losses, f"no finite losses after resume: {hist.train_loss}"
        task = hist.task or {}
        assert "error" not in task, f"resumed job failed: {task.get('error')}"
    # the journal entry cleared with the successful finish
    assert JobJournal(config=tmp_config).pending() == []
