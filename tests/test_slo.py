"""Serving SLO observability (PR 11): the embedded time-series store
(utils/timeseries.py), the declarative SLO engine (ps/slo.py — objectives,
multi-window burn rates, the pending→firing→resolved alert machine), the
PS's /metrics/history + /slo surfaces, and behavior parity of the
preemption controller's rewired overload signal against the old
hand-rolled window."""

import threading
import time

import pytest

from kubeml_tpu.ps.slo import (FIRING, INACTIVE, PENDING, Objective,
                               SLOEngine, parse_objectives)
from kubeml_tpu.utils.timeseries import Sampler, Series, TimeSeriesStore

T0 = 1_000_000.0  # synthetic wall-clock origin


# --- Series: the one windowed-rate implementation ---


def test_series_counter_increase_and_rate():
    s = Series(capacity=128, kind="counter")
    for i in range(11):
        s.observe(i * 5.0, t=T0 + i)  # +5/s for 10s
    assert s.increase(10.0, now=T0 + 10) == pytest.approx(50.0)
    assert s.rate(10.0, now=T0 + 10) == pytest.approx(5.0)
    # a narrower window sees only its own increase
    assert s.increase(2.0, now=T0 + 10) == pytest.approx(10.0)


def test_series_counter_reset_counts_like_prometheus():
    s = Series(kind="counter")
    s.observe(100.0, t=T0)
    s.observe(120.0, t=T0 + 1)
    s.observe(3.0, t=T0 + 2)   # process restarted: counter reset
    s.observe(10.0, t=T0 + 3)
    # 20 (before reset) + 3 (the reset sample's full value) + 7
    assert s.increase(10.0, now=T0 + 3) == pytest.approx(30.0)


def test_series_rate_decays_to_zero_across_idle_gap():
    """A counter that stops moving must read rate 0 once the window slides
    past its last increment — the property the old hand-rolled overload
    deque provided and the preemption controller's calm detection needs."""
    s = Series(kind="counter")
    s.observe(0.0, t=T0)
    for i in range(5):
        s.observe(i + 1.0, t=T0 + i + 1)  # 5 events over 5s
    assert s.rate(10.0, now=T0 + 5) == pytest.approx(0.5)
    # 20s later, no new events: the cumulative value is unchanged, so the
    # windowed increase is 0 — even though the ring still holds samples
    s.observe(5.0, t=T0 + 25)
    assert s.rate(10.0, now=T0 + 25) == 0.0


def test_series_elapsed_span_reads_burst_rate():
    """span="elapsed" divides by the time the window actually covers — a
    fresh 2-second burst reads as its burst rate (the serving tokens/sec
    semantics), not diluted over the full window."""
    s = Series(kind="counter")
    s.observe(0.0, t=T0)
    s.observe(100.0, t=T0 + 1)
    s.observe(200.0, t=T0 + 2)
    assert s.rate(10.0, now=T0 + 2, span="elapsed") == pytest.approx(100.0)
    # the plain rate dilutes the same increase over the whole window
    assert s.rate(10.0, now=T0 + 2) == pytest.approx(20.0)


def test_series_gauge_quantiles_and_window():
    s = Series()
    for i in range(100):
        s.observe(float(i), t=T0 + i)
    assert s.quantile(0.5, window=100.0, now=T0 + 99) == pytest.approx(50.0)
    assert s.max_over(10.0, now=T0 + 99) == 99.0
    # only the samples inside the window survive the cut
    assert s.quantile(0.0, window=10.0, now=T0 + 99) == 89.0
    assert s.quantile(0.5, window=1.0, now=T0 + 500) is None  # empty window


def test_series_ring_bounded():
    s = Series(capacity=16)
    for i in range(100):
        s.observe(float(i), t=T0 + i)
    assert len(s) == 16
    assert s.samples()[0][1] == 84.0  # oldest evicted


# --- TimeSeriesStore + Sampler ---


def test_store_kind_inference_and_eviction():
    st = TimeSeriesStore(capacity=8, max_series=3)
    assert st.series("kubeml_x_total").kind == "counter"
    assert st.series('kubeml_y_total{model="m"}').kind == "counter"
    assert st.series("kubeml_gauge").kind == "gauge"
    st.series("d")  # 4th series: oldest evicts
    assert st.get("kubeml_x_total") is None
    assert len(st.names()) == 3


def test_store_matching_and_history_payload():
    st = TimeSeriesStore()
    st.record('m_total{model="a"}', 1.0, t=T0)
    st.record('m_total{model="a"}', 5.0, t=T0 + 10)
    st.record('m_total{model="b"}', 2.0, t=T0 + 10)
    st.record("g", 7.0, t=T0 + 10)
    assert sorted(st.matching("m_total")) == ['m_total{model="a"}',
                                              'm_total{model="b"}']
    hist = st.history(stats=True, stats_window=30.0, now=T0 + 10)
    e = hist["series"]['m_total{model="a"}']
    assert e["kind"] == "counter" and e["latest"] == 5.0
    assert e["increase"] == pytest.approx(4.0)
    assert len(e["samples"]) == 2
    g = hist["series"]["g"]
    assert g["kind"] == "gauge" and g["p50"] == 7.0
    # match filter + samples suppression
    hist2 = st.history(match="m_total", include_samples=False)
    assert list(hist2["series"]) == ['m_total{model="a"}',
                                     'm_total{model="b"}']
    assert "samples" not in hist2["series"]['m_total{model="b"}']


def test_sampler_tick_collects_and_hooks():
    st = TimeSeriesStore()
    ticks = []
    sampler = Sampler(st, interval=0.01)
    sampler.add_collector(lambda: {"a_total": 1.0, "b": 2.0})
    sampler.add_collector(lambda: 1 / 0)  # broken collector is skipped
    sampler.add_tick_hook(ticks.append)
    sampler.tick(now=T0)
    assert st.get("a_total").latest() == 1.0
    assert st.get("b").latest() == 2.0
    assert ticks == [T0]


def test_sampler_thread_lifecycle():
    st = TimeSeriesStore()
    sampler = Sampler(st, interval=0.02)
    sampler.add_collector(lambda: {"n": time.time()})
    sampler.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and len(st.series("n")) < 2:
            time.sleep(0.02)
        assert len(st.series("n")) >= 2
    finally:
        sampler.stop()
    assert sampler._thread is None


# --- SLO objective parsing + burn math ---


def test_parse_objectives_spec():
    objs = parse_objectives(
        "availability>=0.99;overload_rate<=5;p99-ttft:ttft_p99<=0.5@2")
    assert [o.name for o in objs] == ["availability", "overload_rate",
                                      "p99-ttft"]
    assert objs[2].signal == "ttft_p99"
    assert objs[2].burn_threshold == 2.0
    # malformed / unknown / duplicate entries are skipped, not fatal
    objs = parse_objectives(
        "garbage!!;nosuchsignal<=1;availability>=0.99;availability>=0.9;")
    assert [o.name for o in objs] == ["availability"]
    assert objs[0].target == 0.99
    # floors need a (0,1) target; ceilings a positive one
    assert parse_objectives("availability>=1.5") == []
    assert parse_objectives("overload_rate<=0") == []


def test_burn_math():
    avail = Objective.parse("availability>=0.99")
    assert avail.burn(1.0) == 0.0
    assert avail.burn(0.99) == pytest.approx(1.0)
    assert avail.burn(0.9) == pytest.approx(10.0)   # 10x the budget
    assert avail.burn(None) == 0.0                  # no traffic, no burn
    ceil = Objective.parse("overload_rate<=5")
    assert ceil.burn(5.0) == pytest.approx(1.0)
    assert ceil.burn(15.0) == pytest.approx(3.0)
    assert ceil.burn(0.0) == 0.0


# --- SLO signals over the store ---


def _seed_traffic(st: TimeSeriesStore, now: float, completed=0.0, failed=0.0,
                  overload=0.0, model="m"):
    """Two samples bracketing the window so counter increases are visible."""
    pairs = (
        ("kubeml_serving_requests_completed_total", completed),
        ("kubeml_serving_requests_failed_total", failed),
        ("kubeml_serving_requests_overload_total", overload),
    )
    for metric, v in pairs:
        st.record(f'{metric}{{model="{model}"}}', 0.0, t=now - 60)
        st.record(f'{metric}{{model="{model}"}}', v, t=now)


def test_signal_availability_and_overload_rate():
    st = TimeSeriesStore()
    eng = SLOEngine(st, parse_objectives("availability>=0.99"))
    now = T0 + 100
    assert eng.signal_value("availability", 30.0, now=now) is None  # no data
    _seed_traffic(st, now, completed=90.0, overload=10.0)
    assert eng.signal_value("availability", 120.0, now=now) == \
        pytest.approx(0.9)
    assert eng.signal_value("error_rate", 120.0, now=now) == \
        pytest.approx(0.1)
    assert eng.signal_value("overload_rate", 100.0, now=now) == \
        pytest.approx(0.1)
    # gauges: worst recent value across models
    st.record('kubeml_serving_first_token_p99_seconds{model="m"}', 0.3, t=now)
    st.record('kubeml_serving_first_token_p99_seconds{model="n"}', 0.8, t=now)
    assert eng.signal_value("ttft_p99", 30.0, now=now) == 0.8


# --- the alert state machine ---


def _engine(st, spec="availability>=0.99", **kw):
    alerts = []
    kw.setdefault("fast_window", 10.0)
    kw.setdefault("slow_window", 30.0)
    kw.setdefault("for_s", 2.0)
    kw.setdefault("resolve_for_s", 3.0)
    eng = SLOEngine(st, parse_objectives(spec), on_alert=alerts.append, **kw)
    return eng, alerts


def _state(eng, name):
    return eng._states[name].state


def test_alert_pending_firing_resolved_cycle():
    st = TimeSeriesStore()
    eng, alerts = _engine(st)
    now = T0

    def burst(t, overload):
        # availability collapses: only 429s, no completions
        st.record('kubeml_serving_requests_overload_total{model="m"}',
                  overload, t=t)

    st.record('kubeml_serving_requests_overload_total{model="m"}', 0.0,
              t=now - 1)
    eng.evaluate(now=now)
    assert _state(eng, "availability") == INACTIVE

    burst(now + 1, 10.0)
    eng.evaluate(now=now + 1)
    assert _state(eng, "availability") == PENDING
    # held for for_s -> firing, and the alert hook saw the transition
    burst(now + 4, 20.0)
    eng.evaluate(now=now + 4)
    assert _state(eng, "availability") == FIRING
    assert [a["to"] for a in alerts] == ["firing"]
    assert alerts[0]["burn_fast"] >= 1.0
    # traffic recovers: completions flow, 429s stop — burn drops but the
    # alert must hold for resolve_for_s before resolving (hysteresis)
    st.record('kubeml_serving_requests_completed_total{model="m"}', 0.0,
              t=now + 40)
    st.record('kubeml_serving_requests_completed_total{model="m"}', 500.0,
              t=now + 41)
    eng.evaluate(now=now + 41)
    assert _state(eng, "availability") == FIRING  # clear, not long enough
    eng.evaluate(now=now + 45)
    assert _state(eng, "availability") == INACTIVE
    assert [a["to"] for a in alerts] == ["firing", "resolved"]
    # the full transition history is recorded
    assert [e["to"] for e in eng.events()] == [
        "pending", "firing", "resolved"]


def test_alert_pending_clears_without_firing():
    st = TimeSeriesStore()
    eng, alerts = _engine(st, for_s=5.0)
    st.record('kubeml_serving_requests_overload_total{model="m"}', 0.0, t=T0)
    st.record('kubeml_serving_requests_overload_total{model="m"}', 5.0,
              t=T0 + 1)
    eng.evaluate(now=T0 + 1)
    assert _state(eng, "availability") == PENDING
    # budget stops burning before for_s elapses -> back to inactive, no alert
    st.record('kubeml_serving_requests_completed_total{model="m"}', 0.0,
              t=T0 + 1.5)
    st.record('kubeml_serving_requests_completed_total{model="m"}', 900.0,
              t=T0 + 2)
    eng.evaluate(now=T0 + 2)
    assert _state(eng, "availability") == INACTIVE
    assert alerts == []


def test_firing_clear_clock_resets_on_reburn():
    """Hysteresis: a flap back into burn while waiting out resolve_for_s
    restarts the clear clock — the alert must not resolve mid-incident."""
    st = TimeSeriesStore()
    eng, _ = _engine(st, for_s=0.0, resolve_for_s=10.0,
                     spec="overload_rate<=1")
    key = 'kubeml_serving_requests_overload_total{model="m"}'
    st.record(key, 0.0, t=T0 - 60)
    st.record(key, 1000.0, t=T0)
    eng.evaluate(now=T0)
    eng.evaluate(now=T0 + 0.1)
    assert _state(eng, "overload_rate") == FIRING
    # 50s later the burst is long out of both windows: condition clear
    eng.evaluate(now=T0 + 50)
    assert _state(eng, "overload_rate") == FIRING
    # it flaps: a fresh burst inside the resolve wait resets the clock
    st.record(key, 2000.0, t=T0 + 55)
    eng.evaluate(now=T0 + 55)
    eng.evaluate(now=T0 + 58)  # burst still in the fast window
    eng.evaluate(now=T0 + 100)  # calm again, clear clock restarted @ ~70
    st2 = eng._states["overload_rate"]
    assert st2.state == FIRING or st2.clear_since > T0 + 50
    eng.evaluate(now=T0 + 200)
    assert _state(eng, "overload_rate") == INACTIVE


def test_metrics_source_and_registry_render():
    from kubeml_tpu.ps.metrics import MetricsRegistry

    st = TimeSeriesStore()
    eng, _ = _engine(st, spec="overload_rate<=1")
    key = 'kubeml_serving_requests_overload_total{model="m"}'
    st.record(key, 0.0, t=T0 - 60)
    st.record(key, 100.0, t=T0)
    eng.evaluate(now=T0)
    src = eng.metrics_source()
    assert src["burn"][("overload_rate", "fast")] > 1.0
    assert src["state"]["overload_rate"] in (PENDING, FIRING)
    reg = MetricsRegistry()
    reg.set_slo_source(eng.metrics_source)
    text = reg.render()
    assert 'kubeml_slo_burn_rate{slo="overload_rate",window="fast"}' in text
    assert 'kubeml_slo_alert_state{slo="overload_rate"}' in text


def test_status_payload():
    st = TimeSeriesStore()
    eng, _ = _engine(st, spec="availability>=0.99;overload_rate<=5")
    eng.evaluate(now=T0)
    status = eng.status()
    assert status["windows"] == {"fast": 10.0, "slow": 30.0}
    assert [o["name"] for o in status["objectives"]] == [
        "availability", "overload_rate"]
    assert all(o["state"] == "inactive" for o in status["objectives"])


# --- PS integration: collector, history, slo status ---


@pytest.fixture
def ps(tmp_path, monkeypatch):
    monkeypatch.setenv("KUBEML_DATA_ROOT", str(tmp_path / "kubeml"))
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.ps.parameter_server import ParameterServer

    cfg = Config()
    cfg.ensure_dirs()
    return ParameterServer(config=cfg)


def test_ps_sampler_collects_serving_series(ps):
    from kubeml_tpu.serving.stats import DecoderStats

    stats = DecoderStats(slots=4)
    stats.submitted(3)
    stats.emitted(12)
    snap = stats.snapshot()
    snap["queue_depth"] = 2.0
    ps._serving_telemetry = lambda: {"m1": snap}
    ps.sampler.tick()
    hist = ps.metrics_history(match="kubeml_serving", stats=True)
    series = hist["series"]
    assert series['kubeml_serving_requests_submitted_total{model="m1"}'][
        "latest"] == 3.0
    assert series['kubeml_serving_queue_depth{model="m1"}']["latest"] == 2.0
    assert series['kubeml_serving_goodput_tokens_total{model="m1"}'][
        "latest"] == 12.0
    # running gauge + preemption counter ride the same sample
    full = ps.metrics_history()
    assert "kubeml_preemptions_total" in full["series"]


def test_ps_slo_status_default_objectives(ps):
    status = ps.slo_status()
    names = [o["name"] for o in status["objectives"]]
    # the default KUBEML_SLOS spec declares these three
    assert names == ["availability", "overload_rate", "ttft_p99"]


def test_ps_routes_history_and_slo(ps, monkeypatch):
    """The HTTP surface: GET /metrics/history and GET /slo through a live
    PSAPI, including the query-parameter plumbing."""
    monkeypatch.setenv("KUBEML_PS_PORT", "0")
    from kubeml_tpu.ps.transport import PSAPI
    from kubeml_tpu.utils import traced_http

    ps.cfg.ps_port = 0
    api = PSAPI(ps, config=ps.cfg).start()
    try:
        ps.sampler.tick()
        r = traced_http.get(f"{api.url}/metrics/history?stats=1&samples=0",
                            timeout=10)
        assert r.status_code == 200
        body = r.json()
        assert "series" in body and "kubeml_preemptions_total" in body["series"]
        assert "samples" not in body["series"]["kubeml_preemptions_total"]
        r = traced_http.get(f"{api.url}/slo", timeout=10)
        assert r.status_code == 200
        assert [o["name"] for o in r.json()["objectives"]]
        # /metrics still serves the exposition (route precedence)
        r = traced_http.get(f"{api.url}/metrics", timeout=10)
        assert r.status_code == 200 and "kubeml_slo_burn_rate" in r.text
    finally:
        api.stop()


# --- preemption controller: parity with the old hand-rolled window ---


class _FakeSched:
    class usage:
        @staticmethod
        def get(t):
            return 0.0


class _FakePS:
    def __init__(self):
        self.telemetry = {}

    def serving_telemetry(self):
        return self.telemetry


def _pc(tmp_path, **over):
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.scheduler.preemption import PreemptionController

    cfg = Config(data_root=tmp_path / "kubeml")
    cfg.preempt_queue_depth = over.pop("queue_depth", 0)
    cfg.preempt_overload_rate = over.pop("overload_rate", 1.0)
    cfg.preempt_p99 = over.pop("p99", 0.0)
    for k, v in over.items():
        setattr(cfg, f"preempt_{k}", v)
    return PreemptionController(_FakeSched(), _FakePS(), config=cfg)


class _OldWindow:
    """The pre-PR-11 controller signal: per-poll cumulative-counter delta
    rate, floored by the decoders' own 10s-window rate — reimplemented here
    verbatim as the parity reference."""

    def __init__(self):
        self.prev = None
        self.prev_t = None

    def rate(self, telemetry, now):
        overloads = sum(s.get("requests_overload", 0.0)
                        for s in telemetry.values())
        rate = 0.0
        if self.prev is not None:
            dt = max(now - self.prev_t, 1e-3)
            rate = max(0.0, overloads - self.prev) / dt
        self.prev, self.prev_t = overloads, now
        return max(rate, sum(s.get("overload_per_second", 0.0)
                             for s in telemetry.values()))


@pytest.mark.parametrize("scenario", ["steady_burst", "short_burst", "calm",
                                      "decoder_window_only"])
def test_overload_signal_parity_old_vs_new(tmp_path, scenario, monkeypatch):
    """The rewired time-series signal must make the same overload/calm
    decisions as the old hand-rolled window on representative traffic
    shapes (the acceptance gate for deleting the one-off implementation)."""
    import kubeml_tpu.scheduler.preemption as preemption_mod

    ctrl = _pc(tmp_path, overload_rate=1.0)
    old = _OldWindow()
    # drive both off the same synthetic clock, 1s polls
    clock = [T0]
    monkeypatch.setattr(preemption_mod.time, "monotonic", lambda: clock[0])

    def telemetry_at(i):
        if scenario == "steady_burst":      # 5 x 429/s, sustained
            return {"m": {"requests_overload": 5.0 * i,
                          "overload_per_second": 5.0 if i > 0 else 0.0}}
        if scenario == "short_burst":       # one 20-429 spike at poll 3
            # the decoders' own ~10s ring keeps the burst visible for the
            # window (realistic telemetry — both implementations read it)
            cum = 20.0 if i >= 3 else 0.0
            return {"m": {"requests_overload": cum,
                          "overload_per_second": 2.0 if 3 <= i < 13 else 0.0}}
        if scenario == "decoder_window_only":
            # the poll delta alone is sub-threshold, the decoders' own
            # window is not — both implementations take the max
            return {"m": {"requests_overload": 0.5 * i,
                          "overload_per_second": 3.0}}
        return {"m": {"requests_overload": 0.0,
                      "overload_per_second": 0.0}}  # calm

    decisions_new, decisions_old = [], []
    for i in range(8):
        clock[0] = T0 + i
        ctrl.ps.telemetry = telemetry_at(i)
        sig = ctrl.signals()
        decisions_new.append(ctrl.overloaded(sig))
        old_rate = old.rate(telemetry_at(i), clock[0])
        decisions_old.append(old_rate >= 1.0)
    assert decisions_new == decisions_old, (
        f"{scenario}: new {decisions_new} != old {decisions_old}")


def test_preemption_signals_expose_windowed_rate(tmp_path, monkeypatch):
    """The controller's rate now comes from a Series query: a burst decays
    out of the window instead of persisting forever."""
    import kubeml_tpu.scheduler.preemption as preemption_mod

    ctrl = _pc(tmp_path)
    clock = [T0]
    monkeypatch.setattr(preemption_mod.time, "monotonic", lambda: clock[0])
    ctrl.ps.telemetry = {"m": {"requests_overload": 0.0}}
    ctrl.signals()
    clock[0] = T0 + 1
    ctrl.ps.telemetry = {"m": {"requests_overload": 30.0}}
    assert ctrl.signals()["overload_rate"] >= 1.0
    # 60s of calm later the same cumulative counter reads rate 0
    clock[0] = T0 + 61
    assert ctrl.signals()["overload_rate"] == 0.0


# --- the heavy end-to-end scenario (slow tier; pytest -m slo runs it) ---


@pytest.mark.slo
def test_slo_overload_end_to_end(tmp_path, monkeypatch):
    """The full acceptance chain on a live in-process cluster: a burst past
    the queue limit fires an SLO alert through the errorhook webhook
    (pending -> firing -> resolved), occupancy/goodput counters sum
    consistently on /metrics, /metrics/history serves windowed rates, and
    the warmed serving request's span tree is fetchable by request id."""
    for k, v in (("KUBEML_DATA_ROOT", str(tmp_path / "kubeml")),
                 ("KUBEML_SERVING_SLOTS", "2"),
                 ("KUBEML_SERVING_QUEUE_LIMIT", "4"),
                 ("KUBEML_TSDB_INTERVAL", "0.2"),
                 ("KUBEML_SLOS", "availability>=0.95;overload_rate<=2.0"),
                 ("KUBEML_SLO_FAST_WINDOW", "3"),
                 ("KUBEML_SLO_SLOW_WINDOW", "10"),
                 ("KUBEML_SLO_FOR", "1"),
                 ("KUBEML_SLO_RESOLVE_FOR", "3"),
                 ("KUBEML_CONTROLLER_PORT", "0"),
                 ("KUBEML_SCHEDULER_PORT", "0"),
                 ("KUBEML_PS_PORT", "0"),
                 ("KUBEML_STORAGE_PORT", "0"),
                 ("KUBEML_TRACE", str(tmp_path / "traces"))):
        monkeypatch.setenv(k, v)
    from kubeml_tpu.api.config import Config
    from scenario_support import run_slo_overload
    from kubeml_tpu.utils import tracing

    tracing.get_tracer()  # picks up KUBEML_TRACE before the cluster boots
    row = run_slo_overload(config=Config())
    assert row["status"] == "ok"
    kinds = {(t["from"], t["to"]) for t in row["transitions"]}
    assert {("inactive", "pending"), ("pending", "firing"),
            ("firing", "resolved")} <= kinds
    assert row["alert_webhook"]["context"].startswith("slo:")
    assert row["occupancy"]["overloads_429"] > 0
    occ = row["occupancy"]
    assert occ["live"] + occ["dead"] + occ["idle"] == occ["slot_steps"]
    assert occ["goodput_tokens"] + occ["wasted_tokens"] == \
        occ["emitted_tokens"]
    assert row["history"]["samples"] > 0
    assert row["trace"]["spans"] >= 4


def test_cli_slo_and_top_against_live_cluster(tmp_path, monkeypatch, capsys):
    """`kubeml slo` and `kubeml top --once` render against a live cluster:
    the controller proxies /slo and /metrics/history from the PS."""
    for k, v in (("KUBEML_DATA_ROOT", str(tmp_path / "kubeml")),
                 ("KUBEML_CONTROLLER_PORT", "0"),
                 ("KUBEML_SCHEDULER_PORT", "0"),
                 ("KUBEML_PS_PORT", "0"),
                 ("KUBEML_STORAGE_PORT", "0")):
        monkeypatch.setenv(k, v)
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.cli import main
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.serving.stats import DecoderStats

    cfg = Config()
    cfg.ensure_dirs()
    with LocalCluster(config=cfg) as cluster:
        # fake one resident decoder's telemetry so top has a model row
        stats = DecoderStats(slots=2)
        stats.submitted(2)
        stats.emitted(16)
        snap = stats.snapshot()
        snap["queue_depth"] = 1.0
        cluster.ps._serving_telemetry = lambda: {"slomodel": snap}
        cluster.ps.sampler.tick()
        url = ["--url", cluster.controller_url]
        assert main(url + ["slo"]) == 0
        out = capsys.readouterr().out
        assert "availability" in out and "BURN(fast)" in out
        assert main(url + ["slo", "--json"]) == 0
        assert '"objectives"' in capsys.readouterr().out
        assert main(url + ["top", "--once"]) == 0
        out = capsys.readouterr().out
        assert "slomodel" in out and "TOK/S" in out and "slo:" in out


def test_latency_signals_need_traffic_in_window():
    """The p99 gauges are request rings: an idle server's gauge holds its
    last (cold-compile) value forever. Without request flow in the window
    the latency signal must read None — a stale 8s TTFT on a quiet system
    must neither burn budget nor hold an alert firing."""
    st = TimeSeriesStore()
    eng, _ = _engine(st, spec="ttft_p99<=2.5")
    gauge = 'kubeml_serving_first_token_p99_seconds{model="m"}'
    comp = 'kubeml_serving_requests_completed_total{model="m"}'
    # one cold request: the gauge jumps to 8s WITH traffic flowing
    st.record(comp, 0.0, t=T0 - 5)
    st.record(comp, 1.0, t=T0)
    st.record(gauge, 8.0, t=T0)
    assert eng.signal_value("ttft_p99", 10.0, now=T0) == 8.0
    eng.evaluate(now=T0)
    assert _state(eng, "ttft_p99") == PENDING  # genuinely slow, pends
    # traffic stops; the stale gauge keeps its value but the signal gates
    # on flow — the alert clears instead of wedging on a quiet server
    st.record(comp, 1.0, t=T0 + 60)
    st.record(gauge, 8.0, t=T0 + 60)
    assert eng.signal_value("ttft_p99", 10.0, now=T0 + 60) is None
    eng.evaluate(now=T0 + 60)
    assert _state(eng, "ttft_p99") == INACTIVE


def test_series_reset_clamp_for_summed_components():
    """reset="clamp": a series summing per-component counters must not
    read a component's eviction (sum shrinks, no events) as a burst."""
    s = Series(kind="counter")
    s.observe(0.0, t=T0)
    s.observe(250.0, t=T0 + 1)   # two decoders' 429s summed
    s.observe(50.0, t=T0 + 2)    # one decoder evicted: sum drops, 0 events
    assert s.increase(10.0, now=T0 + 2, reset="clamp") == \
        pytest.approx(250.0)     # only the real increase counted
    # Prometheus semantics would add the survivor's full value
    assert s.increase(10.0, now=T0 + 2) == pytest.approx(300.0)


def test_preemption_rate_survives_decoder_eviction(tmp_path, monkeypatch):
    """A decoder-cache eviction shrinks the summed 429 counter — the
    controller must NOT read that as a fresh burst and preempt."""
    import kubeml_tpu.scheduler.preemption as preemption_mod

    ctrl = _pc(tmp_path, overload_rate=1.0)
    clock = [T0]
    monkeypatch.setattr(preemption_mod.time, "monotonic", lambda: clock[0])
    # two models, historical 429s, currently calm
    ctrl.ps.telemetry = {
        "a": {"requests_overload": 200.0, "overload_per_second": 0.0},
        "b": {"requests_overload": 50.0, "overload_per_second": 0.0}}
    assert not ctrl.overloaded(ctrl.signals())
    # model a's decoder evicts: the sum drops 250 -> 50 with zero events
    clock[0] = T0 + 1
    ctrl.ps.telemetry = {
        "b": {"requests_overload": 50.0, "overload_per_second": 0.0}}
    sig = ctrl.signals()
    assert sig["overload_rate"] == 0.0, sig
    assert not ctrl.overloaded(sig)


def test_store_running_total_is_a_gauge():
    """kubeml_job_running_total is decremented at task finish — the PS
    marks it a gauge so /metrics/history stats render quantiles, not a
    counter 'increase' that spikes precisely when jobs complete."""
    st = TimeSeriesStore()
    st.mark_gauge("kubeml_job_running_total")
    s = st.series('kubeml_job_running_total{type="train"}')
    assert s.kind == "gauge"
    for i, v in enumerate((3.0, 3.0, 2.0, 1.0)):
        s.observe(v, t=T0 + i)
    hist = st.history(stats=True, stats_window=30.0, now=T0 + 3)
    entry = hist["series"]['kubeml_job_running_total{type="train"}']
    assert "rate" not in entry and entry["max"] == 3.0


def test_store_eviction_is_recency_not_insertion_order():
    """Past max_series the store must evict the series longest without a
    sample — insertion-order eviction would thrash every actively-sampled
    series once the cap is crossed."""
    st = TimeSeriesStore(max_series=3)
    for name in ("a", "b", "c"):
        st.record(name, 1.0, t=T0)
    # a and c stay hot; b goes quiet
    for i in range(1, 4):
        st.record("a", float(i), t=T0 + i)
        st.record("c", float(i), t=T0 + i)
    st.record("d", 1.0, t=T0 + 5)  # over the cap: the STALE series evicts
    assert st.get("b") is None
    assert st.get("a") is not None and st.get("c") is not None


def test_preemption_burst_floor_on_mature_series(tmp_path, monkeypatch):
    """Parity in the regime the original parity scenarios missed: once the
    controller has polled LONGER than the window, a burst landing in one
    poll must still read at its per-poll delta rate (the old floor), not
    diluted over the full window's worth of samples."""
    import kubeml_tpu.scheduler.preemption as preemption_mod

    ctrl = _pc(tmp_path, overload_rate=5.0)
    clock = [T0]
    monkeypatch.setattr(preemption_mod.time, "monotonic", lambda: clock[0])
    # 15 calm 1s polls: the series is now older than the 10s window
    for i in range(15):
        clock[0] = T0 + i
        ctrl.ps.telemetry = {"m": {"requests_overload": 0.0,
                                   "overload_per_second": 0.0}}
        assert not ctrl.overloaded(ctrl.signals())
    # 20 429s land within one poll; the decoders' own window reads 2/s
    clock[0] = T0 + 15
    ctrl.ps.telemetry = {"m": {"requests_overload": 20.0,
                               "overload_per_second": 2.0}}
    sig = ctrl.signals()
    assert sig["overload_rate"] >= 5.0, sig  # old delta floor: 20/1s
    assert ctrl.overloaded(sig)
