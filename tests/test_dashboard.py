"""Grafana dashboard drift guard (fast tier-1).

Every panel expression in ``deploy/grafana/kubeml-dashboard.json`` must
reference only metric names some module actually exports — PR 6 shipped a
``*_total``-suffix typo on a gauge panel that exactly this test would have
caught. The exported-name universe is built by RENDERING a fully-seeded
registry (serving telemetry with every histogram fed, job histograms,
preemption/yield/queue series, resilience counters, profiler data-plane
counters, SLO burn/state) rather than hand-listing names, so the test can't
itself drift from the renderers.
"""

import json
import re
from pathlib import Path

DASHBOARD = Path(__file__).parent.parent / "deploy" / "grafana" / \
    "kubeml-dashboard.json"

_NAME_RE = re.compile(r"kubeml_[a-z0-9_]+")
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _exported_names() -> set:
    """Every metric name a fully-seeded exposition render emits."""
    from kubeml_tpu.api.types import MetricUpdate
    from kubeml_tpu.ps.metrics import MetricsRegistry
    from kubeml_tpu.serving.stats import DecoderStats
    from kubeml_tpu.utils import profiler, resilience

    reg = MetricsRegistry()
    # job gauges + histograms (incl. the statistical-efficiency signals)
    reg.update(MetricUpdate(job_id="drift-job", validation_loss=1.0,
                            accuracy=0.5, train_loss=1.0, parallelism=2,
                            epoch_duration=1.0, moe_overflow=0.1,
                            round_seconds=[0.1], merge_seconds=0.2,
                            round_divergence=[0.01],
                            round_loss_spread=[0.1],
                            round_skew_ratio=1.5))
    reg.task_started()
    # preemption series + per-priority queue gauges + scale decisions
    reg.preemption("drift")
    reg.observe_yield(0.5)
    reg.set_queue_source(lambda: {0: 1})
    reg.set_decision_source(lambda: {("up", "speedup"): 1})
    # serving telemetry: one decoder with every counter/gauge/histogram fed
    stats = DecoderStats(slots=4)
    stats.submitted(1)
    stats.first_token(0.05)
    stats.completed(0.2)
    stats.emitted(8)
    stats.emitted(2, wasted=True)
    stats.overloaded()
    stats.shed()
    stats.deadline_expired()
    stats.timed_out()
    stats.canceled()
    stats.failed()
    stats.rejected()
    stats.admitted_wave()
    stats.chunk()
    stats.chunk_fetched(0.08, 8)
    # latency anatomy (PR 18): colocated decode split, ITL, HOL stall,
    # cold-start quarantine, and the compile tracker
    stats.chunk_fetched(0.09, 8, colocated=True)
    stats.inter_token(0.02)
    stats.hol_stall(0.1, 2)
    stats.cold_start(0.5)
    # chunked prefill (ISSUE 19): chunk dispatch counters
    stats.prefill_chunk(2, 48)
    if stats.compile_begin("step", (8,)):
        stats.compiled("step", 0.4)
    # mid-stream recovery (ISSUE 20): snapshot/restore/replay counters,
    # the KMS1 size/latency histograms, and the pool-audit watchdog —
    # all conditionally exposed, so the seed must fire each event
    stats.snapshot_save(1 << 16, 0.01)
    stats.snapshot_restore(1 << 16, 0.02)
    stats.snapshot_replay(2)
    stats.snapshot_fail()
    stats.pool_audit(True)
    stats.pool_audit(False)
    stats.chunk_occupancy(8, 20, 6, 6)
    stats.admit_tokens(10, 22)
    stats.kv_read(1 << 20, 0.01)
    stats.spec_step(drafted=8, accepted=6, proposed=10)
    stats.fetch_started()
    stats.fetch_finished(0.01)
    stats.fetchers_total = 4
    for phase in ("queue_wait", "prefill", "decode_active", "slot_idle"):
        stats.phase(phase, 0.01)
    snap = stats.snapshot()
    snap.update({"queue_depth": 1.0, "slots_busy": 1.0, "slots_total": 4.0,
                 "slot_occupancy": 0.25, "weight_bytes": 1024.0,
                 "queue_limit": 16.0, "spec_k": 4.0,
                 "paged_attn_kernel": 1.0, "kv_quant": 1.0,
                 "spec_disabled": 0.0, "prefills_in_progress": 1.0,
                 "draining": 0.0})
    reg.set_serving_source(lambda: {"drift-model": snap})
    # SLO burn/state gauges
    reg.set_slo_source(lambda: {"burn": {("drift", "fast"): 0.5},
                                "state": {"drift": 0}})
    # resilience + profiler families render inside reg.render(); seed the
    # conditional ones so their series (not just HELP headers) exist
    resilience.incr("kubeml_http_retries_total", "drift-dest")
    profiler.account("drift.phase", 1024, 0.1)
    profiler.record_retry("drift.phase")

    text = reg.render()
    names = set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            names.add(line.split()[2])
        elif line and not line.startswith("#"):
            names.add(re.split(r"[{ ]", line, 1)[0])
    return names


def _dashboard_names() -> dict:
    """{metric name: [panel titles referencing it]} from every target expr."""
    doc = json.loads(DASHBOARD.read_text())
    refs = {}
    for panel in doc.get("panels", []):
        for target in panel.get("targets", []):
            for name in _NAME_RE.findall(target.get("expr", "")):
                refs.setdefault(name, []).append(panel.get("title", "?"))
    return refs


def test_dashboard_parses_and_has_panels():
    doc = json.loads(DASHBOARD.read_text())
    assert doc.get("panels"), "dashboard has no panels"
    assert all(p.get("targets") for p in doc["panels"]), \
        "every panel needs at least one target expression"


def test_every_panel_metric_is_exported():
    exported = _exported_names()
    missing = {}
    for name, panels in _dashboard_names().items():
        base = name
        for suf in _HIST_SUFFIXES:
            if name.endswith(suf) and name[: -len(suf)] in exported:
                base = name[: -len(suf)]
                break
        if base not in exported and name not in exported:
            missing[name] = sorted(set(panels))
    assert not missing, (
        f"dashboard panels reference metrics no module exports: {missing}")


def test_new_observability_panels_present():
    """The PR-11 panels: occupancy ratio, goodput vs device tokens, SLO
    burn rate — the dashboard must chart the new accounting."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_batch_occupancy_ratio_bucket",
                   "kubeml_serving_goodput_tokens_total",
                   "kubeml_serving_occupancy_dead_steps_total",
                   "kubeml_slo_burn_rate",
                   "kubeml_slo_alert_state",
                   "kubeml_serving_queue_wait_seconds_bucket"):
        assert metric in refs, f"no panel charts {metric}"


def test_elastic_observability_panels_present():
    """The PR-13 panels: the parallelism timeline, scale decisions by
    direction/reason, and the statistical-efficiency histograms (worker
    divergence, loss spread, round skew) — elastic training must be
    chartable next to the serving view."""
    refs = _dashboard_names()
    for metric in ("kubeml_job_parallelism",
                   "kubeml_scale_decisions_total",
                   "kubeml_job_worker_divergence_bucket",
                   "kubeml_job_loss_spread_bucket",
                   "kubeml_job_round_skew_ratio_bucket"):
        assert metric in refs, f"no panel charts {metric}"


def test_spec_decode_panels_present():
    """The ISSUE-14 acceptance panel: drafted/accepted rates, the per-step
    acceptance-ratio histogram, and the adaptive-k gauge must be charted."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_spec_accepted_tokens_total",
                   "kubeml_serving_spec_drafted_tokens_total",
                   "kubeml_serving_spec_accept_ratio_bucket",
                   "kubeml_serving_spec_k"):
        assert metric in refs, f"no panel charts {metric}"


def test_paged_attention_kv_panel_present():
    """The ISSUE-15 panel: KV-read byte rate, achieved-bandwidth p95 and
    the kernel/gather gauge — the paged-attention traffic win must be
    chartable."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_kv_read_bytes_total",
                   "kubeml_serving_kv_bandwidth_bytes_per_sec_bucket",
                   "kubeml_serving_paged_attn_pallas"):
        assert metric in refs, f"no panel charts {metric}"


def test_kv_quant_and_spec_disabled_panels_present():
    """The ISSUE-16 panels: the kv-quant storage-mode gauge charted next to
    the arena capacity it doubles, and the draft-retreat guard gauge next
    to the acceptance rate that trips it."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_kv_quant",
                   "kubeml_serving_spec_disabled"):
        assert metric in refs, f"no panel charts {metric}"
    assert "kubeml_serving_pages_total" in refs


def test_latency_anatomy_panels_present():
    """The PR-18 panels: inter-token latency, head-of-line stall, the
    cause-split decode-step histogram, per-program compiles, and the
    quarantined compile/cold-start walls."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_itl_p99_seconds",
                   "kubeml_serving_inter_token_seconds_bucket",
                   "kubeml_serving_hol_stall_seconds_total",
                   "kubeml_serving_decode_step_seconds_bucket",
                   "kubeml_serving_compiles_total",
                   "kubeml_serving_compiled_programs",
                   "kubeml_serving_compile_storm",
                   "kubeml_serving_compile_seconds_bucket",
                   "kubeml_serving_cold_start_seconds_bucket"):
        assert metric in refs, f"no panel charts {metric}"


def test_chunked_prefill_panels_present():
    """The ISSUE-19 panels: chunk dispatch rate with the mid-prefill
    prompt gauge, and chunked-prefill token throughput charted against
    the head-of-line stall rate the knob exists to push down."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_prefill_chunks_total",
                   "kubeml_serving_prefill_chunk_tokens_total",
                   "kubeml_serving_prefills_in_progress"):
        assert metric in refs, f"no panel charts {metric}"
    assert "kubeml_serving_hol_stall_seconds_total" in refs


def test_serving_recovery_panels_present():
    """The ISSUE-20 panels: snapshot save/restore/replay/fail rates with
    the draining gauge, the KMS1 frame-size and capture-latency
    histograms, and the kvpool invariant-audit watchdog."""
    refs = _dashboard_names()
    for metric in ("kubeml_serving_snapshot_saved_total",
                   "kubeml_serving_snapshot_restored_total",
                   "kubeml_serving_snapshot_replayed_total",
                   "kubeml_serving_snapshot_failed_total",
                   "kubeml_serving_snapshot_bytes_bucket",
                   "kubeml_serving_snapshot_seconds_bucket",
                   "kubeml_serving_draining",
                   "kubeml_serving_pool_audit_runs_total",
                   "kubeml_serving_pool_audit_failures_total"):
        assert metric in refs, f"no panel charts {metric}"


# Exported metrics deliberately NOT charted — the reverse drift guard
# (below) fails on any exported name missing from BOTH the dashboard and
# this allowlist, so a new metric must ship with either a panel or a
# written reason. Histogram _count/_sum/_bucket siblings of a charted
# family never need listing (the guard strips suffixes on both sides).
UNPANELED = {
    # debug/internals: useful in ad-hoc PromQL, too noisy as panels
    "kubeml_dataplane_events_total": "per-event codec debug counter",
    "kubeml_dataplane_seconds_total": "per-event codec debug counter",
    "kubeml_http_breaker_rejected_total": "client-resilience internals",
    "kubeml_http_deadline_expired_total": "client-resilience internals",
    "kubeml_http_idempotent_replays_total": "client-resilience internals",
    "kubeml_http_retry_budget_exhausted_total":
        "client-resilience internals",
    # raw inputs to ratios/histograms that ARE charted
    "kubeml_job_epoch": "epoch progress charted via epoch_duration",
    "kubeml_job_epoch_seconds": "charted as kubeml_job_epoch_duration",
    "kubeml_job_merge_seconds": "merge wall folds into round-time panels",
    "kubeml_job_round_seconds": "round wall folds into round-time panels",
    "kubeml_job_moe_overflow": "model-specific; ad-hoc only",
    "kubeml_preempt_yield_seconds": "yield wall; preemptions_total charted",
    "kubeml_serving_admission_waves_total": "denominator of admit ratios",
    "kubeml_serving_chunks_total": "denominator of per-chunk rates",
    "kubeml_serving_fetcher_utilization": "pipeline debug gauge",
    "kubeml_serving_prefill_tokens_total": "input to goodput ratio panel",
    "kubeml_serving_prefill_head_positions_total":
        "one a program row since PR 46; a count to test against, ad-hoc only",
    "kubeml_serving_spec_steps_total": "denominator of spec accept rate",
    "kubeml_serving_spec_accept_rate": "ratio derived on-panel from totals",
    "kubeml_serving_requests_submitted_total": "completed/failed charted",
    "kubeml_serving_requests_canceled_total": "folded into failure panels",
    # ring-quantile gauges shadowing charted histograms (the histogram
    # panels chart the same signal with bucket accuracy)
    "kubeml_serving_first_token_p50_seconds": "hist panel charts TTFT",
    "kubeml_serving_first_token_p95_seconds": "hist panel charts TTFT",
    "kubeml_serving_first_token_p99_seconds": "hist panel charts TTFT",
    "kubeml_serving_first_token_max_seconds": "hist panel charts TTFT",
    "kubeml_serving_request_seconds": "request-latency ring + histogram",
    # static capacity/config gauges: constants, not timelines
    "kubeml_serving_page_tokens": "static config gauge",
    "kubeml_serving_queue_limit": "static config gauge",
    "kubeml_serving_slots_busy": "occupancy ratio panel charts this",
    "kubeml_serving_slots_total": "static capacity gauge",
    "kubeml_serving_weight_bytes": "static per-model constant",
    "kubeml_serving_kv_latent_width": "static per-model constant",
    "kubeml_serving_kv_latent_row_width": "static per-model constant",
    "kubeml_serving_moe_layers": "static per-model constant",
    "kubeml_serving_expert_param_bytes": "static per-model constant",
    # expert models only; the benchmark reads their ratio per decode step
    "kubeml_serving_moe_assignments_total": "model-specific; ad-hoc only",
    "kubeml_serving_moe_experts_touched_total":
        "model-specific; ad-hoc only",
    # a chip's share of a layer's experts, identity experts (LongCat-Flash)
    "kubeml_serving_moe_assignments_zero_total":
        "model-specific; ad-hoc only",
    "kubeml_serving_moe_assignments_absent_total":
        "model-specific; ad-hoc only",
    "kubeml_serving_moe_experts_held": "static per-model constant",
    "kubeml_serving_cache_sublayers": "static per-model constant",
    # hyper-connected models only; the benchmark reads the admit part
    "kubeml_serving_residual_streams": "static per-model constant",
    "kubeml_serving_hc_positions_total": "model-specific; ad-hoc only",
    "kubeml_serving_hc_positions_admit_total": "model-specific; ad-hoc only",
    "kubeml_serving_hc_positions_step_total": "model-specific; ad-hoc only",
    # the K/V page walk's decode body only; the benchmark reads their ratio
    "kubeml_serving_walk_chunks_grid_total": "kernel-specific; ad-hoc only",
    "kubeml_serving_walk_chunks_live_total": "kernel-specific; ad-hoc only",
    # the same walk's tile body, in the prefill and admission programs
    "kubeml_serving_tile_chunks_grid_total": "kernel-specific; ad-hoc only",
    "kubeml_serving_tile_chunks_live_total": "kernel-specific; ad-hoc only",
    # the latent page walk's loop (PR 49); the benchmark reads live / run
    "kubeml_serving_latent_walk_trips_run_total":
        "kernel-specific; ad-hoc only",
    "kubeml_serving_latent_walk_trips_live_total":
        "kernel-specific; ad-hoc only",
    "kubeml_serving_latent_walk_pages_total": "kernel-specific; ad-hoc only",
    # PR 44: the window layers' part of the four above, and the second
    # kind of lease's bound against its use; the benchmark reads them
    # (window_live_chunk_share, window_pages_share)
    "kubeml_serving_walk_chunks_grid_window_total":
        "kernel-specific; ad-hoc only",
    "kubeml_serving_walk_chunks_live_window_total":
        "kernel-specific; ad-hoc only",
    "kubeml_serving_tile_chunks_grid_window_total":
        "kernel-specific; ad-hoc only",
    "kubeml_serving_tile_chunks_live_window_total":
        "kernel-specific; ad-hoc only",
    "kubeml_serving_window_pages_held_total": "benchmark-read; ad-hoc only",
    "kubeml_serving_window_pages_live_total": "benchmark-read; ad-hoc only",
    # PR 48: what the recurrent layers' state kernel moves against what
    # advances; the benchmark reads their ratio (state_rows_live_share)
    "kubeml_serving_state_rows_moved_total": "benchmark-read; ad-hoc only",
    "kubeml_serving_state_rows_live_total": "benchmark-read; ad-hoc only",
    # PR 51: which delta rule those states follow (a decay a head, or one a
    # key channel), shown beside the two counters
    "kubeml_serving_state_gate_width": "static per-model constant",
}


def test_every_exported_metric_is_paneled_or_allowlisted():
    """Reverse drift guard (PR 18): a metric the fully-seeded registry
    exports but no panel charts is invisible telemetry — dead code at
    best, a silently-regressing signal at worst. Every exported name must
    appear in some panel expr or carry a documented UNPANELED reason."""
    def base(name):
        for suf in _HIST_SUFFIXES + ("_p50", "_p95", "_p99", "_max"):
            if name.endswith(suf):
                return name[: -len(suf)]
        return name

    paneled = set()
    for name in _dashboard_names():
        paneled.add(name)
        paneled.add(base(name))
    unaccounted = sorted(
        name for name in _exported_names()
        if name not in paneled and base(name) not in paneled
        and name not in UNPANELED and base(name) not in UNPANELED)
    assert not unaccounted, (
        "exported metrics with neither a dashboard panel nor an UNPANELED "
        f"reason: {unaccounted}")
    stale = sorted(n for n in UNPANELED if not any(
        e == n or base(e) == n for e in _exported_names()))
    assert not stale, f"UNPANELED entries no module exports: {stale}"


def test_unique_panel_ids():
    """Grafana resolves panels by id — duplicates make edits land on the
    wrong panel (earlier PRs appended id-less panels; ids are now
    assigned)."""
    doc = json.loads(DASHBOARD.read_text())
    ids = [p.get("id") for p in doc["panels"]]
    assert None not in ids, "panel without an id"
    assert len(ids) == len(set(ids)), "duplicate panel ids"
