"""Prometheus-format job metrics.

Same metric names and label scheme as the reference's parameter-server gauges
(reference: ml/pkg/ps/metrics.go:33-86): per-job gauges labeled ``jobid`` plus a
running-jobs gauge labeled ``type``; updated each epoch/validation and cleared
when the job finishes (metrics.go:90-133). Rendered in the Prometheus text
exposition format on ``/metrics`` with no client-library dependency.

Beyond the reference's gauges, hot-path timings get real distributions: a
small :class:`Histogram` primitive (cumulative ``_bucket``/``_sum``/``_count``
series) records per-round function latency, epoch-end merge time, and epoch
wall time per job — the gauges only ever showed the LAST epoch's value, which
flattens exactly the tail behavior latency attribution needs. The serving
runtime feeds the same primitive (serving/stats.py: TTFT, request latency,
decode-step time), rendered here next to the training series.
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Dict, List, Sequence, Tuple

from ..api.types import MetricUpdate


def escape_label_value(v) -> str:
    """Escape a label VALUE per the Prometheus text exposition format
    (backslash, double-quote, and newline must be escaped inside the
    ``label="..."`` quoting — a jobid carrying any of them previously
    produced an unparseable scrape)."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def escape_help(v) -> str:
    """Escape a HELP string per the exposition format (backslash and
    newline; quotes are legal in HELP text)."""
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


GAUGES = {
    "kubeml_job_validation_loss": "Validation loss of a train job",
    "kubeml_job_validation_accuracy": "Validation accuracy of a train job",
    "kubeml_job_train_loss": "Train loss of a train job",
    "kubeml_job_parallelism": "Parallelism of a train job",
    "kubeml_job_epoch_duration_seconds": "Duration of the last epoch",
    # epochs reported so far (one MetricUpdate per epoch) — the live
    # training view's progress column; resets with a PS restart
    "kubeml_job_epoch": "Epochs reported by a train job since it started",
    # extension: MoE expert-capacity overflow (dropped top-k assignment
    # fraction); series exists only for jobs whose model routes experts
    "kubeml_job_moe_overflow": "MoE expert-capacity overflow rate",
}
RUNNING = "kubeml_job_running_total"

# elastic scale decisions, labeled by transition direction + enumerated
# reason (scheduler/decisions.py; counts survive audit-ring eviction)
SCALE_DECISIONS = "kubeml_scale_decisions_total"

# default bucket edges (seconds): spans sub-10ms decode steps through
# multi-minute epochs; +Inf is implicit
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

# ratio edges (0..1) for the per-chunk batch-occupancy histogram: the live
# fraction of device slot-steps (1.0 = every slot emitted every step)
OCCUPANCY_BUCKETS = (0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875,
                     0.9375, 1.0)

# log-scaled bytes/sec edges for the achieved-KV-bandwidth histogram
# (serving/stats.py kv_read): spans a CPU box's ~MB/s through a v5e's
# ~800 GB/s HBM
BANDWIDTH_BUCKETS = (1e6, 3e6, 1e7, 3e7, 1e8, 3e8, 1e9, 3e9, 1e10, 3e10,
                     1e11, 3e11, 1e12)

# log-scaled byte edges for KMS1 snapshot frame sizes (serving/kvsnap.py):
# a short test-model row is ~KB, a long-context production row with a deep
# stack runs to hundreds of MB
SNAPSHOT_BYTES_BUCKETS = (1e3, 1e4, 1e5, 3e5, 1e6, 3e6, 1e7, 3e7, 1e8,
                          3e8, 1e9)


class Histogram:
    """Minimal Prometheus histogram: fixed bucket edges, cumulative counts,
    ``observe`` is O(log buckets) under the caller's locking discipline (the
    registry wraps access in its own lock; serving stats in theirs)."""

    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets: Sequence[float] = LATENCY_BUCKETS):
        self.buckets = tuple(float(b) for b in buckets)
        self.counts = [0] * len(self.buckets)  # per-edge (non-cumulative)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        self.sum += v
        self.count += 1
        idx = bisect_left(self.buckets, v)
        if idx < len(self.counts):
            self.counts[idx] += 1

    def cumulative(self) -> List[Tuple[float, int]]:
        """[(le, cumulative count)] per edge; +Inf is ``self.count``."""
        out, total = [], 0
        for edge, c in zip(self.buckets, self.counts):
            total += c
            out.append((edge, total))
        return out

    @staticmethod
    def _fmt_le(edge: float) -> str:
        s = f"{edge:g}"
        return s

    def render(self, name: str, label: str = "", value: str = "") -> List[str]:
        """Exposition lines for one labeled series (no HELP/TYPE headers)."""
        return self.render_snapshot(name, self.snapshot(), label, value)

    def snapshot(self) -> dict:
        """Plain-data form for cross-thread/process transport (serving
        telemetry snapshots carry these to the registry's renderer)."""
        return {"buckets": [[e, c] for e, c in self.cumulative()],
                "sum": self.sum, "count": self.count}

    @staticmethod
    def render_snapshot(name: str, snap: dict, label: str = "",
                        value: str = "",
                        extra: Dict[str, str] = None) -> List[str]:
        sel = f'{label}="{escape_label_value(value)}",' if label else ""
        for k, v in (extra or {}).items():
            sel += f'{k}="{escape_label_value(v)}",'
        bare = f'{{{sel[:-1]}}}' if sel else ""
        lines = [
            f'{name}_bucket{{{sel}le="{Histogram._fmt_le(float(edge))}"}} {int(c)}'
            for edge, c in snap.get("buckets", ())
        ]
        lines.append(f'{name}_bucket{{{sel}le="+Inf"}} {int(snap.get("count", 0))}')
        lines.append(f'{name}_sum{bare} {snap.get("sum", 0.0)}')
        lines.append(f'{name}_count{bare} {int(snap.get("count", 0))}')
        return lines

# serving-runtime series (continuous batcher, serving/stats.py): per-model,
# labeled ``model``. Counters end in _total; the rest are gauges.
SERVING_COUNTERS = {
    "kubeml_serving_tokens_total": ("tokens_emitted",
                                    "Tokens emitted by the decode engine"),
    "kubeml_serving_requests_submitted_total": (
        "requests_submitted", "Generate requests accepted into the queue"),
    "kubeml_serving_requests_completed_total": (
        "requests_completed", "Generate requests fully served"),
    "kubeml_serving_requests_rejected_total": (
        "requests_rejected", "Generate requests rejected at validation"),
    "kubeml_serving_requests_timeout_total": (
        "requests_timeout", "Generate requests abandoned on waiter timeout"),
    "kubeml_serving_requests_canceled_total": (
        "requests_canceled", "Generate requests explicitly canceled"),
    "kubeml_serving_requests_failed_total": (
        "requests_failed", "Generate requests failed by an engine fault"),
    "kubeml_serving_requests_overload_total": (
        "requests_overload",
        "Generate requests refused 429 at the queue admission limit"),
    "kubeml_serving_requests_shed_total": (
        "requests_shed",
        "Queued generate requests shed oldest-first under overload"),
    "kubeml_serving_deadline_expired_total": (
        "requests_deadline_expired",
        "Queued generate requests failed on an expired deadline"),
    "kubeml_serving_admission_waves_total": (
        "admission_waves", "Prefill+admit programs dispatched"),
    "kubeml_serving_chunks_total": ("chunks",
                                    "Decode chunk programs dispatched"),
    # fetcher pool (short-request workloads can be fetch-pipeline-bound —
    # the pool must be observable)
    "kubeml_serving_fetches_total": (
        "fetches", "Device result fetches completed by the fetcher pool"),
    "kubeml_serving_fetch_busy_seconds_total": (
        "fetch_busy_seconds",
        "Cumulative wall seconds fetcher threads spent blocked on device "
        "result fetches (rate() / pool size = utilization)"),
    # batch-occupancy / goodput accounting (per-device-step truth from the
    # chunk loop — the before/after evidence for continuous batching)
    "kubeml_serving_device_steps_total": (
        "device_steps", "Decode steps executed on device (sum of chunk "
                        "lengths)"),
    "kubeml_serving_occupancy_slot_steps_total": (
        "slot_steps", "Raw device slot-step capacity spent (steps x slots "
                      "per chunk — the device-step token throughput "
                      "denominator)"),
    "kubeml_serving_occupancy_live_steps_total": (
        "live_slot_steps", "Slot-steps that emitted a token (useful decode "
                           "work)"),
    "kubeml_serving_occupancy_dead_steps_total": (
        "dead_slot_steps", "Slot-steps spent on a resident row that emitted "
                           "nothing (finished/eos rows still stepping — the "
                           "dead-step waste)"),
    "kubeml_serving_occupancy_idle_steps_total": (
        "idle_slot_steps", "Slot-steps with no resident row (free capacity)"),
    "kubeml_serving_prefill_tokens_total": (
        "prefill_tokens", "Real prompt tokens prefilled at admission"),
    "kubeml_serving_prefill_pad_tokens_total": (
        "prefill_pad_tokens", "Padding tokens computed at admission (prompt "
                              "bucket + repeated-row padding)"),
    "kubeml_serving_prefill_head_positions_total": (
        "prefill_head_positions", "Positions the admission programs' output "
                                  "heads multiplied: the one a row that is "
                                  "sampled from, not the bucket"),
    "kubeml_serving_goodput_tokens_total": (
        "goodput_tokens", "Tokens delivered to a live waiter (useful-token "
                          "goodput vs device-step throughput)"),
    "kubeml_serving_wasted_tokens_total": (
        "wasted_tokens", "Tokens routed to a request whose waiter already "
                         "gave up (timeout/cancel)"),
    # KV-read accounting (ISSUE 15, ops/paged_attention.py): decode-path
    # attention reads, host-modeled from the table geometry each dispatch
    # shipped — gather reads rows x gathered width, the Pallas kernel only
    # each row's live pages, so this counter's rate is where the paged
    # kernel's traffic win (and the live-width gather clamp) shows up
    "kubeml_serving_kv_read_bytes_total": (
        "kv_read_bytes", "KV-cache bytes the decode-path attention read "
                         "(host-modeled from dispatched table geometry: "
                         "gather = rows x table width, Pallas kernel = "
                         "live pages only)"),
    # shared-prefix reuse (paged engine, serving/kvpool.py)
    "kubeml_serving_prefix_hits_total": (
        "prefix_hits", "Admissions whose leading prompt blocks were served "
                       "from the shared-prefix KV cache"),
    "kubeml_serving_prefix_tokens_saved_total": (
        "prefix_tokens_saved", "Prompt tokens whose prefill was skipped "
                               "because their KV pages were prefix-cached"),
    # speculative decoding (paged engine spec mode, serving/batcher.py —
    # series exist only once a spec step ran)
    "kubeml_serving_spec_drafted_tokens_total": (
        "spec_drafted_tokens", "Tokens the speculative drafter sampled "
                               "(k per live row per verify step)"),
    "kubeml_serving_spec_proposed_tokens_total": (
        "spec_proposed_tokens", "Candidate emissions submitted to one-pass "
                                "batched verification (drafts + the bonus "
                                "position per live row)"),
    "kubeml_serving_spec_accepted_tokens_total": (
        "spec_accepted_tokens", "Drafted tokens the rejection-sampling "
                                "acceptance rule kept"),
    "kubeml_serving_spec_steps_total": (
        "spec_steps", "Speculative verify macro-steps processed"),
    # head-of-line stall attribution (ISSUE 18): wall seconds of prefill
    # work charged to every OTHER live decoding row it stalled — the
    # measured cost chunked prefill / disaggregation would remove
    "kubeml_serving_hol_stall_seconds_total": (
        "hol_stall_seconds",
        "Decode-seconds live rows lost waiting behind a dispatched chunk "
        "that carried admission/prefill work (seconds x stalled rows)"),
    # chunked prefill (ISSUE 19): long cold prompts prefilled in
    # page-aligned chunks interleaved with decode
    # (KUBEML_PREFILL_CHUNK_TOKENS)
    "kubeml_serving_prefill_chunks_total": (
        "prefill_chunks",
        "Per-row prefill chunk dispatches (intermediates plus the final "
        "admission chunk of each chunked long-prompt row)"),
    "kubeml_serving_prefill_chunk_tokens_total": (
        "prefill_chunk_tokens",
        "Prompt tokens prefilled via the chunked path (subset of "
        "kubeml_serving_prefill_tokens_total)"),
    # mid-stream recovery (ISSUE 20, serving/kvsnap.py): portable KMS1
    # KV snapshots — saved on fault/drain, restored into a rebuilt or
    # fresh engine, replayed through the admission queue
    "kubeml_serving_snapshot_saved_total": (
        "snapshot_saved", "Live-request KV snapshots captured (engine "
                          "fault recovery or graceful drain)"),
    "kubeml_serving_snapshot_restored_total": (
        "snapshot_restored", "KV snapshots scattered into fresh pages and "
                             "resumed mid-stream"),
    "kubeml_serving_snapshot_replayed_total": (
        "snapshot_replayed", "Rows re-admitted through the queue after an "
                             "engine-fault snapshot-and-rebuild cycle"),
    "kubeml_serving_snapshot_failed_total": (
        "snapshot_failed", "Snapshot or restore attempts that failed "
                           "(the request got a retryable error instead)"),
    # KVPool invariant watchdog (KUBEML_POOL_AUDIT_INTERVAL)
    "kubeml_serving_pool_audit_runs_total": (
        "pool_audit_runs", "Periodic kvpool.check() invariant audits run "
                           "under the engine lock"),
    "kubeml_serving_pool_audit_failures_total": (
        "pool_audit_failures", "Pool audits that found a broken invariant "
                               "and triggered fault recovery"),
    "kubeml_serving_moe_assignments_total": (
        "moe_assignments", "Token-to-expert assignments given to experts "
                           "held here in decode steps (live rows x experts "
                           "per token x expert layers where every choice is "
                           "one)"),
    "kubeml_serving_moe_assignments_zero_total": (
        "moe_assignments_zero", "Decode-step assignments to identity "
                                "(zero-compute) experts: the token itself "
                                "times the gate, no weights read"),
    "kubeml_serving_moe_assignments_absent_total": (
        "moe_assignments_absent", "Decode-step assignments to experts held "
                                  "on other chips (an expert-parallel "
                                  "share): they add nothing here"),
    "kubeml_serving_moe_experts_touched_total": (
        "moe_experts_touched", "Distinct experts chosen by a decode step's "
                               "live rows, summed over expert layers and "
                               "steps: the expert weights the steps read"),
    "kubeml_serving_hc_positions_total": (
        "hc_positions", "Positions x sub-layers whose residual streams the "
                        "hyper-connection maps mixed, bucket padding and "
                        "dead rows included (0 for a single stream)"),
    "kubeml_serving_hc_positions_admit_total": (
        "hc_positions_admit", "The admission programs' part of "
                              "kubeml_serving_hc_positions_total"),
    "kubeml_serving_hc_positions_step_total": (
        "hc_positions_step", "The decode steps' part of "
                             "kubeml_serving_hc_positions_total"),
    "kubeml_serving_walk_chunks_grid_total": (
        "walk_chunks_grid", "Programs of the K/V page walk's decode body the "
                            "decode steps ran, all attention layers: program "
                            "rows x table width / pages a program (absent "
                            "where steps do not take that body)"),
    "kubeml_serving_walk_chunks_live_total": (
        "walk_chunks_live", "Those of kubeml_serving_walk_chunks_grid_total "
                            "inside a live row's depth: the ones that fetch "
                            "pages and multiply, the rest are empty"),
    "kubeml_serving_tile_chunks_grid_total": (
        "tile_chunks_grid", "Programs of the K/V page walk's tile body the "
                            "prefill and admission programs ran, all "
                            "attention layers: query tiles x table width / "
                            "pages a program (absent where "
                            "kubeml_serving_walk_chunks_grid_total is)"),
    "kubeml_serving_tile_chunks_live_total": (
        "tile_chunks_live", "Those of kubeml_serving_tile_chunks_grid_total "
                            "under their tile's causal depth and the row's: "
                            "the ones that fetch pages and multiply"),
    "kubeml_serving_latent_walk_trips_run_total": (
        "latent_walk_trips_run", "Trips of the latent page walk's loop the "
                                 "decode steps ran, all latent layers: a "
                                 "program row is ceil(depth / C) trips of C "
                                 "pages (absent where steps walk no "
                                 "latents)"),
    "kubeml_serving_latent_walk_trips_live_total": (
        "latent_walk_trips_live", "Those of kubeml_serving_latent_walk_trips_"
                                  "run_total a live row made; the rest are "
                                  "dead rows', one over the trash page a "
                                  "step"),
    "kubeml_serving_latent_walk_pages_total": (
        "latent_walk_pages", "Pages the trips of kubeml_serving_latent_walk_"
                             "trips_run_total copied"),
    "kubeml_serving_walk_chunks_grid_window_total": (
        "walk_chunks_grid_window", "The window layers' part of "
                                   "kubeml_serving_walk_chunks_grid_total: a "
                                   "ring a row (absent without window "
                                   "layers)"),
    "kubeml_serving_walk_chunks_live_window_total": (
        "walk_chunks_live_window", "The window layers' part of "
                                   "kubeml_serving_walk_chunks_live_total"),
    "kubeml_serving_tile_chunks_grid_window_total": (
        "tile_chunks_grid_window", "The window layers' part of "
                                   "kubeml_serving_tile_chunks_grid_total"),
    "kubeml_serving_tile_chunks_live_window_total": (
        "tile_chunks_live_window", "The window layers' part of "
                                   "kubeml_serving_tile_chunks_live_total: "
                                   "the chunks a tile's window meets"),
    "kubeml_serving_window_pages_held_total": (
        "window_pages_held", "Ring pages the live rows held, a decode step "
                             "and window layer (the second kind of lease)"),
    "kubeml_serving_window_pages_live_total": (
        "window_pages_live", "Those of kubeml_serving_window_pages_held_total "
                             "a step's query could read: the pages its "
                             "window of keys lies in"),
    "kubeml_serving_state_rows_moved_total": (
        "state_rows_moved", "Slab rows a decode step's state kernel read and "
                            "wrote in each layer that keeps a recurrent "
                            "state, live or not (absent for a model without "
                            "recurrent state)"),
    "kubeml_serving_state_rows_live_total": (
        "state_rows_live", "Those of kubeml_serving_state_rows_moved_total "
                           "that belonged to a live row and advanced"),
}
# XLA compile counter, labeled {model, program} — rendered from the
# snapshot's per-program compile-count dict rather than the scalar tables
SERVING_COMPILES = "kubeml_serving_compiles_total"
SERVING_COMPILES_HELP = (
    "XLA programs compiled by the serving engine, by program seam "
    "(step/prefill/spec_step — a distinct shape signature per compile)")
# per-job latency histograms (no reference counterpart — the gauges above
# keep only the LAST epoch's value). Fed from MetricUpdate; series OUTLIVE
# the job (histograms are cumulative; a finished job's distribution is the
# artifact), bounded by MAX_HISTOGRAM_JOBS oldest-first eviction.
HISTOGRAMS = {
    "kubeml_job_epoch_seconds": "Epoch wall-time distribution of a train job",
    "kubeml_job_round_seconds": (
        "Per-sync-round wall time (the function/update latency)"),
    "kubeml_job_merge_seconds": (
        "Epoch-end merge/loss sync wall time (the on-chip K-AVG merge is "
        "awaited here)"),
    # statistical-efficiency signals (engine/kavg.py round program,
    # KUBEML_ROUND_STATS): what elastic scaling COSTS statistically —
    # per-round distributions, fed from MetricUpdate each epoch
    "kubeml_job_worker_divergence": (
        "Pre-merge worker weight divergence per K-AVG round (norm of the "
        "stacked worker vars minus their mean, over the mean's norm)"),
    "kubeml_job_loss_spread": (
        "Worker-loss spread per K-AVG round (max - min over effective "
        "participants)"),
    "kubeml_job_round_skew_ratio": (
        "Per-epoch round-time skew (max/median over the epoch's rounds — "
        "the straggler signal)"),
}
MAX_HISTOGRAM_JOBS = 32

# ratio-valued histograms need ratio-scaled edges, not latency seconds:
# divergence/spread live in ~1e-5..1, skew is >= 1 with a heavy tail
RATIO_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 0.001, 0.0025,
                 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
SKEW_BUCKETS = (1.0, 1.1, 1.25, 1.5, 2.0, 3.0, 5.0, 10.0, 25.0, 100.0)
HISTOGRAM_BUCKETS = {
    "kubeml_job_worker_divergence": RATIO_BUCKETS,
    "kubeml_job_loss_spread": RATIO_BUCKETS,
    "kubeml_job_round_skew_ratio": SKEW_BUCKETS,
}

# serving histograms: rendered from the decoders' telemetry snapshots
# (serving/stats.py feeds Histogram.snapshot() dicts under snap["hist"])
SERVING_HISTOGRAMS = {
    "kubeml_serving_first_token_seconds": (
        "first_token", "Time-to-first-token distribution"),
    "kubeml_serving_request_seconds": (
        "request", "Full request latency distribution"),
    "kubeml_serving_decode_step_seconds": (
        "decode_step", "Per-decode-step device time (chunk fetch / steps)"),
    # request lifecycle phases (one observation per admitted row)
    "kubeml_serving_queue_wait_seconds": (
        "queue_wait", "Submission to decode-slot assignment"),
    "kubeml_serving_prefill_seconds": (
        "prefill", "Slot assignment to the first token landing on the host "
                   "(prefill program + fetch pipeline)"),
    "kubeml_serving_decode_active_seconds": (
        "decode_active", "First token to the row's last emitted token"),
    "kubeml_serving_slot_idle_seconds": (
        "slot_idle", "Slot held after the row's last token before the slot "
                     "freed (completion-detection lag; ~0 for pre-freed "
                     "drained rows)"),
    "kubeml_serving_batch_occupancy_ratio": (
        "occupancy_ratio", "Per-chunk live fraction of device slot-steps"),
    "kubeml_serving_kv_bandwidth_bytes_per_sec": (
        "kv_bandwidth", "Achieved KV-read bandwidth per decode chunk "
                        "(modeled bytes over the chunk's fetch wall time)"),
    "kubeml_serving_spec_accept_ratio": (
        "spec_accept_ratio", "Per-verify-step speculative acceptance ratio "
                             "(accepted / drafted)"),
    # serving latency anatomy (ISSUE 18)
    "kubeml_serving_inter_token_seconds": (
        "inter_token", "Host-visible gap between consecutive token "
                       "emissions for one row (stream smoothness)"),
    "kubeml_serving_cold_start_seconds": (
        "cold_start", "First-call program walls (trace + XLA compile + "
                      "execute) quarantined away from the steady-state "
                      "first_token/decode_step distributions"),
    "kubeml_serving_compile_seconds": (
        "compile", "Per-compile wall time at the engine's jit-program "
                   "seams"),
    # mid-stream recovery (ISSUE 20)
    "kubeml_serving_snapshot_bytes": (
        "snapshot_bytes", "KMS1 snapshot frame size per save/restore "
                          "(page data + scale rows + token chunks)"),
    "kubeml_serving_snapshot_seconds": (
        "snapshot_seconds", "Wall time per snapshot capture or restore "
                            "(arena gather/scatter + codec)"),
}

# histograms rendered as cause-labeled variants of ONE metric name: the
# decode-step distribution splits into chunks that ran clean vs chunks
# dispatched while admission/prefill work was in flight on the device —
# the direct evidence row for chunked prefill (ISSUE 18)
SERVING_HISTOGRAM_VARIANTS = {
    "kubeml_serving_decode_step_seconds": (
        ("decode_step", {"cause": "clean"}),
        ("decode_step_colocated", {"cause": "prefill_colocated"}),
    ),
}

SERVING_GAUGES = {
    "kubeml_serving_tokens_per_second": (
        "tokens_per_second", "Sustained decode rate (10s window)"),
    "kubeml_serving_queue_depth": ("queue_depth",
                                   "Rows waiting for a decode slot"),
    "kubeml_serving_overload_per_second": (
        "overload_per_second",
        "Sustained 429 admission-refusal rate (10s window; a preemption "
        "controller overload signal)"),
    "kubeml_serving_queue_limit": (
        "queue_limit", "Admission limit on queued rows (0 = unbounded)"),
    "kubeml_serving_slots_busy": ("slots_busy", "Occupied decode slots"),
    "kubeml_serving_slots_total": ("slots_total", "Configured decode slots"),
    "kubeml_serving_weight_bytes": (
        "weight_bytes", "Weight bytes read per decode step (int8 halves it)"),
    "kubeml_serving_slot_occupancy": ("slot_occupancy",
                                      "Busy fraction of decode slots"),
    "kubeml_serving_latency_p50_seconds": (
        "latency_p50_seconds", "Median request latency (recent window)"),
    "kubeml_serving_latency_p95_seconds": (
        "latency_p95_seconds", "p95 request latency (recent window)"),
    "kubeml_serving_latency_p99_seconds": (
        "latency_p99_seconds", "p99 request latency (recent window)"),
    "kubeml_serving_latency_max_seconds": (
        "latency_max_seconds", "Max request latency (recent window)"),
    "kubeml_serving_first_token_p50_seconds": (
        "first_token_p50_seconds", "Median time to first token"),
    "kubeml_serving_first_token_p95_seconds": (
        "first_token_p95_seconds", "p95 time to first token"),
    "kubeml_serving_first_token_p99_seconds": (
        "first_token_p99_seconds", "p99 time to first token"),
    "kubeml_serving_first_token_max_seconds": (
        "first_token_max_seconds", "Max time to first token (recent window)"),
    "kubeml_serving_fetchers_inflight": (
        "fetchers_inflight", "Fetcher threads currently blocked on a device "
                             "result fetch"),
    # deliberately NOT *_total: the _total suffix is the counter convention,
    # and this is a gauge one typo away from kubeml_serving_fetches_total
    "kubeml_serving_fetcher_pool_size": (
        "fetchers_total", "Configured result-fetcher pool size"),
    "kubeml_serving_fetcher_utilization": (
        "fetcher_utilization", "Busy fraction of the fetcher pool (in-flight "
                               "/ pool size at scrape time)"),
    "kubeml_serving_goodput_ratio": (
        "goodput_ratio", "Lifetime useful fraction of raw device slot-step "
                         "capacity (live / total slot-steps)"),
    # paged KV arena (PagedBatchingDecoder only — absent on dense decoders)
    "kubeml_serving_pages_total": (
        "pages_total", "Allocatable KV pages in the paged arena (excludes "
                       "the reserved trash page)"),
    "kubeml_serving_pages_free": (
        "pages_free", "KV pages on the free list right now"),
    "kubeml_serving_page_occupancy": (
        "page_occupancy", "Allocated fraction of the paged KV arena"),
    "kubeml_serving_page_tokens": (
        "page_tokens", "Tokens per physical KV page "
                       "(KUBEML_SERVING_PAGE_TOKENS)"),
    "kubeml_serving_prefix_cache_pages": (
        "prefix_cache_pages", "Pages currently held by the shared-prefix "
                              "trie (evictable when unreferenced)"),
    "kubeml_serving_paged_attn_pallas": (
        "paged_attn_kernel", "1 when the paged engine attends through the "
                             "Pallas paged-attention kernel "
                             "(KUBEML_PAGED_ATTN), 0 on the gather "
                             "fallback"),
    "kubeml_serving_kv_quant": (
        "kv_quant", "1 when KV-cache pages are stored int8 with per-page "
                    "scale arenas (KUBEML_KV_QUANT), 0 for compute-dtype "
                    "storage"),
    "kubeml_serving_kv_latent_width": (
        "kv_latent_width", "Values one cached token holds in one layer of a "
                           "latent (MLA) arena, once for all heads and for K "
                           "and V; 0 for a model that pages K/V heads"),
    "kubeml_serving_kv_latent_row_width": (
        "kv_latent_row_width", "Lanes the latent arena stores one token's "
                               "row in: kv_latent_width rounded up to whole "
                               "128-lane rows, zeros past the values; 0 for "
                               "a model that pages K/V heads"),
    "kubeml_serving_state_gate_width": (
        "state_gate_width", "Values the gate of a recurrent state carries a "
                            "step and head: 1 (one decay a head) or the "
                            "keys' width (one a key channel); absent for a "
                            "model without recurrent state and until a step "
                            "has moved one"),
    "kubeml_serving_moe_layers": (
        "moe_layers", "Layers of the served model whose feed-forward is "
                      "routed experts (0: none)"),
    "kubeml_serving_moe_experts_held": (
        "moe_experts_held", "Routed experts of a layer whose weights are "
                            "held here: all of them, or this chip's share "
                            "(0: no expert layers)"),
    "kubeml_serving_cache_sublayers": (
        "cache_sublayers", "Sub-layers of the served model that hold a paged "
                           "cache: its depth, or twice that where a layer "
                           "has two attentions"),
    "kubeml_serving_residual_streams": (
        "residual_streams", "Streams of the served model's residual path: 1, "
                            "or hc_mult under hyper-connections"),
    "kubeml_serving_expert_param_bytes": (
        "expert_param_bytes", "Bytes of the routed experts' stacked weights "
                              "resident for this model; a decode step reads "
                              "the share its rows chose"),
    "kubeml_serving_prefills_in_progress": (
        "prefills_in_progress",
        "Rows currently mid-chunked-prefill: holding a slot and pages but "
        "not yet decoding (KUBEML_PREFILL_CHUNK_TOKENS > 0)"),
    # speculative decoding (spec-mode decoders only)
    "kubeml_serving_spec_accept_rate": (
        "spec_accept_rate", "Lifetime speculative acceptance rate "
                            "(accepted / drafted tokens)"),
    "kubeml_serving_spec_k": (
        "spec_k", "Current adaptive speculation depth (0 = retreated to "
                  "plain decode pending a re-probe)"),
    "kubeml_serving_spec_disabled": (
        "spec_disabled", "1 once the draft backend's sustained acceptance "
                         "fell below KUBEML_SPEC_MIN_ACCEPT and drafting "
                         "was permanently disabled for this model"),
    # serving latency anatomy (ISSUE 18): ITL stream-smoothness quantiles
    # (ring of recent inter-emission gaps), compile-tracker state
    "kubeml_serving_itl_p50_seconds": (
        "itl_p50_seconds", "Median inter-token gap (recent window)"),
    "kubeml_serving_itl_p95_seconds": (
        "itl_p95_seconds", "p95 inter-token gap (recent window)"),
    "kubeml_serving_itl_p99_seconds": (
        "itl_p99_seconds", "p99 inter-token gap (recent window) — the "
                           "kubeml slo itl_p99 signal's source"),
    "kubeml_serving_itl_max_seconds": (
        "itl_max_seconds", "Max inter-token gap (recent window)"),
    "kubeml_serving_compiled_programs": (
        "compiled_programs", "Distinct (program, shape signature) XLA "
                             "executables the engine has traced"),
    "kubeml_serving_compiles_per_minute": (
        "compiles_per_minute", "Compile rate over the last 60s — sustained "
                               "nonzero in steady state means shape churn"),
    "kubeml_serving_compile_storm": (
        "compile_storm", "1 while the compile rate exceeds "
                         "KUBEML_COMPILE_STORM_PER_MIN (0 = healthy)"),
    # graceful drain (ISSUE 20): 1 while the engine refuses admissions and
    # runs down / snapshots live rows ahead of a shutdown
    "kubeml_serving_draining": (
        "draining", "1 while the decoder is draining for shutdown "
                    "(admissions refused 429, live rows running down)"),
}


# SLO engine series (ps/slo.py): burn rates per objective x window, and the
# alert state machine's current state (0=inactive 1=pending 2=firing)
SLO_BURN = "kubeml_slo_burn_rate"
SLO_STATE = "kubeml_slo_alert_state"


PREEMPTIONS = "kubeml_preemptions_total"
YIELD_SECONDS = "kubeml_preempt_yield_seconds"
QUEUE_DEPTH = "kubeml_scheduler_queue_depth"

# distinct preemption reasons kept on the exposition (an unbounded reason
# label would be a cardinality leak; extra reasons fold into "other")
MAX_PREEMPT_REASONS = 16


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        # {(metric, jobid): value}
        self._values: Dict[Tuple[str, str], float] = {}
        # {(metric, jobid): Histogram}; insertion-ordered for oldest-job
        # eviction past MAX_HISTOGRAM_JOBS
        self._hists: Dict[Tuple[str, str], Histogram] = {}
        self._running: Dict[str, int] = {"train": 0, "inference": 0}
        # multi-tenant preemption: {reason: count} + yield-latency histogram
        # (preempt request -> slot freed); per-priority queue depths come
        # from a scheduler-provided source at render time
        self._preemptions: Dict[str, int] = {}
        self._yield_hist = Histogram()
        self._queue_source = None
        # () -> {(direction, reason): count} from the scheduler's decision
        # log (kubeml_scale_decisions_total); read at render/sample time
        self._decision_source = None
        # per-job high-water mark of applied dataplane delta batches
        # (MetricUpdate.dataplane seqs): a redelivered batch — the runner
        # re-sends until a client-observed ack — must fold into the
        # profiler registry at most once. Insertion-ordered, oldest-evicted.
        self._dp_applied: Dict[str, int] = {}
        # () -> {model_id: telemetry dict} from the PS's resident decoders
        # (serving/batcher.telemetry); set by the PS, read at render time
        self._serving_source = None
        # () -> {"burn": {(slo, window): x}, "state": {slo: 0|1|2}} from the
        # SLO engine (ps/slo.py); read at render time
        self._slo_source = None

    def set_serving_source(self, source) -> None:
        self._serving_source = source

    def set_slo_source(self, source) -> None:
        self._slo_source = source

    def set_queue_source(self, source) -> None:
        """() -> {priority: queued count} (scheduler.queue.depths); read at
        render time so the exposition never holds the queue lock long."""
        self._queue_source = source

    def set_decision_source(self, source) -> None:
        """() -> {(direction, reason): count} (scheduler DecisionLog.counts)
        — the kubeml_scale_decisions_total export; read at render/sample
        time, same no-nested-lock discipline as the queue source."""
        self._decision_source = source

    def decisions_snapshot(self) -> Dict[tuple, int]:
        """{(direction, reason): cumulative count} from the bound decision
        source ({} when unbound/broken)."""
        source = getattr(self, "_decision_source", None)
        if source is None:
            return {}
        try:
            return dict(source() or {})
        except Exception:
            return {}

    def job_gauges_snapshot(self) -> Dict[Tuple[str, str], float]:
        """{(metric, jobid): latest value} — every per-job scalar the
        registry holds (the GAUGES values plus the statistical-efficiency
        epoch means), for the tsdb sampler so training series land in
        GET /metrics/history next to the serving ones."""
        with self._lock:
            return dict(self._values)

    def preemption(self, reason: str) -> None:
        """Count one preemption decision (kubeml_preemptions_total{reason})."""
        with self._lock:
            if reason not in self._preemptions:
                # reserve a slot for "other" INSIDE the budget: folding must
                # not itself mint a 17th series
                limit = (MAX_PREEMPT_REASONS if "other" in self._preemptions
                         else MAX_PREEMPT_REASONS - 1)
                if len(self._preemptions) >= limit:
                    reason = "other"
            self._preemptions[reason] = self._preemptions.get(reason, 0) + 1

    def observe_yield(self, seconds: float) -> None:
        """Yield latency: preempt request -> the job's slot freed."""
        with self._lock:
            self._yield_hist.observe(seconds)

    def update(self, u: MetricUpdate) -> None:
        """Per-epoch push from a job (reference: metrics.go:90-98)."""
        if u.dataplane:
            # a standalone runner's dataplane counter delta batches (it has
            # no scraped /metrics of its own): fold into this process's
            # registry so weights.encode.* reaches the exposition. Batches
            # already applied (seq <= high-water mark) are redeliveries of
            # a push whose response was lost — skip, or the Grafana
            # compression panels would overcount. In-process jobs share
            # the registry and push no batches.
            from ..utils import profiler

            with self._lock:
                applied = self._dp_applied.get(u.job_id, 0)
                fresh = [b for b in u.dataplane if isinstance(b, dict)
                         and int(b.get("seq", 0)) > applied]
                if fresh:
                    self._dp_applied.pop(u.job_id, None)  # re-insert as newest
                    self._dp_applied[u.job_id] = max(
                        int(b["seq"]) for b in fresh)
                    # backstop only (primary cleanup is clear() at job
                    # finish): evicting a LIVE job's mark would let its
                    # still-redelivered batches re-fold and overcount, so
                    # the bound is sized far above plausible concurrent
                    # pushers and trips only if jobs leak without finishing
                    while len(self._dp_applied) > 4096:
                        self._dp_applied.pop(next(iter(self._dp_applied)))
            for b in fresh:
                profiler.merge_counters(b.get("phases") or {})
        with self._lock:
            jid = u.job_id
            self._values[("kubeml_job_validation_loss", jid)] = u.validation_loss
            self._values[("kubeml_job_validation_accuracy", jid)] = u.accuracy
            self._values[("kubeml_job_train_loss", jid)] = u.train_loss
            self._values[("kubeml_job_parallelism", jid)] = float(u.parallelism)
            self._values[("kubeml_job_epoch_duration_seconds", jid)] = u.epoch_duration
            # epoch progress: the job reports its own (resume-correct)
            # epoch count; engines predating the field fall back to
            # counting pushes (one MetricUpdate arrives per epoch)
            if u.epoch >= 0:
                self._values[("kubeml_job_epoch", jid)] = float(u.epoch)
            else:
                self._values[("kubeml_job_epoch", jid)] = (
                    self._values.get(("kubeml_job_epoch", jid), 0.0) + 1.0)
            if u.moe_overflow >= 0.0:
                self._values[("kubeml_job_moe_overflow", jid)] = u.moe_overflow
            # promote the flattened timings into real distributions
            self._observe("kubeml_job_epoch_seconds", jid, (u.epoch_duration,))
            self._observe("kubeml_job_round_seconds", jid,
                          u.round_seconds or ())
            if u.merge_seconds >= 0.0:
                self._observe("kubeml_job_merge_seconds", jid,
                              (u.merge_seconds,))
            # statistical-efficiency signals: per-round observations into
            # the histograms, plus the epoch mean stashed under the SAME
            # name for the tsdb sampler (job_gauges_snapshot) — the series
            # `kubeml top` and /metrics/history read. Not in GAUGES, so the
            # exposition renders them as histograms only.
            if u.round_divergence:
                self._observe("kubeml_job_worker_divergence", jid,
                              u.round_divergence)
                self._values[("kubeml_job_worker_divergence", jid)] = (
                    sum(u.round_divergence) / len(u.round_divergence))
            if u.round_loss_spread:
                self._observe("kubeml_job_loss_spread", jid,
                              u.round_loss_spread)
                self._values[("kubeml_job_loss_spread", jid)] = (
                    sum(u.round_loss_spread) / len(u.round_loss_spread))
            if u.round_skew_ratio >= 0.0:
                self._observe("kubeml_job_round_skew_ratio", jid,
                              (u.round_skew_ratio,))
                self._values[("kubeml_job_round_skew_ratio", jid)] = (
                    u.round_skew_ratio)

    def _observe(self, metric: str, job_id: str, values) -> None:
        """Observe into a per-(metric, jobid) histogram; caller holds _lock.
        Bounded: past MAX_HISTOGRAM_JOBS distinct jobs per metric the oldest
        job's series evicts (finished jobs' series deliberately linger —
        histograms are cumulative and the distribution IS the artifact)."""
        if not values:
            return
        h = self._hists.get((metric, job_id))
        if h is None:
            h = self._hists[(metric, job_id)] = Histogram(
                HISTOGRAM_BUCKETS.get(metric, LATENCY_BUCKETS))
            jobs = [j for m, j in self._hists if m == metric]
            while len(jobs) > MAX_HISTOGRAM_JOBS:
                self._hists.pop((metric, jobs.pop(0)), None)
        for v in values:
            h.observe(v)

    def observe(self, metric: str, job_id: str, value: float) -> None:
        """Public single-value observe (engine hooks outside MetricUpdate)."""
        with self._lock:
            self._observe(metric, job_id, (value,))

    def clear(self, job_id: str) -> None:
        """Drop a finished job's series (reference: metrics.go:100-106)."""
        with self._lock:
            for key in [k for k in self._values if k[1] == job_id]:
                del self._values[key]
            # the runner exits with its job, so redeliveries of its
            # dataplane batches stop here — dropping the seq high-water
            # mark now is what keeps the bounded map from ever evicting a
            # LIVE job's mark (which would double-count redelivered bytes)
            self._dp_applied.pop(job_id, None)

    def running_snapshot(self) -> Dict[str, int]:
        """{kind: running count} — the sampler's gauge read."""
        with self._lock:
            return dict(self._running)

    def preemptions_snapshot(self) -> Dict[str, int]:
        """{reason: count} — the sampler's counter read."""
        with self._lock:
            return dict(self._preemptions)

    def queue_depths(self) -> Dict[object, int]:
        """Per-priority queued counts from the bound queue source ({} when
        unbound/broken) — read OUTSIDE the registry lock, same discipline
        as render()."""
        source = self._queue_source
        if source is None:
            return {}
        try:
            return dict(source() or {})
        except Exception:
            return {}

    def task_started(self, kind: str = "train") -> None:
        with self._lock:
            self._running[kind] = self._running.get(kind, 0) + 1

    def task_finished(self, kind: str = "train") -> None:
        with self._lock:
            self._running[kind] = max(0, self._running.get(kind, 0) - 1)

    def render(self) -> str:
        """Prometheus text exposition format."""
        with self._lock:
            lines = []
            for metric, help_text in GAUGES.items():
                series = [(jid, v) for (m, jid), v in self._values.items() if m == metric]
                lines.append(f"# HELP {metric} {escape_help(help_text)}")
                lines.append(f"# TYPE {metric} gauge")
                for jid, v in sorted(series):
                    lines.append(
                        f'{metric}{{jobid="{escape_label_value(jid)}"}} {v}')
            for metric, help_text in HISTOGRAMS.items():
                lines.append(f"# HELP {metric} {escape_help(help_text)}")
                lines.append(f"# TYPE {metric} histogram")
                for (m, jid), h in sorted(self._hists.items()):
                    if m == metric:
                        lines.extend(h.render(metric, "jobid", jid))
            lines.append(f"# HELP {RUNNING} Number of running tasks")
            lines.append(f"# TYPE {RUNNING} gauge")
            for kind, n in sorted(self._running.items()):
                lines.append(
                    f'{RUNNING}{{type="{escape_label_value(kind)}"}} {n}')
            # multi-tenant preemption series (scheduler/preemption.py)
            lines.append(f"# HELP {PREEMPTIONS} Training jobs preempted "
                         f"(checkpoint-and-yield), by reason")
            lines.append(f"# TYPE {PREEMPTIONS} counter")
            for reason, n in sorted(self._preemptions.items()):
                lines.append(f'{PREEMPTIONS}{{reason='
                             f'"{escape_label_value(reason)}"}} {n}')
            lines.append(f"# HELP {YIELD_SECONDS} Preemption yield latency "
                         f"(preempt request until the job's slot freed)")
            lines.append(f"# TYPE {YIELD_SECONDS} histogram")
            # rendered even at zero observations: the exported metric set
            # (and the dashboard's quantile query) must not depend on a
            # preemption having happened yet
            lines.extend(self._yield_hist.render(YIELD_SECONDS))
            source = self._serving_source
            queue_source = self._queue_source
        # per-priority scheduler queue gauges OUTSIDE the lock (the source
        # snapshots the queue under its own lock and must not nest under ours)
        lines.append(f"# HELP {QUEUE_DEPTH} Queued train tasks per priority "
                     f"class")
        lines.append(f"# TYPE {QUEUE_DEPTH} gauge")
        if queue_source is not None:
            try:
                depths = queue_source()
            except Exception:
                depths = {}
            for prio, n in sorted(depths.items()):
                lines.append(f'{QUEUE_DEPTH}{{priority='
                             f'"{escape_label_value(prio)}"}} {n}')
        # elastic scale-decision counters (scheduler/decisions.py) — the
        # audit trail's aggregate view, labeled by transition direction and
        # enumerated reason. Headers render even before any decision so the
        # exported metric set is stable.
        lines.append(f"# HELP {SCALE_DECISIONS} Elastic scale decisions by "
                     f"transition direction and enumerated reason")
        lines.append(f"# TYPE {SCALE_DECISIONS} counter")
        for (direction, reason), n in sorted(self.decisions_snapshot().items()):
            lines.append(
                f'{SCALE_DECISIONS}{{direction="{escape_label_value(direction)}"'
                f',reason="{escape_label_value(reason)}"}} {int(n)}')
        # serving telemetry OUTSIDE the lock: the source snapshots each
        # decoder under its own lock and must not nest under ours. HELP/TYPE
        # headers render even with no source/decoders — the exported metric
        # set must not depend on traffic having happened yet.
        per_model = {}
        if source is not None:
            try:
                per_model = source()
            except Exception:
                per_model = {}
        for metric, (key, help_text) in SERVING_COUNTERS.items():
            lines.append(f"# HELP {metric} {escape_help(help_text)}")
            lines.append(f"# TYPE {metric} counter")
            for model, snap in sorted(per_model.items()):
                if key in snap:
                    lines.append(f'{metric}{{model='
                                 f'"{escape_label_value(model)}"}} {snap[key]}')
        # XLA compile counters, labeled {model, program} (ISSUE 18): one
        # line per jit-program seam the engine compiled through
        lines.append(f"# HELP {SERVING_COMPILES} "
                     f"{escape_help(SERVING_COMPILES_HELP)}")
        lines.append(f"# TYPE {SERVING_COMPILES} counter")
        for model, snap in sorted(per_model.items()):
            for program, n in sorted((snap.get("compiles") or {}).items()):
                lines.append(
                    f'{SERVING_COMPILES}{{model="{escape_label_value(model)}"'
                    f',program="{escape_label_value(program)}"}} {int(n)}')
        for metric, (key, help_text) in SERVING_GAUGES.items():
            lines.append(f"# HELP {metric} {escape_help(help_text)}")
            lines.append(f"# TYPE {metric} gauge")
            for model, snap in sorted(per_model.items()):
                if key in snap:
                    lines.append(f'{metric}{{model='
                                 f'"{escape_label_value(model)}"}} {snap[key]}')
        for metric, (key, help_text) in SERVING_HISTOGRAMS.items():
            lines.append(f"# HELP {metric} {escape_help(help_text)}")
            lines.append(f"# TYPE {metric} histogram")
            # cause-labeled variants render each populated half under the
            # SAME metric name (decode_step clean vs prefill_colocated)
            variants = SERVING_HISTOGRAM_VARIANTS.get(metric, ((key, None),))
            for model, snap in sorted(per_model.items()):
                for vkey, extra in variants:
                    hist_snap = (snap.get("hist") or {}).get(vkey)
                    if hist_snap:
                        lines.extend(Histogram.render_snapshot(
                            metric, hist_snap, "model", model, extra=extra))
        # SLO burn rates + alert states (ps/slo.py). Headers render even
        # with no engine/objectives — same stable-metric-set discipline.
        lines.append(f"# HELP {SLO_BURN} SLO error-budget burn rate per "
                     f"objective and window (1.0 = burning exactly the "
                     f"budget)")
        lines.append(f"# TYPE {SLO_BURN} gauge")
        slo = {}
        if self._slo_source is not None:
            try:
                slo = self._slo_source() or {}
            except Exception:
                slo = {}
        for (name, window), burn in sorted((slo.get("burn") or {}).items()):
            lines.append(
                f'{SLO_BURN}{{slo="{escape_label_value(name)}",'
                f'window="{escape_label_value(window)}"}} {burn:g}')
        lines.append(f"# HELP {SLO_STATE} SLO alert state "
                     f"(0=inactive 1=pending 2=firing)")
        lines.append(f"# TYPE {SLO_STATE} gauge")
        for name, state in sorted((slo.get("state") or {}).items()):
            lines.append(
                f'{SLO_STATE}{{slo="{escape_label_value(name)}"}} {int(state)}')
        # control-plane resilience counters (utils.resilience): retries,
        # breaker state/opens, deadline rejections, chaos injections —
        # process-local, rendered on the same exposition so one scrape sees
        # the whole fault-handling picture
        try:
            from ..utils import resilience

            lines.extend(resilience.render_metrics())
        except Exception:  # exposition must never fail the scrape
            pass
        # data-plane byte accounting (utils.profiler): per-phase byte/second
        # totals + the staging-bandwidth histogram, same one-scrape discipline
        try:
            from ..utils import profiler

            lines.extend(profiler.render_metrics())
        except Exception:
            pass
        return "\n".join(lines) + "\n"

    def get(self, metric: str, job_id: str) -> float:
        with self._lock:
            return self._values[(metric, job_id)]
