"""Serving-runtime telemetry for the continuous batcher.

The reference instruments every surface it ships with Prometheus gauges
(reference: ml/pkg/ps/metrics.go:33-86); its serving surface is a bare
forward pass so there is nothing to count. The TPU rebuild's serving engine
(serving/batcher.py) is a real runtime — slots, queues, admission waves —
so it gets the same discipline: one ``DecoderStats`` per resident decoder,
counters bumped on the engine/submit threads (lock-guarded, O(1) per
event), rendered into the PS ``/metrics`` exposition next to the training
gauges (VERDICT r4 weak-4).

Two truth layers beyond the basic counters (PR 11 — the measurement
substrate the continuous-batching refactor and the SLO autoscaler are
judged against):

* **Request lifecycle attribution** — every request's timeline
  (admitted -> queued -> slot-assigned -> prefill -> first-token ->
  decode -> drained/shed/expired) feeds per-phase histograms
  (``kubeml_serving_{queue_wait,prefill,decode_active,slot_idle}_seconds``)
  so the question "where did this request's latency go" has a measured
  answer instead of the fetch-pipeline arithmetic SERVING_R5 did by hand.
* **Batch-occupancy / goodput accounting** — per-device-step slot truth
  from the chunk loop: live vs dead vs idle slot-steps (dead = a resident
  row the device stepped but that emitted nothing — the exact waste the
  pre-free hack attacks), prefill padding tokens, and useful-token goodput
  vs raw device-step token throughput, plus a per-chunk occupancy-ratio
  histogram (``kubeml_serving_batch_occupancy_ratio``).

Latency quantiles come from a bounded ring of recent requests (no
unbounded growth on a long-lived server); sustained tokens/sec and the
windowed 429 rate ride shared :class:`utils.timeseries.Series` rings —
the one windowed-rate implementation the preemption controller and the
SLO engine also query (the hand-rolled deque windows this file used to
carry are gone). Cumulative Prometheus histograms (ps/metrics.Histogram)
record TTFT, full request latency, and per-decode-step device time since
process start.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..ps.metrics import (BANDWIDTH_BUCKETS, Histogram, OCCUPANCY_BUCKETS,
                          SNAPSHOT_BYTES_BUCKETS)
from ..utils.timeseries import Series

# ring sizes: enough for stable p95 under load, bounded for a resident server
LATENCY_RING = 512
RATE_WINDOW_S = 10.0
# samples the windowed-rate rings keep: sized to hold a full RATE_WINDOW_S of
# per-event samples under heavy traffic (one sample per emit/429 event)
RATE_RING = 4096
# compile-storm detection window: compiles/minute is judged over this span
COMPILE_WINDOW_S = 60.0

# a replica's start before its first program, and jax's phases of a first
# call (with the persistent cache's two counts) as telemetry() names them
STARTUP_PHASES = ("restore", "hold", "decoder", "slab")
COMPILE_PHASES = {"trace_s": "compile_trace_seconds",
                  "lower_s": "compile_lower_seconds",
                  "backend_s": "compile_backend_seconds",
                  "cache_hits": "compile_cache_hits",
                  "cache_misses": "compile_cache_misses"}

logger = logging.getLogger(__name__)


class DecoderStats:
    """Thread-safe counters/gauges for one resident decoder."""

    def __init__(self, slots: int):
        self.slots = int(slots)
        self._lock = threading.Lock()
        self.requests_submitted = 0   # requests accepted into the queue
        self.requests_completed = 0   # requests that returned a full result
        self.requests_rejected = 0    # validation 400s (never enqueued)
        self.requests_timeout = 0     # waiter gave up (504) — rows canceled
        self.requests_canceled = 0    # abandoned by explicit cancel
        self.requests_failed = 0      # engine-side failure surfaced
        # overload protection (batcher admission limit / shed / deadlines)
        self.requests_overload = 0    # 429-refused at admission (not queued)
        self.requests_shed = 0        # shed oldest-first after queueing
        self.requests_deadline_expired = 0  # expired while queued (504)
        self.tokens_emitted = 0
        self.admission_waves = 0      # prefill+admit programs dispatched
        self.chunks = 0               # decode chunk programs
        # --- occupancy / goodput (per-device-step truth, chunk loop) ---
        self.device_steps = 0         # decode steps executed (sum of T)
        self.slot_steps = 0           # T x S per chunk: raw device capacity
        self.live_slot_steps = 0      # slot-steps that emitted a token
        self.dead_slot_steps = 0      # resident row, no emission (waste)
        self.idle_slot_steps = 0      # no resident row (free capacity)
        self.prefill_tokens = 0       # real prompt tokens prefilled
        self.prefill_pad_tokens = 0   # bucket + row padding tokens computed
        self.prefill_head_positions = 0  # positions their output heads took
        # routed-expert layers, decode steps only: token-to-expert
        # assignments given to experts held here (live rows x top_k x
        # expert layers a step where every choice is one: what enters the
        # grouped product) and distinct experts they chose (summed over
        # layers), whose weights a step has to read; and, where a layer
        # holds a share of its experts or has identity experts, the
        # assignments to identity (zero-compute) experts and to experts that
        # lie on other chips, which add nothing here. The three add up to
        # live rows x top_k x expert layers a step
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_assignments_zero = 0
        self.moe_assignments_absent = 0
        # a residual path of several streams (hyper-connections): positions
        # x sub-layers its maps were made and its streams mixed for, bucket
        # padding and dead rows included (the device does them), by the
        # kind of program; ``hc_sublayers`` is the model's count of such
        # sub-layers (set by the engine; 0 for a single stream)
        self.hc_sublayers = 0
        self.hc_positions_admit = 0
        self.hc_positions_step = 0
        # the K/V page walk's decode body (ops/paged_attention.py): grid
        # programs the decode steps ran, all attention layers (program rows
        # x table width / pages a program), and those inside a live row's
        # depth, which fetch pages and multiply; the rest are empty. Set and
        # fed by the paged engine where its steps take that body
        # (``walks_kv_chunks``); an engine that walks latents, a quantized
        # arena or gathers reports neither. ``tile_chunks_*`` are the same
        # two counts for the tile body's programs in the prefill and
        # admission programs (query tiles x table width / pages a program):
        # live are those under the tile's causal depth and the row's
        self.walks_kv_chunks = False
        self.walk_chunks_live = 0
        self.walk_chunks_grid = 0
        self.tile_chunks_live = 0
        self.tile_chunks_grid = 0
        # the latent page walk's loop (ops/mla_attention.py), where the
        # paged engine's steps take it (``walks_latents``; absent otherwise):
        # trips the decode steps' kernel made, all latent layers (every
        # program row is ``ceil(depth / C)`` trips of ``C`` pages, whatever
        # the table's width), those of them a live row's, and the pages the
        # trips copied; a trip that is not live is a dead row's, one over
        # the trash page a step
        self.walks_latents = False
        self.latent_walk_trips_live = 0
        self.latent_walk_trips_run = 0
        self.latent_walk_pages = 0
        # the same four split by layer kind, for a model that mixes window
        # layers with full ones (``window_layers`` > 0, set by the engine;
        # absent otherwise): ``*_window`` are the window layers' part of the
        # totals above, which keep their meaning. A window layer's decode
        # grid is a ring a row (one program where the ring is at most 16
        # pages), an admit's the bucket's own pages of which a tile's window
        # meets two chunks. ``window_pages_held`` / ``window_pages_live``:
        # ring pages the live rows hold, a step and window layer, and those
        # of them a step's query could read (the pages its ``window`` keys
        # lie in): the lease's bound against its use
        self.window_layers = 0
        self.walk_chunks_live_window = 0
        self.walk_chunks_grid_window = 0
        self.tile_chunks_live_window = 0
        self.tile_chunks_grid_window = 0
        self.window_pages_held = 0
        self.window_pages_live = 0
        # recurrent state beside the pages (absent for a model without one
        # and until a step has run): the decode step's state kernel reads
        # and writes every slab row in each layer that keeps a state
        # (``state_rows_moved``, rows a step; telemetry's
        # ``recurrent_layers`` says how many layers), of which the live
        # rows' advance (``state_rows_live``)
        self.state_rows_moved = 0
        self.state_rows_live = 0
        self.goodput_tokens = 0       # tokens delivered to a live waiter
        self.wasted_tokens = 0        # tokens routed to an aborted request
        # shared-prefix reuse (paged engine, serving/kvpool.py): admissions
        # whose leading prompt blocks came from the prefix trie, and the
        # prompt tokens those cached pages covered (prefill skipped them)
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        # KV-read accounting (ISSUE 15): bytes the decode-path attention
        # read from the KV cache, host-modeled from the table geometry each
        # dispatch shipped (gather = rows x gathered width, Pallas kernel =
        # live pages only — the whole point of the paged-attention kernel
        # is making this number scale with occupancy, and the counter is
        # how the win shows on a scrape)
        self.kv_read_bytes = 0
        # speculative decoding (paged engine spec mode): drafted = tokens
        # the drafter sampled, proposed = candidate emissions submitted to
        # one-pass verification (drafts + the bonus position per live row),
        # accepted = drafted tokens that survived the rejection rule.
        # acceptance ratio = accepted / drafted; tokens-per-step reads
        # tokens_emitted / device_steps (a spec step counts ONE device
        # step — its k+1-wide token capacity rides chunk_occupancy)
        self.spec_steps = 0
        self.spec_drafted_tokens = 0
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        # fetcher pool (short-request workloads can be fetch-pipeline-
        # bound): completed fetches,
        # cumulative blocked wall seconds (rate/pool = utilization), live
        # in-flight count, and the configured pool size (set by the engine)
        self.fetches = 0
        self.fetch_busy_seconds = 0.0
        self.fetchers_inflight = 0
        self.fetchers_total = 0
        # head-of-line stall attribution (ISSUE 18): wall seconds charged to
        # decoding rows that sat behind a dispatched chunk carrying prefill
        # work (admission or long suffix-prefill) — seconds x stalled rows,
        # the direct evidence counter for chunked prefill / disaggregation
        self.hol_stall_seconds = 0.0
        # chunked prefill (ISSUE 19): prefill dispatches that were chunks
        # of a long prompt (intermediates AND the final admission chunk of
        # a chunked row), and the prompt tokens those chunks covered —
        # monolithic admissions bump neither, so nonzero means the
        # KUBEML_PREFILL_CHUNK_TOKENS path actually ran
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        # mid-stream recovery (ISSUE 20, serving/kvsnap.py): KMS1 snapshot
        # lifecycle — saved (fault/drain capture), restored (scattered into
        # fresh pages and resumed), replayed (re-admitted through the queue
        # after a fault rebuild), failed (either direction; the request got
        # a retryable error instead of a silent hang)
        self.snapshot_saved = 0
        self.snapshot_restored = 0
        self.snapshot_replayed = 0
        self.snapshot_failed = 0
        # KVPool invariant watchdog (KUBEML_POOL_AUDIT_INTERVAL)
        self.pool_audit_runs = 0
        self.pool_audit_failures = 0
        # compile tracker (ISSUE 18): distinct traced XLA programs keyed by
        # (program label, shape signature); per-label compile counts; the
        # storm threshold is set by the engine from config (compiles/min
        # above it flips the storm gauge and logs a throttled warning)
        self._compiled: set = set()
        self.compiles: Dict[str, int] = {}
        # where a replica's start went, in seconds: the parameter server's
        # load path (checkpoint files to leaves, leaves held in the served
        # type, the decoder's construction) and the slab's own program,
        # then the first call of each engine program by jax's phases
        # (utils.tracing.CompileClock) beside the walls the ``compile``
        # histogram sums, and the persistent cache's hits and writes. They
        # grow at a first call and nowhere else
        self.startup_seconds = dict.fromkeys(STARTUP_PHASES, 0.0)
        # leaves of the served tree held narrower than the checkpoint has
        # them because the programs only ever cast them (the parameter
        # server's hold sets it; 0 where an option decided the type)
        self.param_leaves_narrowed = 0
        self.compile_phases = dict.fromkeys(COMPILE_PHASES, 0.0)
        self.compile_storm_per_min = 0.0
        self._storm_logged_at = 0.0
        self._lat: deque = deque(maxlen=LATENCY_RING)        # (total_s,)
        self._first: deque = deque(maxlen=LATENCY_RING)      # first-token s
        self._itl: deque = deque(maxlen=LATENCY_RING)        # inter-token s
        # windowed rates ride the shared time-series primitive: cumulative
        # samples at event time, queried over RATE_WINDOW_S (the preemption
        # controller and SLO engine use the same Series.rate machinery)
        self._emit_series = Series(RATE_RING, kind="counter")
        self._overload_series = Series(RATE_RING, kind="counter")
        # seed the cumulative rings at zero: a counter's value before its
        # first event is KNOWN here (0 at construction), so the first
        # event's full increment must count toward the windowed rate —
        # unseeded, Series anchors a newborn ring at its own first sample
        t0 = time.monotonic()
        self._emit_series.observe(0.0, t=t0)
        self._overload_series.observe(0.0, t=t0)
        # cumulative compile count over time — the storm rate's substrate
        self._compile_series = Series(RATE_RING, kind="counter")
        self._compile_series.observe(0.0, t=t0)
        # cumulative bucket histograms (process lifetime, not windowed):
        # rendered as kubeml_serving_*_seconds_bucket on the PS /metrics
        self._hist_first = Histogram()
        self._hist_request = Histogram()
        self._hist_decode_step = Histogram()
        # decode steps whose chunk shipped colocated prefill work — the
        # {cause="prefill_colocated"} half of the decode-step exposition;
        # clean steps stay in _hist_decode_step ({cause="clean"})
        self._hist_decode_step_coloc = Histogram()
        # host-visible gap between consecutive token emissions for one row
        self._hist_itl = Histogram()
        # first-call program walls (trace + XLA compile) quarantined away
        # from the steady-state first_token/decode_step histograms
        self._hist_cold = Histogram()
        self._hist_compile = Histogram()
        # request lifecycle phases (one observation per ROW: a batch-B
        # request contributes B queue waits — each row queues and holds a
        # slot individually)
        self._hist_queue_wait = Histogram()
        self._hist_prefill = Histogram()
        self._hist_decode_active = Histogram()
        self._hist_slot_idle = Histogram()
        # per-chunk live-fraction distribution (0..1 edges)
        self._hist_occupancy = Histogram(OCCUPANCY_BUCKETS)
        # achieved KV-read bandwidth per decode chunk (modeled bytes over
        # the chunk's fetch wall — the execution barrier), log-scaled edges
        self._hist_kv_bw = Histogram(BANDWIDTH_BUCKETS)
        # per-verify-step acceptance-ratio distribution (0..1 edges)
        self._hist_spec_accept = Histogram(OCCUPANCY_BUCKETS)
        # KMS1 snapshot frame sizes (log byte edges) and capture/restore
        # walls — one observation per save AND per restore
        self._hist_snap_bytes = Histogram(SNAPSHOT_BYTES_BUCKETS)
        self._hist_snap_seconds = Histogram()
        # live gauges are read from the decoder at render time (queue depth,
        # busy slots) — they belong to the engine's own state, not counters

    # --- event hooks (engine/submit threads) ---

    def submitted(self, rows: int) -> None:
        with self._lock:
            self.requests_submitted += rows

    def admitted_wave(self) -> None:
        with self._lock:
            self.admission_waves += 1

    def chunk(self, state_rows: tuple = (0, 0)) -> None:
        """One dispatched decode chunk; ``state_rows`` is its ``(moved,
        live)`` recurrent states over all its steps: slab rows that went
        through the state kernel, and the live rows among them."""
        with self._lock:
            self.chunks += 1
            self.state_rows_moved += int(state_rows[0])
            self.state_rows_live += int(state_rows[1])

    def chunk_occupancy(self, steps: int, live: int, dead: int,
                        idle: int, capacity: Optional[int] = None) -> None:
        """Per-device-step slot accounting for one processed chunk:
        ``steps`` decode steps over ``capacity`` resident rows (the chunk
        program's own width — the paged engine's page-indexed row count is
        decoupled from the dense engine's slot count, so capacity travels
        per call; None keeps the constructor's slot count) split into live
        (token emitted), dead (resident row, nothing emitted — the
        dead-step waste SERVING_R5 had to reason about blind) and idle (no
        row) slot-steps. The partition identity live + dead + idle ==
        steps x capacity holds for every call regardless of capacity."""
        if steps <= 0:
            return
        total = steps * (capacity if capacity is not None else self.slots)
        with self._lock:
            self.device_steps += int(steps)
            self.slot_steps += total
            self.hc_positions_step += total * self.hc_sublayers
            self.live_slot_steps += int(live)
            self.dead_slot_steps += int(dead)
            self.idle_slot_steps += int(idle)
            self._hist_occupancy.observe(live / total if total else 0.0)

    def spec_step(self, drafted: int, accepted: int, proposed: int) -> None:
        """One processed speculative verify step: ``drafted`` tokens were
        sampled by the drafter across the step's live rows, ``accepted``
        of them passed the acceptance rule, ``proposed`` candidate
        emissions went through the one-pass verification (drafts + the
        bonus position per live row)."""
        if drafted <= 0:
            return
        with self._lock:
            self.spec_steps += 1
            self.spec_drafted_tokens += int(drafted)
            self.spec_accepted_tokens += int(accepted)
            self.spec_proposed_tokens += int(proposed)
            self._hist_spec_accept.observe(
                min(1.0, int(accepted) / int(drafted)))

    def kv_read(self, nbytes: int, seconds: float = 0.0) -> None:
        """One dispatched program's modeled KV-cache read traffic:
        ``nbytes`` accumulates the counter; with ``seconds`` (the decode
        chunk's service time, batcher.service_interval) the
        achieved-bandwidth histogram gets one observation. Prefill programs
        report bytes only (seconds 0)."""
        if nbytes <= 0:
            return
        with self._lock:
            self.kv_read_bytes += int(nbytes)
            if seconds > 0:
                self._hist_kv_bw.observe(nbytes / seconds)

    def prefix_hit(self, tokens_saved: int) -> None:
        """One admission served partly from the shared-prefix cache:
        ``tokens_saved`` prompt tokens' prefill was skipped entirely."""
        with self._lock:
            self.prefix_hits += 1
            self.prefix_tokens_saved += int(tokens_saved)

    def admit_tokens(self, real: int, padding: int,
                     head_positions: int = 1) -> None:
        """Prefill token accounting for one admission program: ``real``
        prompt tokens vs ``padding`` computed-but-useless tokens (prompt
        bucket padding; in the slot engine also the repeated rows padding
        the program to S — a paged admission program carries one row).
        ``head_positions`` of them went through the output head: the one
        a row that is sampled from (until PR 46 every position computed,
        ``real + padding``)."""
        with self._lock:
            self.prefill_tokens += int(real)
            self.prefill_pad_tokens += int(padding)
            self.prefill_head_positions += int(head_positions)
            self.hc_positions_admit += (
                int(real) + int(padding)) * self.hc_sublayers

    def moe_steps(self, assignments: int, touched: int, zero: int,
                  absent: int) -> None:
        """Expert-layer accounting for one processed decode chunk, from the
        step program's own outputs (no extra fetch): ``assignments`` to
        ``touched`` experts held here, ``zero`` to identity experts,
        ``absent`` to experts held elsewhere."""
        with self._lock:
            self.moe_assignments += int(assignments)
            self.moe_experts_touched += int(touched)
            self.moe_assignments_zero += int(zero)
            self.moe_assignments_absent += int(absent)

    def walk_chunks(self, live: int, grid: int, window: tuple = (0, 0),
                    ring_pages: tuple = (0, 0)) -> None:
        """One dispatched decode chunk's page-walk programs, all steps and
        attention layers: ``live`` of ``grid`` had pages to read;
        ``window`` is the window layers' ``(live, grid)`` among them and
        ``ring_pages`` their ``(live, held)`` ring pages."""
        with self._lock:
            self.walk_chunks_live += int(live)
            self.walk_chunks_grid += int(grid)
            self.walk_chunks_live_window += int(window[0])
            self.walk_chunks_grid_window += int(window[1])
            self.window_pages_live += int(ring_pages[0])
            self.window_pages_held += int(ring_pages[1])

    def latent_walk(self, live: int, run: int, pages: int) -> None:
        """One dispatched decode chunk's latent-walk trips, all steps and
        latent layers: ``live`` of the ``run`` were a live row's, and the
        ``run`` copied ``pages`` pages."""
        with self._lock:
            self.latent_walk_trips_live += int(live)
            self.latent_walk_trips_run += int(run)
            self.latent_walk_pages += int(pages)

    def tile_chunks(self, live: int, grid: int,
                    window: tuple = (0, 0)) -> None:
        """One dispatched prefill's page-walk programs, all query tiles and
        attention layers: ``live`` of ``grid`` had pages to read;
        ``window`` is the window layers' ``(live, grid)`` among them."""
        with self._lock:
            self.tile_chunks_live += int(live)
            self.tile_chunks_grid += int(grid)
            self.tile_chunks_live_window += int(window[0])
            self.tile_chunks_grid_window += int(window[1])

    def fetch_started(self) -> None:
        with self._lock:
            self.fetchers_inflight += 1

    def fetch_finished(self, seconds: float) -> None:
        with self._lock:
            self.fetchers_inflight = max(0, self.fetchers_inflight - 1)
            self.fetches += 1
            self.fetch_busy_seconds += float(seconds)

    def chunk_fetched(self, seconds: float, steps: int,
                      colocated: bool = False, cold: bool = False) -> None:
        """A decode chunk's results landed on the host: ``seconds`` is the
        chunk's own service time (batcher.service_interval: not the wall of
        its fetch, which covers the programs queued ahead of it as well),
        ``steps`` the decode steps it covered — the per-step quotient is
        the decode-step latency distribution.
        ``colocated`` routes the observation to the
        ``{cause="prefill_colocated"}`` series (the chunk shared the device
        with admission/prefill work); ``cold`` quarantines a first-call
        program wall into the cold-start histogram so XLA compile time
        never pollutes the steady-state decode-step distribution."""
        if steps <= 0:
            return
        with self._lock:
            per_step = float(seconds) / steps
            if cold:
                self._hist_cold.observe(per_step)
            elif colocated:
                self._hist_decode_step_coloc.observe(per_step)
            else:
                self._hist_decode_step.observe(per_step)

    def inter_token(self, gap_s: float) -> None:
        """Host-visible gap between two consecutive token emissions for one
        row (stream smoothness — the thing TTFT can't see). One observation
        per gap: a row emitting n tokens contributes n-1 gaps."""
        with self._lock:
            g = max(0.0, float(gap_s))
            self._itl.append(g)
            self._hist_itl.observe(g)

    def hol_stall(self, seconds: float, rows: int) -> None:
        """Charge one prefill-carrying program's service time to the
        ``rows`` live decoding rows that sat behind it (head-of-line
        blocking): the counter accumulates seconds x rows — total
        decode-seconds lost."""
        if rows <= 0 or seconds <= 0:
            return
        with self._lock:
            self.hol_stall_seconds += float(seconds) * int(rows)

    def prefill_chunk(self, rows: int, tokens: int) -> None:
        """One chunked-prefill dispatch advanced ``rows`` mid-prefill rows
        by ``tokens`` real prompt tokens total (each row counts one chunk;
        the final chunk of a chunked row counts here too). Token totals
        ride :meth:`admit_tokens` as usual — this pair isolates how much
        prefill ran chunked."""
        if rows <= 0:
            return
        with self._lock:
            self.prefill_chunks += int(rows)
            self.prefill_chunk_tokens += int(tokens)

    def snapshot_save(self, nbytes: int, seconds: float) -> None:
        """One live row's KV state captured into a KMS1 frame (engine
        fault recovery or graceful drain)."""
        with self._lock:
            self.snapshot_saved += 1
            self._hist_snap_bytes.observe(float(nbytes))
            self._hist_snap_seconds.observe(max(0.0, float(seconds)))

    def snapshot_restore(self, nbytes: int, seconds: float) -> None:
        """One snapshot scattered into fresh pages and resumed mid-stream."""
        with self._lock:
            self.snapshot_restored += 1
            self._hist_snap_bytes.observe(float(nbytes))
            self._hist_snap_seconds.observe(max(0.0, float(seconds)))

    def snapshot_replay(self, rows: int) -> None:
        """``rows`` snapshotted rows re-admitted through the queue after a
        fault snapshot-and-rebuild cycle."""
        if rows <= 0:
            return
        with self._lock:
            self.snapshot_replayed += int(rows)

    def snapshot_fail(self, rows: int = 1) -> None:
        """A snapshot capture or restore attempt failed — the request was
        failed with a clean retryable error instead."""
        with self._lock:
            self.snapshot_failed += int(rows)

    def pool_audit(self, ok: bool) -> None:
        """One periodic kvpool.check() invariant audit completed."""
        with self._lock:
            self.pool_audit_runs += 1
            if not ok:
                self.pool_audit_failures += 1

    def cold_start(self, seconds: float) -> None:
        """A first-call (trace+compile) wall observed outside the decode
        path — admission or spec programs — lands in the cold series."""
        with self._lock:
            self._hist_cold.observe(max(0.0, float(seconds)))

    # --- compile tracker (engine thread) ---

    def compile_begin(self, program: str, sig: Tuple) -> bool:
        """Atomically record intent to run program ``program`` with shape
        signature ``sig``; returns True exactly once per distinct
        (program, sig) pair — the caller times that first (compiling) call
        and reports it via :meth:`compiled`. Subsequent calls are XLA
        executable-cache hits and return False."""
        key = (str(program), tuple(sig))
        with self._lock:
            if key in self._compiled:
                return False
            self._compiled.add(key)
            return True

    def startup(self, phase: str, seconds: float) -> None:
        """Seconds of one of :data:`STARTUP_PHASES`, timed where it ran."""
        with self._lock:
            self.startup_seconds[phase] += max(0.0, float(seconds))

    def startup_report(self) -> str:
        """Where the start went, for the line a decoder logs at its first
        token: every phase in seconds, the cache's hits and writes."""
        with self._lock:
            parts = [f"{k} {v:.2f}" for k, v in self.startup_seconds.items()]
            c = self.compile_phases
            return (f"{', '.join(parts)}; {len(self._compiled)} programs "
                    f"{self._hist_compile.sum:.2f} (trace {c['trace_s']:.2f}"
                    f", lower {c['lower_s']:.2f}, backend "
                    f"{c['backend_s']:.2f}; cache {c['cache_hits']:.0f} hits"
                    f", {c['cache_misses']:.0f} writes)")

    def compiled(self, program: str, seconds: float,
                 phases: Optional[Dict[str, float]] = None) -> None:
        """One first-call program wall: tracing, lowering and XLA's compile
        or the persistent cache's read. Not the execution: the dispatch that
        follows is asynchronous. ``phases`` is the compile clock's bracket
        of the same call (:data:`COMPILE_PHASES`). Bumps the per-program
        compile counter, the compile-wall histogram,
        and the storm-rate series; logs a throttled warning when the
        60s compile rate exceeds the configured compiles/min knob."""
        now = time.monotonic()
        with self._lock:
            self.compiles[program] = self.compiles.get(program, 0) + 1
            self._hist_compile.observe(max(0.0, float(seconds)))
            for key in COMPILE_PHASES:
                self.compile_phases[key] += (phases or {}).get(key, 0.0)
            total = sum(self.compiles.values())
            self._compile_series.observe(float(total), t=now)
            per_min = self._compile_series.rate(
                COMPILE_WINDOW_S, now=now) * 60.0
            storm = (self.compile_storm_per_min > 0
                     and per_min > self.compile_storm_per_min)
            warn = storm and now - self._storm_logged_at > 30.0
            if warn:
                self._storm_logged_at = now
        if warn:
            logger.warning(
                "compile storm: %.1f compiles/min exceeds the %.1f/min "
                "threshold (last: %s, %.2fs) — check for shape churn "
                "(table-width buckets, chunk ladder, clone toggles)",
                per_min, self.compile_storm_per_min, program, seconds)

    def emitted(self, n: int, wasted: bool = False) -> None:
        """``n`` tokens routed to a request; ``wasted`` marks tokens whose
        waiter already gave up (timeout/cancel) — computed, not goodput."""
        now = time.monotonic()
        with self._lock:
            self.tokens_emitted += n
            if wasted:
                self.wasted_tokens += n
            else:
                self.goodput_tokens += n
            self._emit_series.observe(self.tokens_emitted, t=now)

    def phase(self, name: str, seconds: float) -> None:
        """Observe one request-lifecycle phase duration (``queue_wait``,
        ``prefill``, ``decode_active``, ``slot_idle``)."""
        h = {"queue_wait": self._hist_queue_wait,
             "prefill": self._hist_prefill,
             "decode_active": self._hist_decode_active,
             "slot_idle": self._hist_slot_idle}.get(name)
        if h is None:
            return
        with self._lock:
            h.observe(max(0.0, float(seconds)))

    def first_token(self, seconds: float, cold: bool = False) -> None:
        """TTFT for one row; ``cold`` means the admission program compiled
        on this call — the wall is quarantined into the cold-start series
        and excluded from the steady-state TTFT histogram AND ring."""
        with self._lock:
            if cold:
                self._hist_cold.observe(float(seconds))
                return
            self._first.append(float(seconds))
            self._hist_first.observe(float(seconds))

    def completed(self, latency_s: float) -> None:
        with self._lock:
            self.requests_completed += 1
            self._lat.append(float(latency_s))
            self._hist_request.observe(float(latency_s))

    def rejected(self) -> None:
        with self._lock:
            self.requests_rejected += 1

    def timed_out(self) -> None:
        with self._lock:
            self.requests_timeout += 1

    def canceled(self) -> None:
        with self._lock:
            self.requests_canceled += 1

    def overloaded(self) -> None:
        now = time.monotonic()
        with self._lock:
            self.requests_overload += 1
            self._overload_series.observe(self.requests_overload, t=now)

    def shed(self) -> None:
        with self._lock:
            self.requests_shed += 1

    def deadline_expired(self) -> None:
        with self._lock:
            self.requests_deadline_expired += 1

    def failed(self, rows: int = 1) -> None:
        with self._lock:
            self.requests_failed += rows

    # --- render-time reads ---

    def overload_per_second(self) -> float:
        """Sustained 429 rate over the ~10s window (0 when quiet) — a
        Series.rate query; the hand-rolled timestamp deque this used to be
        is the windowed-rate logic utils.timeseries now owns."""
        return self._overload_series.rate(RATE_WINDOW_S, now=time.monotonic())

    def tokens_per_second(self) -> float:
        """Sustained decode rate: tokens over the ~10s window divided by the
        elapsed span they actually cover (a fresh burst reads as its burst
        rate — the semantics this gauge has always had)."""
        return self._emit_series.rate(RATE_WINDOW_S, now=time.monotonic(),
                                      span="elapsed")

    @staticmethod
    def _quantile(values: List[float], q: float) -> Optional[float]:
        if not values:
            return None
        vs = sorted(values)
        idx = min(len(vs) - 1, max(0, int(round(q * (len(vs) - 1)))))
        return vs[idx]

    def snapshot(self) -> Dict[str, float]:
        """One consistent read of everything the exposition needs (plus the
        cumulative histograms as plain dicts under ``"hist"``)."""
        now = time.monotonic()
        with self._lock:
            lat = list(self._lat)
            first = list(self._first)
            itl = list(self._itl)
            out = {
                "requests_submitted": float(self.requests_submitted),
                "requests_completed": float(self.requests_completed),
                "requests_rejected": float(self.requests_rejected),
                "requests_timeout": float(self.requests_timeout),
                "requests_canceled": float(self.requests_canceled),
                "requests_failed": float(self.requests_failed),
                "requests_overload": float(self.requests_overload),
                "requests_shed": float(self.requests_shed),
                "requests_deadline_expired": float(
                    self.requests_deadline_expired),
                "tokens_emitted": float(self.tokens_emitted),
                "admission_waves": float(self.admission_waves),
                "chunks": float(self.chunks),
                "device_steps": float(self.device_steps),
                "slot_steps": float(self.slot_steps),
                "live_slot_steps": float(self.live_slot_steps),
                "dead_slot_steps": float(self.dead_slot_steps),
                "idle_slot_steps": float(self.idle_slot_steps),
                "prefill_tokens": float(self.prefill_tokens),
                "prefill_pad_tokens": float(self.prefill_pad_tokens),
                "prefill_head_positions": float(self.prefill_head_positions),
                "moe_assignments": float(self.moe_assignments),
                "moe_experts_touched": float(self.moe_experts_touched),
                "moe_assignments_zero": float(self.moe_assignments_zero),
                "moe_assignments_absent": float(self.moe_assignments_absent),
                "hc_positions": float(self.hc_positions_admit
                                      + self.hc_positions_step),
                "hc_positions_admit": float(self.hc_positions_admit),
                "hc_positions_step": float(self.hc_positions_step),
                "goodput_tokens": float(self.goodput_tokens),
                "wasted_tokens": float(self.wasted_tokens),
                "prefix_hits": float(self.prefix_hits),
                "prefix_tokens_saved": float(self.prefix_tokens_saved),
                "kv_read_bytes": float(self.kv_read_bytes),
                # lifetime useful fraction of raw device slot-step capacity
                "goodput_ratio": (self.live_slot_steps / self.slot_steps
                                  if self.slot_steps else 0.0),
                "fetches": float(self.fetches),
                "fetch_busy_seconds": float(self.fetch_busy_seconds),
                "fetchers_inflight": float(self.fetchers_inflight),
                "fetchers_total": float(self.fetchers_total),
                "fetcher_utilization": (
                    self.fetchers_inflight / self.fetchers_total
                    if self.fetchers_total else 0.0),
                "hol_stall_seconds": float(self.hol_stall_seconds),
                "prefill_chunks": float(self.prefill_chunks),
                "prefill_chunk_tokens": float(self.prefill_chunk_tokens),
                "compiled_programs": float(len(self._compiled)),
                "compile_wall_seconds": float(self._hist_compile.sum),
            }
            for phase, seconds in self.startup_seconds.items():
                out[f"startup_{phase}_seconds"] = seconds
            out["param_leaves_narrowed"] = float(self.param_leaves_narrowed)
            for key, name in COMPILE_PHASES.items():
                out[name] = float(self.compile_phases[key])
            compiles_per_min = self._compile_series.rate(
                COMPILE_WINDOW_S, now=now) * 60.0
            out["compiles_per_minute"] = compiles_per_min
            out["compile_storm"] = float(
                self.compile_storm_per_min > 0
                and compiles_per_min > self.compile_storm_per_min)
            if self.compiles:
                out["compiles"] = dict(self.compiles)
            if self.walks_kv_chunks:
                out["walk_chunks_live"] = float(self.walk_chunks_live)
                out["walk_chunks_grid"] = float(self.walk_chunks_grid)
                out["tile_chunks_live"] = float(self.tile_chunks_live)
                out["tile_chunks_grid"] = float(self.tile_chunks_grid)
                if self.window_layers:
                    for name in ("walk_chunks_live_window",
                                 "walk_chunks_grid_window",
                                 "tile_chunks_live_window",
                                 "tile_chunks_grid_window",
                                 "window_pages_held", "window_pages_live"):
                        out[name] = float(getattr(self, name))
            if self.walks_latents:
                for name in ("latent_walk_trips_live",
                             "latent_walk_trips_run", "latent_walk_pages"):
                    out[name] = float(getattr(self, name))
            if self.state_rows_moved:
                out["state_rows_moved"] = float(self.state_rows_moved)
                out["state_rows_live"] = float(self.state_rows_live)
            # speculative-decoding series only exist once a spec step ran:
            # dense decoders / spec-off engines keep a clean exposition
            # (absence reads as "not speculating", like the paged gauges)
            # recovery series exist only once a snapshot/audit event ran:
            # a decoder that never faulted, drained, or audited keeps a
            # clean exposition (same absence convention as the spec series)
            if (self.snapshot_saved or self.snapshot_restored
                    or self.snapshot_replayed or self.snapshot_failed):
                out["snapshot_saved"] = float(self.snapshot_saved)
                out["snapshot_restored"] = float(self.snapshot_restored)
                out["snapshot_replayed"] = float(self.snapshot_replayed)
                out["snapshot_failed"] = float(self.snapshot_failed)
            if self.pool_audit_runs:
                out["pool_audit_runs"] = float(self.pool_audit_runs)
                out["pool_audit_failures"] = float(self.pool_audit_failures)
            if self.spec_steps:
                out["spec_steps"] = float(self.spec_steps)
                out["spec_drafted_tokens"] = float(self.spec_drafted_tokens)
                out["spec_proposed_tokens"] = float(self.spec_proposed_tokens)
                out["spec_accepted_tokens"] = float(self.spec_accepted_tokens)
                out["spec_accept_rate"] = (
                    self.spec_accepted_tokens / self.spec_drafted_tokens
                    if self.spec_drafted_tokens else 0.0)
            hist = {}
            for key, h in (("first_token", self._hist_first),
                           ("request", self._hist_request),
                           ("decode_step", self._hist_decode_step),
                           ("decode_step_colocated",
                            self._hist_decode_step_coloc),
                           ("inter_token", self._hist_itl),
                           ("cold_start", self._hist_cold),
                           ("compile", self._hist_compile),
                           ("queue_wait", self._hist_queue_wait),
                           ("prefill", self._hist_prefill),
                           ("decode_active", self._hist_decode_active),
                           ("slot_idle", self._hist_slot_idle),
                           ("occupancy_ratio", self._hist_occupancy),
                           ("kv_bandwidth", self._hist_kv_bw),
                           ("spec_accept_ratio", self._hist_spec_accept),
                           ("snapshot_bytes", self._hist_snap_bytes),
                           ("snapshot_seconds", self._hist_snap_seconds)):
                if h.count:
                    hist[key] = h.snapshot()
        if hist:
            out["hist"] = hist
        out["tokens_per_second"] = self.tokens_per_second()
        out["overload_per_second"] = self.overload_per_second()
        for q, name in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99"),
                        (1.0, "max")):
            v = self._quantile(lat, q)
            if v is not None:
                out[f"latency_{name}_seconds"] = v
            v = self._quantile(first, q)
            if v is not None:
                out[f"first_token_{name}_seconds"] = v
            v = self._quantile(itl, q)
            if v is not None:
                out[f"itl_{name}_seconds"] = v
        return out
