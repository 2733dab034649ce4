"""Performance attribution: where the bytes and the seconds actually go.

The flagship bench recorded 32.8k samples/sec on-device but 14.8k end-to-end
(round 5), and until now nothing in the system could say what happens in
between — the PR-3 span tree answers "when did each phase run", not "how many
bytes/FLOPs did it move and what bandwidth did it achieve". This module is the
measurement substrate the weight-movement data-plane work needs:

* **Byte-level data-plane accounting** — every weight-movement seam
  (host->HBM staging, native weight publish/fetch, checkpoint save/restore,
  dataset reads) calls :func:`account`/:func:`record_io` with its byte count
  and, where the call blocks, its wall time. Totals render as
  ``kubeml_dataplane_bytes_total{phase}`` on the PS ``/metrics`` exposition,
  blocking transfers additionally feed a per-phase achieved-bandwidth
  histogram (``kubeml_staging_bandwidth_bytes_per_sec``).
* :class:`ProfileSession` — phase-scoped profiling: wrap the phases of a run
  (``with session.phase("stage", bytes=n):``), get a per-phase report with
  achieved bandwidth/FLOP rate and a roofline-based compute-bound vs
  transfer-bound classification (cost model: utils/roofline.py). When a
  device-trace dir is given the whole session also captures a
  TensorBoard/XProf device trace via ``jax.profiler`` (pure-Python timeline
  fallback when jax/the backend is unavailable).
* :class:`FlightRecorder` — an always-on bounded ring of recent spans and
  data-plane events plus counter snapshots. ``dump()`` writes a postmortem
  JSON (ring tail + counters) on errorhook/watchdog trips so chaos and
  overload events leave evidence behind (``KUBEML_FLIGHT_DIR`` gates the
  disk dump; the errorhook payload carries the tail either way).
* Span-tree attribution — :func:`attribution_report` folds byte/FLOP span
  attributes (collected across processes by ps/traces.py) into a per-phase
  byte/FLOP/bandwidth table, and :func:`perfetto_with_counters` exports the
  merged trace WITH Perfetto counter tracks (cumulative data-plane bytes,
  per-span bandwidth) — the ``kubeml profile <task-id>`` report.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional

from .tracing import (add_span_sink, current_context, current_task,
                      get_tracer, merge_chrome_trace)

# achieved-bandwidth histogram edges (bytes/sec): spans a ~10 KB/s trickle
# through multi-GB/s HBM-adjacent paths; +Inf implicit
BANDWIDTH_BUCKETS = (1e4, 1e5, 1e6, 4e6, 1.6e7, 6.4e7, 2.56e8, 1e9,
                     4e9, 1.6e10, 6.4e10, 2.56e11)

# phase-label cardinality bound (phases are a small fixed vocabulary; the cap
# is a guard against a caller interpolating ids into phase names)
MAX_PHASES = 64

_lock = threading.Lock()
# {phase: {"bytes": float, "seconds": float, "events": int}}
_phases: Dict[str, Dict[str, float]] = {}
# {phase: Histogram of achieved bytes/sec for BLOCKING transfers}
_bw_hists: Dict[str, Any] = {}
# {phase: retry count} — transfers that had to be re-attempted (e.g. a
# torn seqlock weight fetch); the wasted bytes land on their own phase
# (weights.fetch_torn) so the attribution report can't under-count the
# channel, and this counter says how often
_retries: Dict[str, int] = {}


def account(phase: str, nbytes: float, seconds: Optional[float] = None) -> None:
    """Record one data-plane event: ``nbytes`` moved in ``seconds`` (None =
    the call did not block, e.g. an async device_put dispatch — bytes count,
    no bandwidth observation). O(1), never raises on the hot path."""
    from ..ps.metrics import Histogram

    nbytes = float(nbytes)
    with _lock:
        agg = _phases.get(phase)
        if agg is None:
            if len(_phases) >= MAX_PHASES:
                _phases.pop(next(iter(_phases)))
            agg = _phases[phase] = {"bytes": 0.0, "seconds": 0.0, "events": 0}
        agg["bytes"] += nbytes
        agg["events"] += 1
        if seconds is not None and seconds > 0:
            agg["seconds"] += float(seconds)
            if nbytes > 0:
                h = _bw_hists.get(phase)
                if h is None:
                    if len(_bw_hists) >= MAX_PHASES:
                        _bw_hists.pop(next(iter(_bw_hists)))
                    h = _bw_hists[phase] = Histogram(BANDWIDTH_BUCKETS)
                h.observe(nbytes / seconds)
    get_recorder().note({
        "kind": "dataplane", "phase": phase, "bytes": nbytes,
        "seconds": seconds,
    })


def record_io(phase: str, nbytes: float, seconds: float,
              flops: Optional[float] = None, **attrs: Any) -> None:
    """``account`` plus a byte-carrying span in the distributed trace (when
    tracing is on) — the one call a blocking weight-movement seam makes so
    its bytes show up in BOTH the counters and the span tree."""
    account(phase, nbytes, seconds)
    tracer = get_tracer()
    if tracer.enabled:
        span_attrs = dict(attrs)
        span_attrs["bytes"] = int(nbytes)
        if flops:
            span_attrs["flops"] = float(flops)
        if seconds and seconds > 0 and nbytes > 0:
            span_attrs["bandwidth_bps"] = nbytes / seconds
        tracer.record(phase, max(float(seconds or 0.0), 0.0), **span_attrs)


def record_retry(phase: str) -> None:
    """Count one retried data-plane transfer on ``phase`` (rendered as
    ``kubeml_dataplane_retries_total``). O(1), never raises."""
    with _lock:
        if phase not in _retries and len(_retries) >= MAX_PHASES:
            _retries.pop(next(iter(_retries)))
        _retries[phase] = _retries.get(phase, 0) + 1


def counters_snapshot() -> Dict[str, Any]:
    """Plain-data snapshot of the data-plane accounting (per-phase byte/
    second/event totals + bandwidth histogram snapshots) — posted with a
    task's spans to the PS collector and embedded in flight-recorder dumps.

    Scope: PROCESS LIFETIME, not per task — a long-lived control plane's
    snapshot includes every prior task's traffic (and a standalone runner's
    is per-job only because the process is). The snapshot says so
    explicitly; per-TASK byte budgets come from the span attributes, which
    are task-scoped by construction."""
    with _lock:
        out = {
            "scope": "process-lifetime",
            "pid": os.getpid(),
            "dataplane": {p: dict(agg) for p, agg in _phases.items()},
            "bandwidth": {p: h.snapshot() for p, h in _bw_hists.items()},
            "retries": dict(_retries),
        }
    return out


def merge_counters(phases: Dict[str, Dict[str, float]]) -> None:
    """Fold per-phase counter DELTAS from another process into this
    registry. The runner->PS epoch metric push uses this: a standalone job
    runner has no scraped ``/metrics`` route, so its dataplane counters
    (``weights.encode.*`` and friends) would otherwise never reach the one
    exposition Prometheus scrapes. Bandwidth histograms stay per-process
    (deltas of bucket vectors are not carried on the push)."""
    for phase, d in phases.items():
        if not isinstance(d, dict):
            continue
        with _lock:
            agg = _phases.get(phase)
            if agg is None:
                if len(_phases) >= MAX_PHASES:
                    _phases.pop(next(iter(_phases)))
                agg = _phases[phase] = {"bytes": 0.0, "seconds": 0.0,
                                        "events": 0}
            agg["bytes"] += max(float(d.get("bytes", 0.0)), 0.0)
            agg["seconds"] += max(float(d.get("seconds", 0.0)), 0.0)
            agg["events"] += max(int(d.get("events", 0)), 0)


def reset_accounting() -> None:
    """Test hook: clear the process-wide data-plane accounting."""
    with _lock:
        _phases.clear()
        _bw_hists.clear()
        _retries.clear()


def render_metrics() -> List[str]:
    """Prometheus exposition lines for the data-plane series (appended to the
    PS ``/metrics`` render next to the resilience counters)."""
    from ..ps.metrics import Histogram, escape_label_value

    with _lock:
        phases = {p: dict(agg) for p, agg in _phases.items()}
        hists = {p: h.snapshot() for p, h in _bw_hists.items()}
        retries = dict(_retries)
    lines = [
        "# HELP kubeml_dataplane_bytes_total Bytes moved per data-plane phase",
        "# TYPE kubeml_dataplane_bytes_total counter",
    ]
    for p, agg in sorted(phases.items()):
        lines.append(f'kubeml_dataplane_bytes_total{{phase="'
                     f'{escape_label_value(p)}"}} {agg["bytes"]:g}')
    lines.append("# HELP kubeml_dataplane_seconds_total Blocking wall seconds "
                 "per data-plane phase")
    lines.append("# TYPE kubeml_dataplane_seconds_total counter")
    for p, agg in sorted(phases.items()):
        lines.append(f'kubeml_dataplane_seconds_total{{phase="'
                     f'{escape_label_value(p)}"}} {agg["seconds"]:g}')
    lines.append("# HELP kubeml_dataplane_events_total Data-plane transfer "
                 "events per phase")
    lines.append("# TYPE kubeml_dataplane_events_total counter")
    for p, agg in sorted(phases.items()):
        lines.append(f'kubeml_dataplane_events_total{{phase="'
                     f'{escape_label_value(p)}"}} {agg["events"]:d}')
    if retries:
        lines.append("# HELP kubeml_dataplane_retries_total Re-attempted "
                     "data-plane transfers per phase (e.g. torn weight "
                     "fetches)")
        lines.append("# TYPE kubeml_dataplane_retries_total counter")
        for p, n in sorted(retries.items()):
            lines.append(f'kubeml_dataplane_retries_total{{phase="'
                         f'{escape_label_value(p)}"}} {n:d}')
    lines.append("# HELP kubeml_staging_bandwidth_bytes_per_sec Achieved "
                 "bandwidth of blocking data-plane transfers")
    lines.append("# TYPE kubeml_staging_bandwidth_bytes_per_sec histogram")
    for p, snap in sorted(hists.items()):
        lines.extend(Histogram.render_snapshot(
            "kubeml_staging_bandwidth_bytes_per_sec", snap, "phase", p))
    return lines


# --- roofline classification (cost model: utils/roofline.py) ---


def classify(nbytes: float, flops: float) -> str:
    """Which roofline term dominates a phase: ``compute-bound`` when the
    FLOP time at chip peak exceeds the byte time at HBM bandwidth,
    ``transfer-bound`` when the bytes dominate, ``host`` when the phase
    moved no bytes and ran no FLOPs (control/bookkeeping), ``unknown`` when
    both terms are nonzero and the device's peaks are not in the table
    (utils/roofline.py — e.g. a CPU box): a roofline needs a machine."""
    if not nbytes and not flops:
        return "host"
    if not flops:
        return "transfer-bound"
    if not nbytes:
        return "compute-bound"
    from .roofline import hbm_bandwidth, peak_flops

    try:
        peak, bw = peak_flops(), hbm_bandwidth()
    except RuntimeError:
        # no backend for this process (`kubeml profile` beside a cluster
        # that holds the chip): no machine to ask
        peak, bw = None, None
    if not peak or not bw:
        return "unknown"
    return ("compute-bound" if flops / peak >= nbytes / bw
            else "transfer-bound")


# --- flight recorder ---


class FlightRecorder:
    """Bounded ring of recent spans + data-plane events for postmortems.

    Always on (capacity from ``KUBEML_FLIGHT_RECORDER``, default 256;
    0 disables), fed by the tracer's span sink and :func:`account`.
    ``dump()`` writes the ring tail plus a counter snapshot to
    ``KUBEML_FLIGHT_DIR`` (no disk write when unset — the errorhook payload
    still carries :meth:`tail` either way)."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("KUBEML_FLIGHT_RECORDER", "256"))
            except ValueError:
                capacity = 256
        self.capacity = max(0, int(capacity))
        self._ring: "deque[dict]" = deque(maxlen=self.capacity or 1)
        self._lock = threading.Lock()

    def note(self, event: dict) -> None:
        if self.capacity <= 0:
            return
        e = dict(event)
        e.setdefault("t", time.time())
        ctx = current_context()
        if ctx is not None:
            e.setdefault("trace_id", ctx.trace_id)
        task = current_task()
        if task is not None:
            e.setdefault("task_id", task)
        with self._lock:
            self._ring.append(e)

    def record_span(self, span) -> None:
        """Tracer sink: finished spans enter the ring as compact records."""
        if self.capacity <= 0:
            return
        e = {
            "kind": "span", "t": span.start, "name": span.name,
            "duration": span.duration, "trace_id": span.trace_id,
            "service": span.service,
        }
        for k in ("job", "bytes", "flops", "epoch", "round"):
            if k in span.attrs:
                e[k] = span.attrs[k]
        with self._lock:
            self._ring.append(e)

    def tail(self, n: int = 64) -> List[dict]:
        with self._lock:
            items = list(self._ring)
        return items[-n:]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def dump(self, reason: str,
             out_dir: Optional[Path] = None) -> Optional[Path]:
        """Write the postmortem record. ``out_dir`` falls back to
        ``KUBEML_FLIGHT_DIR``; None/unset means no disk write (returns None).
        Never raises — this runs on failure paths."""
        if out_dir is None:
            env = os.environ.get("KUBEML_FLIGHT_DIR", "")
            if not env:
                return None
            out_dir = Path(env)
        try:
            from . import resilience

            record = {
                "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                "pid": os.getpid(),
                "reason": str(reason),
                "task_id": current_task(),
                "events": self.tail(self.capacity or 1),
                "counters": counters_snapshot(),
                "http_counters": {
                    f"{m}{{{lv}}}": v for (m, lv), v in
                    resilience.counters_snapshot().items()
                },
            }
            ctx = current_context()
            if ctx is not None:
                record["trace_id"] = ctx.trace_id
            out_dir = Path(out_dir)
            out_dir.mkdir(parents=True, exist_ok=True)
            path = out_dir / f"flight-{os.getpid()}-{int(time.time())}.json"
            path.write_text(json.dumps(record, default=str))
            return path
        except Exception:
            return None


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def get_recorder() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
                add_span_sink(_recorder.record_span)
    return _recorder


# --- phase-scoped profiling sessions ---


class _Phase:
    """Mutable handle yielded by :meth:`ProfileSession.phase` — a seam can
    add bytes/FLOPs discovered mid-phase (``ph.bytes += n``)."""

    __slots__ = ("name", "bytes", "flops", "attrs", "seconds")

    def __init__(self, name: str, nbytes: float, flops: float, attrs: dict):
        self.name = name
        self.bytes = float(nbytes)
        self.flops = float(flops)
        self.attrs = attrs
        self.seconds = 0.0


class ProfileSession:
    """One profiled run: named phases with byte/FLOP attribution.

    ``device_trace_dir`` additionally captures a TensorBoard/XProf device
    trace of everything inside the session via ``jax.profiler`` — silently
    skipped when jax/the profiler backend is unavailable (the pure-Python
    phase timeline is the fallback and always recorded)."""

    def __init__(self, name: str, device_trace_dir: Optional[Path] = None):
        self.name = name
        self.device_trace_dir = (Path(device_trace_dir)
                                 if device_trace_dir else None)
        self._phases: List[_Phase] = []
        self._lock = threading.Lock()
        self._device_trace = None
        self.device_trace_error: Optional[str] = None

    # -- session scope (device trace) --

    def __enter__(self) -> "ProfileSession":
        if self.device_trace_dir is not None:
            try:
                import jax

                self.device_trace_dir.mkdir(parents=True, exist_ok=True)
                self._device_trace = jax.profiler.trace(
                    str(self.device_trace_dir))
                self._device_trace.__enter__()
            except Exception as e:  # CPU-only box / profiler backend absent
                self._device_trace = None
                self.device_trace_error = str(e)
        return self

    def __exit__(self, *exc) -> None:
        if self._device_trace is not None:
            try:
                self._device_trace.__exit__(*exc)
            except Exception as e:
                self.device_trace_error = str(e)
            self._device_trace = None

    # -- phases --

    @contextmanager
    def phase(self, name: str, nbytes: float = 0.0, flops: float = 0.0,
              **attrs: Any) -> Iterator[_Phase]:
        # `bytes=`/`flops=` kwargs are accepted as aliases of the positional
        # params (the natural spelling at call sites); they must never be
        # silently swallowed into span attrs as inert decoration
        nbytes = float(attrs.pop("bytes", nbytes))
        flops = float(attrs.pop("flops", flops))
        ph = _Phase(name, nbytes, flops, attrs)
        tracer = get_tracer()
        t0 = time.perf_counter()
        try:
            if tracer.enabled:
                with tracer.span(f"{self.name}.{name}", **attrs) as span:
                    try:
                        yield ph
                    finally:
                        # stamp the (possibly phase-mutated) byte/FLOP
                        # totals onto the span BEFORE the tracer appends it,
                        # so collected span trees carry the attribution
                        if span is not None:
                            if ph.bytes:
                                span.attrs["bytes"] = ph.bytes
                            if ph.flops:
                                span.attrs["flops"] = ph.flops
            else:
                yield ph
        finally:
            ph.seconds = time.perf_counter() - t0
            with self._lock:
                self._phases.append(ph)

    def note_phase(self, name: str, seconds: float, nbytes: float = 0.0,
                   flops: float = 0.0, **attrs: Any) -> None:
        """Record an externally-timed phase (e.g. a benchmark loop whose wall
        time was already measured)."""
        ph = _Phase(name, nbytes, flops, attrs)
        ph.seconds = float(seconds)
        with self._lock:
            self._phases.append(ph)

    # -- reporting --

    def report(self) -> Dict[str, Any]:
        """Per-phase attribution: wall seconds, bytes, FLOPs, achieved
        bandwidth/FLOP rate, share of session wall time, and the roofline
        compute-vs-transfer classification."""
        with self._lock:
            phases = list(self._phases)
        agg: Dict[str, Dict[str, float]] = {}
        for ph in phases:
            a = agg.setdefault(ph.name, {"seconds": 0.0, "bytes": 0.0,
                                         "flops": 0.0, "count": 0})
            a["seconds"] += ph.seconds
            a["bytes"] += ph.bytes
            a["flops"] += ph.flops
            a["count"] += 1
        total_s = sum(a["seconds"] for a in agg.values()) or 1.0
        rows = _phase_rows(agg, total_s=total_s)
        out = {"session": self.name, "total_seconds": total_s, "phases": rows}
        if self.device_trace_dir is not None:
            out["device_trace_dir"] = str(self.device_trace_dir)
            if self.device_trace_error:
                out["device_trace_error"] = self.device_trace_error
        return out

    def dump(self, path: Path, **extra: Any) -> Path:
        """Append the report (one JSON line) to ``path``; ``extra`` fields
        merge into the row."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        row = self.report()
        row.update(extra)
        row["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with path.open("a") as f:
            f.write(json.dumps(row) + "\n")
        return path


# --- span-tree attribution (the `kubeml profile` report) ---


def _phase_rows(agg: Dict[str, Dict[str, float]],
                total_s: Optional[float] = None) -> List[dict]:
    """Attribution rows from {phase: {seconds, bytes, flops, count}} — the
    one row shape ProfileSession.report and attribution_report share."""
    rows = []
    for name, a in agg.items():
        row = {
            "phase": name,
            "count": int(a["count"]),
            "seconds": a["seconds"],
            "bytes": a["bytes"],
            "flops": a["flops"],
            "bound": classify(a["bytes"], a["flops"]),
        }
        if total_s:
            row["share"] = a["seconds"] / total_s
        if a["seconds"] > 0:
            if a["bytes"]:
                row["bandwidth_bps"] = a["bytes"] / a["seconds"]
            if a["flops"]:
                row["flops_per_sec"] = a["flops"] / a["seconds"]
        rows.append(row)
    rows.sort(key=lambda r: -r["seconds"])
    return rows


def attribution_report(span_dicts: List[dict],
                       counters: Optional[dict] = None) -> Dict[str, Any]:
    """Fold a task's span dicts (ps/traces.py collection) into a per-phase
    byte/FLOP attribution table. Spans aggregate by name; byte/FLOP span
    attributes (``record_io``, job.round slabs) feed totals, and each phase
    classifies compute-bound vs transfer-bound via the roofline cost model.
    ``counters`` is the per-service counter collection stored next to the
    spans — PROCESS-LIFETIME scope (each snapshot is tagged so): in a
    long-lived control plane they include earlier tasks' traffic, so they
    are context, not a per-task budget; the per-phase rows above, built
    from the task-scoped spans, are the per-task numbers."""
    agg: Dict[str, Dict[str, float]] = {}
    for d in span_dicts:
        if not isinstance(d, dict):
            continue
        name = d.get("name") or "?"
        attrs = d.get("attrs") or {}
        a = agg.setdefault(name, {"seconds": 0.0, "bytes": 0.0,
                                  "flops": 0.0, "count": 0})
        a["seconds"] += float(d.get("duration") or 0.0)
        a["count"] += 1
        for key, field in (("bytes", "bytes"), ("flops", "flops")):
            try:
                a[field] += float(attrs.get(key) or 0.0)
            except (TypeError, ValueError):
                pass
    rows = _phase_rows(agg)
    out: Dict[str, Any] = {
        "phases": rows,
        "total_bytes": sum(r["bytes"] for r in rows),
        "total_flops": sum(r["flops"] for r in rows),
        "span_count": len(span_dicts),
    }
    if counters:
        out["counters"] = counters
    return out


def perfetto_with_counters(span_dicts: List[dict]) -> Dict[str, Any]:
    """The merged Chrome/Perfetto trace (tracing.merge_chrome_trace) PLUS
    counter tracks: cumulative data-plane bytes over time and per-span
    achieved bandwidth, from the spans' byte attributes — load in
    https://ui.perfetto.dev and the counter tracks render under a dedicated
    ``dataplane`` process row."""
    trace = merge_chrome_trace(span_dicts)
    events = trace["traceEvents"]
    counter_pid = max((e.get("pid", 0) for e in events
                       if isinstance(e.get("pid"), int)), default=0) + 1
    byte_spans = []
    for d in span_dicts:
        if not isinstance(d, dict):
            continue
        attrs = d.get("attrs") or {}
        try:
            nbytes = float(attrs.get("bytes") or 0.0)
        except (TypeError, ValueError):
            continue
        if nbytes <= 0:
            continue
        start = float(d.get("start") or 0.0)
        dur = float(d.get("duration") or 0.0)
        byte_spans.append((start, dur, nbytes, d.get("service") or "?"))
    if not byte_spans:
        return trace
    events.append({"ph": "M", "name": "process_name", "pid": counter_pid,
                   "args": {"name": "dataplane"}})
    # cumulative track: a transfer's bytes land when it COMPLETES, so order
    # by end time — ordering by start would make the "cumulative" counter
    # decrease wherever byte spans overlap (concurrent processes do overlap
    # in a merged trace)
    cumulative = 0.0
    for start, dur, nbytes, _svc in sorted(
            byte_spans, key=lambda b: b[0] + b[1]):
        cumulative += nbytes
        events.append({"ph": "C", "name": "dataplane_bytes_total",
                       "pid": counter_pid, "ts": (start + dur) * 1e6,
                       "args": {"bytes": cumulative}})
    # bandwidth: one track PER SERVICE so a transfer finishing in one
    # process can't zero the rate of another still mid-flight
    for start, dur, nbytes, svc in byte_spans:
        if dur <= 0:
            continue
        name = f"transfer_bandwidth_MBps/{svc}"
        mbps = nbytes / dur / 1e6
        events.append({"ph": "C", "name": name, "pid": counter_pid,
                       "ts": start * 1e6, "args": {"MBps": mbps}})
        events.append({"ph": "C", "name": name, "pid": counter_pid,
                       "ts": (start + dur) * 1e6, "args": {"MBps": 0.0}})
    return trace
