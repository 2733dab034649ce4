"""Distributed span tracing + device profiling.

The reference has no tracing or profiling at all — only zap log lines with
ad-hoc timings (SURVEY §5: merge time ml/pkg/train/job.go:397-412, epoch
ElapsedTime job.go:321-322). This subsystem is the TPU-native upgrade:

* :class:`Tracer` — thread-safe in-memory span recorder with ~zero overhead
  when disabled; spans nest via a context manager and carry attributes
  (job id, epoch, round, parallelism...). Export as Chrome trace-event JSON
  (load in chrome://tracing / Perfetto) or per-name summary statistics.
* **Trace identity** (Dapper-style): every span carries ``trace_id`` /
  ``span_id`` / ``parent_id``. The identity crosses process boundaries as a
  W3C ``traceparent`` header (:func:`parse_traceparent` /
  :meth:`TraceContext.traceparent`): the HTTP server (utils.httpd) binds the
  inbound context to the handler thread, outbound hops
  (utils.traced_http) stamp the current context onto the request — so a
  train request's spans stitch into one tree across CLI → controller →
  scheduler → PS → job runner.
* **One clock**: every span is stamped with :meth:`Tracer.now`, a wall anchor
  taken once at import plus ``time.monotonic()``. Spans of one process never
  step backwards with the wall clock, and a timestamp the program took with
  ``time.monotonic()`` lands on the same clock through :meth:`Tracer.at`.
* :func:`device_profile` — ``jax.profiler.trace`` with the tracer on, a
  ``kubeml.clock_tie`` annotation that maps the profiler's clock onto the
  tracer's, and the block's spans written beside the profiler's files.

The process-wide tracer is enabled with ``KUBEML_TRACE=<dir>`` (spans are
flushed to ``<dir>/kubeml-trace-<pid>.json`` at exit or on ``flush()``), or
programmatically via ``get_tracer().enable(...)``.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import re
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional

log = logging.getLogger("kubeml.trace")

# the tracer's clock: wall time at import less the monotonic clock then, so
# that ``_WALL_ANCHOR + time.monotonic()`` is a wall clock that only moves
# forwards (the serving engine names millisecond gaps with it)
_WALL_ANCHOR = time.time() - time.monotonic()

# the annotation (and span) ``device_profile`` writes first, so a reader can
# put the profiler's clock and the tracer's side by side
CLOCK_TIE = "kubeml.clock_tie"

# hard cap: a runaway loop must not eat the host's RAM. The cap is a RING —
# past it the OLDEST span evicts — so a long-lived traced service (weeks of
# server spans) still records every NEW task's trace instead of silently
# going dark once the buffer fills.
MAX_SPANS = 200_000


# --- trace identity (W3C trace-context) ---

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclass(frozen=True)
class TraceContext:
    """The propagated part of a span: who the next span's parent is."""

    trace_id: str  # 32 lowercase hex chars
    span_id: str  # 16 lowercase hex chars

    def traceparent(self) -> str:
        """W3C ``traceparent`` header value (version 00, sampled)."""
        return f"00-{self.trace_id}-{self.span_id}-01"


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
    """Decode a W3C ``traceparent`` header; None on absent/malformed input
    (a bad peer header must never fail the request it rode in on)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None:
        return None
    version, trace_id, span_id = m.group(1), m.group(2), m.group(3)
    # per spec: version ff is invalid, all-zero ids are invalid
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id=trace_id, span_id=span_id)


# Thread-local context stack. Deliberately independent of Tracer.enabled: a
# process with tracing off must still FORWARD the inbound context unchanged
# (e.g. a controller with KUBEML_TRACE unset between a traced CLI and a
# traced worker), so binding always works and only span *recording* is gated.
_tls = threading.local()


def _ctx_stack() -> list:
    s = getattr(_tls, "ctx", None)
    if s is None:
        s = _tls.ctx = []
    return s


def current_context() -> Optional[TraceContext]:
    """The trace context of this thread (innermost active span, or the
    inbound context bound by the HTTP server / a job thread)."""
    s = _ctx_stack()
    return s[-1] if s else None


@contextmanager
def use_context(ctx: Optional[TraceContext]) -> Iterator[None]:
    """Bind an externally-received trace context to this thread for the
    duration of the block (no span is recorded). None is a no-op."""
    if ctx is None:
        yield
        return
    s = _ctx_stack()
    s.append(ctx)
    try:
        yield
    finally:
        s.pop()


def trace_headers(extra: Optional[dict] = None) -> dict:
    """HTTP headers for an outbound hop: caller's headers plus the current
    ``traceparent`` (when a context is bound). Shared by utils.traced_http."""
    headers = dict(extra or {})
    ctx = current_context()
    if ctx is not None:
        headers.setdefault("traceparent", ctx.traceparent())
    return headers


# --- task binding (log/webhook correlation, satellite of the trace tree) ---


def _task_stack() -> list:
    s = getattr(_tls, "task", None)
    if s is None:
        s = _tls.task = []
    return s


def current_task() -> Optional[str]:
    s = _task_stack()
    return s[-1] if s else None


@contextmanager
def bind_task(task_id: Optional[str]) -> Iterator[None]:
    """Associate a task/job id with this thread (job threads bind it so log
    records and error-webhook payloads correlate with traces)."""
    if not task_id:
        yield
        return
    s = _task_stack()
    s.append(task_id)
    try:
        yield
    finally:
        s.pop()


class TraceLogFilter(logging.Filter):
    """Injects ``trace_id`` and ``task_id`` into every log record (from the
    thread's bound trace context / task), so a format string can carry
    ``%(trace_id)s``/``%(task_id)s`` and log lines correlate with traces."""

    def filter(self, record: logging.LogRecord) -> bool:
        ctx = current_context()
        record.trace_id = ctx.trace_id if ctx is not None else "-"
        record.task_id = current_task() or "-"
        return True


def add_log_context(logger: Optional[logging.Logger] = None) -> None:
    """Attach :class:`TraceLogFilter` to every handler of ``logger`` (root by
    default). Idempotent — safe to call at each service boot."""
    logger = logger or logging.getLogger()
    for handler in logger.handlers:
        if not any(isinstance(f, TraceLogFilter) for f in handler.filters):
            handler.addFilter(TraceLogFilter())


@dataclass
class Span:
    name: str
    start: float  # Tracer.now() seconds (wall, monotonic-derived)
    duration: float  # seconds
    thread: int
    attrs: Dict[str, Any] = field(default_factory=dict)
    # trace identity: spans across processes sharing a trace_id stitch into
    # one tree via parent_id links
    trace_id: str = ""
    span_id: str = ""
    parent_id: Optional[str] = None
    # logical process ("controller", "ps", "worker", ...): the merged
    # Chrome trace renders one process row per service
    service: str = ""

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "start": self.start,
            "duration": self.duration,
            "thread": self.thread,
            "attrs": {k: _json_safe(v) for k, v in self.attrs.items()},
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "service": self.service,
            "pid": os.getpid(),
        }


# finished-span observers (the flight recorder, utils.profiler): called with
# each Span AFTER it is appended to the buffer. A sink must be cheap and must
# never raise into the traced code path.
_span_sinks: List = []


def add_span_sink(fn) -> None:
    """Register a finished-span observer (idempotent per function object)."""
    if fn not in _span_sinks:
        _span_sinks.append(fn)


class Tracer:
    """Span recorder. Disabled by default: ``span()`` costs one attribute read."""

    def __init__(self, enabled: bool = False, out_dir: Optional[Path] = None,
                 service: Optional[str] = None):
        self.enabled = enabled
        self.out_dir = Path(out_dir) if out_dir else None
        # default logical-process label for spans that don't name one
        self.service = service or f"proc-{os.getpid()}"
        self._spans: "deque[Span]" = deque()
        self._lock = threading.Lock()
        self._dropped = 0

    # --- control ---

    def enable(self, out_dir: Optional[Path] = None) -> "Tracer":
        self.enabled = True
        if out_dir is not None:
            self.out_dir = Path(out_dir)
        return self

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    @property
    def dropped(self) -> int:
        """Oldest spans evicted past the MAX_SPANS cap since the last clear()."""
        with self._lock:
            return self._dropped

    # --- the clock ---

    @staticmethod
    def now() -> float:
        """The tracer's clock: wall seconds that never step backwards."""
        return _WALL_ANCHOR + time.monotonic()

    @staticmethod
    def at(monotonic: float) -> float:
        """A ``time.monotonic()`` reading of this process on the tracer's
        clock."""
        return _WALL_ANCHOR + monotonic

    # --- recording ---

    def _append(self, s: Span) -> None:
        with self._lock:
            self._spans.append(s)
            while len(self._spans) > MAX_SPANS:
                self._spans.popleft()
                self._dropped += 1
        for sink in _span_sinks:
            try:
                sink(s)
            except Exception:  # a broken observer must not fail traced code
                pass

    def _identify(self, attrs: Dict[str, Any]) -> Span:
        """A new Span skeleton carrying trace identity: child of the thread's
        current context, or a fresh trace root."""
        service = attrs.pop("service", None) or self.service
        parent = current_context()
        return Span(
            name="", start=0.0, duration=0.0, thread=threading.get_ident(),
            attrs=attrs,
            trace_id=parent.trace_id if parent is not None else new_trace_id(),
            span_id=new_span_id(),
            parent_id=parent.span_id if parent is not None else None,
            service=service,
        )

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Optional[Span]]:
        if not self.enabled:
            yield None
            return
        s = self._identify(attrs)
        s.name = name
        s.start = self.now()
        stack = _ctx_stack()
        stack.append(TraceContext(s.trace_id, s.span_id))
        try:
            yield s
        finally:
            stack.pop()
            s.duration = self.now() - s.start
            self._append(s)

    def record(self, name: str, duration: float, **attrs: Any) -> None:
        """Record an externally-timed span (e.g. a device-side duration)."""
        if not self.enabled:
            return
        s = self._identify(attrs)
        s.name = name
        s.start = self.now() - duration
        s.duration = duration
        self._append(s)

    def add_span(self, name: str, start: float, duration: float, *,
                 trace_id: Optional[str] = None,
                 span_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 service: Optional[str] = None,
                 thread: Optional[int] = None,
                 **attrs: Any) -> Optional[Span]:
        """Record a fully-explicit span: start (on :meth:`now`'s clock),
        duration, and (when given) explicit trace identity. The serving
        batcher reconstructs a request's phase timeline AFTER the fact — at
        completion, on the engine thread, where no context manager ever
        wrapped the phases — so it needs to name the parent/ids itself, and
        ``thread`` where the work ran on another thread than the one that
        records it. Returns the Span (None when disabled) so callers can
        hang children off its ``span_id``."""
        if not self.enabled:
            return None
        s = Span(
            name=name, start=float(start), duration=max(0.0, float(duration)),
            thread=thread or threading.get_ident(), attrs=attrs,
            trace_id=trace_id or new_trace_id(),
            span_id=span_id or new_span_id(),
            parent_id=parent_id, service=service or self.service,
        )
        self._append(s)
        return s

    # --- reading ---

    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def task_spans(self, task_id: str) -> List[Span]:
        """Every span belonging to a task: spans tagged ``job=task_id`` plus
        every other span sharing one of those spans' trace ids (the HTTP hop
        spans of the same request flow), plus the serving engine's spans of
        the program that admitted it (``requests`` lists the ids a batched
        admit program served, comma-separated)."""
        spans = self.spans()
        trace_ids = {s.trace_id for s in spans
                     if s.trace_id and s.attrs.get("job") == task_id}
        return [s for s in spans
                if s.attrs.get("job") == task_id
                or (s.trace_id and s.trace_id in trace_ids)
                or ("requests" in s.attrs
                    and task_id in s.attrs["requests"].split(","))]

    def task_dicts(self, task_id: str) -> List[Dict[str, Any]]:
        return [s.to_dict() for s in self.task_spans(task_id)]

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name {count, total_s, mean_s, max_s}."""
        agg: Dict[str, List[float]] = {}
        for s in self.spans():
            agg.setdefault(s.name, []).append(s.duration)
        return {
            name: {
                "count": len(ds),
                "total_s": sum(ds),
                "mean_s": sum(ds) / len(ds),
                "max_s": max(ds),
            }
            for name, ds in sorted(agg.items())
        }

    # --- export ---

    def flush(self, path: Optional[Path] = None) -> Optional[Path]:
        """Write the Chrome trace JSON; returns the path (None if nothing to do)."""
        if path is None:
            if self.out_dir is None:
                return None
            path = self.out_dir / f"kubeml-trace-{os.getpid()}.json"
        spans = self.spans()
        if not spans:
            return None
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(
            merge_chrome_trace([s.to_dict() for s in spans])))
        if self._dropped:
            log.warning("trace evicted %d oldest spans past the %d cap",
                        self._dropped, MAX_SPANS)
        return path


def merge_chrome_trace(span_dicts: List[Dict[str, Any]]) -> Dict[str, Any]:
    """One Chrome/Perfetto trace spanning processes: span dicts (Span.to_dict,
    possibly collected over HTTP from several processes) grouped into one
    process row per ``service`` label, trace identity preserved in args."""
    procs: Dict[str, int] = {}
    events: List[Dict[str, Any]] = []
    for d in span_dicts:
        key = d.get("service") or f"pid-{d.get('pid', 0)}"
        if key not in procs:
            procs[key] = len(procs) + 1
            events.append({"ph": "M", "name": "process_name", "pid": procs[key],
                           "args": {"name": key}})
    for d in span_dicts:
        key = d.get("service") or f"pid-{d.get('pid', 0)}"
        args = dict(d.get("attrs") or {})
        for k in ("trace_id", "span_id", "parent_id"):
            if d.get(k):
                args[k] = d[k]
        events.append({
            "name": d.get("name", ""),
            "ph": "X",
            "ts": float(d.get("start", 0.0)) * 1e6,
            "dur": float(d.get("duration", 0.0)) * 1e6,
            "pid": procs[key],
            "tid": int(d.get("thread", 0)) % (1 << 31),
            "args": args,
        })
    return {"traceEvents": events}


def post_task_spans(ps_url: str, task_id: str,
                    tracer: Optional["Tracer"] = None) -> bool:
    """POST this process's finished spans for a task to the PS span collector
    (``/traces/{task_id}``). Fire-at-exit path for job runners / workers;
    never raises. Returns whether anything was delivered.

    The payload also carries this process's data-plane counter snapshot
    (utils.profiler) keyed by the tracer's service label, so the
    ``kubeml profile`` report sees every process's byte budget even where
    individual spans carry no byte attributes."""
    tracer = tracer or get_tracer()
    if not tracer.enabled:
        return False
    spans = tracer.task_dicts(task_id)
    if not spans:
        return False
    try:
        from . import traced_http

        payload = {"spans": spans}
        try:
            from . import profiler

            payload["counters"] = profiler.counters_snapshot()
            payload["service"] = tracer.service
        except Exception:
            pass
        traced_http.post(f"{ps_url}/traces/{task_id}",
                         json=payload, timeout=10)
        return True
    except Exception:
        log.debug("posting %d spans for %s failed", len(spans), task_id,
                  exc_info=True)
        return False


def _json_safe(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


# --- process-wide tracer ---

_global = Tracer()
_atexit_armed = False


def get_tracer() -> Tracer:
    global _atexit_armed
    env_dir = os.environ.get("KUBEML_TRACE")
    if env_dir and not _global.enabled:
        _global.enable(Path(env_dir))
        if not _atexit_armed:
            _atexit_armed = True
            atexit.register(_global.flush)
    return _global


# --- the compile clock (jax.monitoring) ---

# the events jax reports around the three phases of a first call, each with
# its start and its end
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
# the persistent cache's events; jax counts a "miss" where it WRITES an entry
_COMPILE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_ZERO_PHASES = dict.fromkeys(_COMPILE_PHASES.values(), 0.0)
_ZERO_COUNTS = {"programs": 0, **dict.fromkeys(_COMPILE_COUNTS.values(), 0)}
# a thread keeps the ends of its phase intervals to find those a later, outer
# interval contains, for as long as an outer one can still be open: no trace
# or compile lasts an hour. (A cap by count fails: one program's trace holds
# thousands of small jits side by side, and a forgotten one is counted again
# under its caller.) An older end is forgotten, its seconds stay counted
_COMPILE_HORIZON_S = 3600.0


class _ThreadCompiles:
    """One thread's compile seconds and counts so far."""

    __slots__ = ("seconds", "counts", "marks", "floor")

    def __init__(self):
        self.seconds = dict(_ZERO_PHASES)
        self.counts = dict(_ZERO_COUNTS)
        # (end of an interval, the seconds as they stood at that end)
        self.marks: deque = deque()
        self.floor = dict(_ZERO_PHASES)   # the seconds before marks[0]


class CompileClock:
    """Where jax says compiling went: seconds tracing, lowering and in the
    backend compiler (a persistent-cache hit lands there as its read time),
    programs compiled, cache hits and writes, from ``jax.monitoring``.

    The events fire on the thread that compiles, so the clock accumulates
    per thread and a caller brackets a region with :meth:`read` before and
    :meth:`since` after: what another thread compiled meanwhile is not in
    it. :meth:`totals` is the sum over all threads.

    jax reports a jit traced inside another's trace, and a helper traced
    while a program is lowered, as events of their own inside their
    caller's interval. A thread's seconds are the union of its intervals
    (jax gives each event's start and end, and an outer one after those it
    holds): an interval that contains earlier ones replaces them, under its
    own phase. So the three phases of a bracket never add up to more than
    the bracket's wall."""

    def __init__(self):
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._total = {**_ZERO_PHASES, **_ZERO_COUNTS}

    def _mine(self) -> _ThreadCompiles:
        st = getattr(self._tls, "st", None)
        if st is None:
            st = self._tls.st = _ThreadCompiles()
        return st

    # --- the two listeners (compiling threads) ---

    def span(self, event: str, start: float, end: float, **_: Any) -> None:
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        st = self._mine()
        while st.marks and st.marks[-1][0] > start:
            st.marks.pop()
        now = dict(st.marks[-1][1] if st.marks else st.floor)
        now[phase] += end - start
        programs = int(phase == "backend_s")
        with self._lock:
            for k, v in now.items():
                self._total[k] += v - st.seconds[k]
            self._total["programs"] += programs
        st.counts["programs"] += programs
        st.seconds = now
        st.marks.append((end, now))
        while st.marks[0][0] < end - _COMPILE_HORIZON_S:
            st.floor = st.marks.popleft()[1]

    def event(self, event: str, **_: Any) -> None:
        key = _COMPILE_COUNTS.get(event)
        if key is None:
            return
        self._mine().counts[key] += 1
        with self._lock:
            self._total[key] += 1

    # --- reading ---

    def read(self) -> Dict[str, float]:
        """What this thread has compiled so far."""
        st = self._mine()
        return {**st.seconds, **st.counts}

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        """What this thread compiled since ``before`` (a :meth:`read`)."""
        return {k: v - before[k] for k, v in self.read().items()}

    def totals(self) -> Dict[str, float]:
        """What every thread of the process has compiled so far."""
        with self._lock:
            return dict(self._total)


_compile_clock: Optional[CompileClock] = None
_compile_clock_lock = threading.Lock()


def compile_clock() -> CompileClock:
    """The process's one compile clock; the first call hangs its two
    listeners on ``jax.monitoring``, for the life of the process: it
    registers once, never per decoder. A listener that is never called
    between compiles costs the hot path nothing."""
    global _compile_clock
    with _compile_clock_lock:
        if _compile_clock is None:
            import jax

            clock = CompileClock()
            jax.monitoring.register_event_time_span_listener(clock.span)
            jax.monitoring.register_event_listener(clock.event)
            _compile_clock = clock
    return _compile_clock


# --- device (XLA) profiling ---


@contextmanager
def device_profile(log_dir: Path) -> Iterator[None]:
    """Capture a TensorBoard/XProf device trace of everything inside the
    block together with the program's own spans of it.

    The tracer is on inside the block (and left as it was found). The first
    thing in the profile is a ``kubeml.clock_tie`` annotation, recorded as a
    span of the same name too: the difference of their starts maps the
    profiler's clock onto the tracer's, so a device idle gap can be named by
    the span that covers it. On exit the block's spans are written to
    ``<log_dir>/kubeml-spans.json`` (Chrome trace-event JSON)."""
    import jax

    log_dir = Path(log_dir)
    log_dir.mkdir(parents=True, exist_ok=True)
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.enable()
    entered = tracer.now()
    try:
        with jax.profiler.trace(str(log_dir)):
            tie = tracer.now()
            with jax.profiler.TraceAnnotation(CLOCK_TIE):
                pass
            tracer.add_span(CLOCK_TIE, tie, tracer.now() - tie)
            yield
    finally:
        tracer.enabled = was_on
        spans = [s.to_dict() for s in tracer.spans() if s.start >= entered]
        (log_dir / "kubeml-spans.json").write_text(
            json.dumps(merge_chrome_trace(spans)))
