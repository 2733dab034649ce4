"""From the profiler's trace to numbers: the one reduction every PR's
per-layer metrics are read through.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with nothing
but jax) into plain rows; the functions below work on rows, so they are
checked on a small recorded trace (``tests/data/trace_rows.json``) without a
profiler. A row is ``(name, start_s, duration_s)`` on one line of one plane;
times are seconds from the start of the trace. On the operations' line the
profiler names an event by its whole HLO instruction; a row keeps the
instruction's name and its opcode (``%attn.394 custom-call``)."""

from __future__ import annotations

import glob
import heapq
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

# the lines of a TPU device plane this reduction reads, as the profiler
# names them (seen in this repo's traces, PERF.md section 5)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench.mark"   # the host annotation that ties the trace to the wall clock


@dataclass
class Trace:
    """lines[(plane, line)] -> list of (name, start_s, duration_s)."""

    lines: dict = field(default_factory=dict)
    # wall-clock time of the trace's zero, from the MARK annotation; None
    # if the trace has none
    wall_zero: float | None = None

    def device_planes(self) -> list:
        return sorted({p for p, _ in self.lines if p.startswith("/device:")})

    def rows(self, plane: str, line: str) -> list:
        return self.lines.get((plane, line), [])

    def to_json(self) -> str:
        return json.dumps({"wall_zero": self.wall_zero,
                           "lines": [[p, l, rows] for (p, l), rows
                                     in sorted(self.lines.items())]})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(lines={(p, l): [tuple(r) for r in rows]
                          for p, l, rows in d["lines"]},
                   wall_zero=d["wall_zero"])


_INSTR = re.compile(r"^(%[^ ]+) = .*?[\]})] ([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """``%attn.394 = bf16[...]{...} custom-call(...)`` -> ``%attn.394
    custom-call``; a name of another form is kept (cut to 120 characters)."""
    m = _INSTR.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name.split(" = ")[0][:120]


def family(name: str) -> str:
    """``%attn.394 custom-call`` -> ``%attn custom-call``: the layers'
    copies of one operation under one name."""
    head, _, op = name.partition(" ")
    head = re.sub(r"\.\d+$", "", head)
    return f"{head} {op}" if op else head


def load(trace_dir: Path, mark_wall: float | None = None) -> Trace:
    """Read the newest trace under ``trace_dir``. Keeps the device planes
    whole and, of the host, only the MARK annotation."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    trace = Trace()
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device:
                trace.lines[(plane.name, line.name)] = [
                    (short_name(e.name), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9) for e in line.events]
            elif mark_wall is not None and trace.wall_zero is None:
                for e in line.events:
                    if e.name == MARK:
                        trace.wall_zero = mark_wall - e.start_ns * 1e-9
                        break
    return trace


def union_seconds(rows: list) -> float:
    """Seconds covered by at least one row."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(rows, key=lambda r: r[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    device planes that ran any."""
    per = [union_seconds(trace.rows(p, OPS_LINE))
           for p in trace.device_planes() if trace.rows(p, OPS_LINE)]
    return sum(per) / len(per) if per else 0.0


def self_seconds(rows: list) -> list:
    """(name, seconds) per row, the seconds less what the rows nested inside
    it cover: a ``while`` is charged its own time, not its body's."""
    out, stack = [], []   # stack of [name, stop, self]

    def close(until: float):
        while stack and stack[-1][1] <= until + 1e-12:
            name, _, own = stack.pop()
            out.append((name, max(own, 0.0)))

    for name, start, dur in sorted(rows, key=lambda r: (r[1], -r[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def top(rows: list, n: int = 10) -> list:
    """[[family, seconds], ...]: the operations that took most time of their
    own, the layers' copies of one operation added up."""
    by: dict = {}
    for name, own in self_seconds(rows):
        k = family(name)
        by[k] = by.get(k, 0.0) + own
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rows: list, window: tuple | None = None) -> list:
    """(start_s, duration_s) of every stretch of ``window`` that no row
    covers, longest first. Without a window: from the first row's start to
    the last row's end."""
    if window is None:
        if not rows:
            return []
        window = (min(r[1] for r in rows), max(r[1] + r[2] for r in rows))
    gaps, end = [], window[0]
    for _, start, dur in sorted(rows, key=lambda r: r[1]):
        if start > end:
            gaps.append((end, min(start, window[1]) - end))
        end = max(end, start + dur)
        if end >= window[1]:
            break
    if end < window[1]:
        gaps.append((end, window[1] - end))
    return sorted((g for g in gaps if g[1] > 0), key=lambda g: -g[1])


def name_gaps(gaps: list, spans: list, wall_zero: float | None,
              default: str = "host, no span open") -> list:
    """Name each gap by the program's innermost host span that covers its
    middle (``spans`` are the program tracer's dicts, on the wall clock),
    and add up by name: [[name, seconds], ...], longest first.

    Innermost is the covering span of least duration, the first in
    ``spans`` among equals. Gaps and spans are swept once in time order
    (a traced window has 200,000 gaps and 16,000 spans; a scan of every
    span for every gap took minutes, ROADMAP D18), and the seconds are
    added up in the order the gaps came in, so the sums are the scan's to
    the last bit."""
    names = [default] * len(gaps)
    if wall_zero is not None and spans:
        by_start = sorted(range(len(spans)), key=lambda i: spans[i]["start"])
        mids = sorted((wall_zero + start + 0.5 * dur, g)
                      for g, (start, dur) in enumerate(gaps))
        # spans whose start has passed: (duration, index in spans, end)
        opened, nxt = [], 0
        for mid, g in mids:
            while (nxt < len(by_start)
                   and spans[by_start[nxt]]["start"] <= mid):
                i = by_start[nxt]
                s = spans[i]
                heapq.heappush(opened, (s["duration"], i,
                                        s["start"] + s["duration"]))
                nxt += 1
            # a span that ended before this middle covers no later one
            while opened and opened[0][2] < mid:
                heapq.heappop(opened)
            if opened:
                names[g] = spans[opened[0][1]]["name"]
    named: dict = {}
    for name, (_, dur) in zip(names, gaps):
        named[name] = named.get(name, 0.0) + dur
    return [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])]


def executions(trace: Trace, plane: str, module_prefixes: tuple) -> list:
    """(start_s, duration_s) of each execution of the modules whose name
    starts with one of ``module_prefixes`` on ``plane``."""
    return [(s, d) for n, s, d in trace.rows(plane, MODULES_LINE)
            if n.startswith(module_prefixes)]


def checked_share(name: str, value: float) -> float:
    """A share of a roofline or of a peak cannot pass 100%: one over 105%
    means the operations or bytes are counted too high, or the time leaves
    out part of the work. It is refused, not clipped."""
    if value > 105.0:
        raise ValueError(f"{name} reads {value:.1f}%: over 105% of the "
                         f"roofline is a fault in the count or in the time")
    return value
