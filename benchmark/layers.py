"""Per-layer metrics: each is a small reader of its own,
``layer_metrics/<name>.py``, found by the name ``BENCHMARK.json`` gives it.

A reader is ``read(r: Reading) -> float | None``; one that finds nothing to
read returns None and the metric is left out of the line."""

from __future__ import annotations

from dataclasses import dataclass

from . import reduce, spec


@dataclass
class Reading:
    cell: spec.Cell
    win: object            # the driver's Window: records, counters, spans
    trace: reduce.Trace    # the reduced profiler trace of the traced window
    peaks: dict            # the chip's row of peaks.json

    def counter(self, name: str) -> float:
        """Growth of one of the program's counters over the window."""
        c0, c1 = self.win.counters
        return float(c1[name]) - float(c0[name])

    def device_plane(self) -> str | None:
        return _ops_plane(self.trace)


def _ops_plane(trace: reduce.Trace) -> str | None:
    """The first device plane on which an operation ran."""
    return next((p for p in trace.device_planes()
                 if trace.rows(p, reduce.OPS_LINE)), None)


def read_all(cell: spec.Cell, win, trace: reduce.Trace, peaks: dict) -> dict:
    r = Reading(cell, win, trace, peaks)
    out = {}
    for name in cell.per_layer:
        value = spec.plugin("layer_metrics", name).read(r)
        if value is not None:
            out[name] = float(value)
    return out


def breakdown(win, trace: reduce.Trace) -> dict:
    """The device operations that took most time, and the longest idle gaps
    named by what the host was doing (the program's spans)."""
    plane = _ops_plane(trace)
    if plane is None:
        return {"device_ops": [], "idle_gaps": []}
    rows = trace.rows(plane, reduce.OPS_LINE)
    return {"device_ops": reduce.top(rows, 10),
            "idle_gaps": reduce.name_gaps(reduce.idle_gaps(rows), win.spans,
                                          trace.wall_zero)[:10]}
