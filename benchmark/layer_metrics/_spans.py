"""The program's spans of the measured window (``win.spans``: the tracer's
dicts, on the wall clock), for the readers of the engine's dispatch-level
timeline (``engine.*``, ``kubeml_tpu/serving/batcher.py``). A program
without those spans, as every commit before PR 24, gives the readers
nothing to read: they return None and their metrics are left out."""

import statistics


def in_window(r, name: str, **attrs) -> list:
    """The window's spans called ``name`` whose attributes match ``attrs``:
    those that start inside it, as ``queue_wait_p50_ms`` takes them."""
    lo, hi = r.win.t_open, r.win.t_open + r.win.seconds
    return [s for s in r.win.spans
            if s["name"] == name and lo <= s["start"] <= hi
            and all(s["attrs"].get(k) == v for k, v in attrs.items())]


def median_ms(seconds: list):
    return 1000.0 * statistics.median(seconds) if seconds else None
