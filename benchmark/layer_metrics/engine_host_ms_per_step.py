"""Serving control: the engine thread's own work (``engine.admit``,
``engine.dispatch`` and ``engine.process`` spans: taking admissions and
packing their arrays, the jitted calls, routing tokens) over the decode
steps it dispatched in the window (``steps`` of the ``step`` programs), ms a
step. What a step costs the host; it shows in time per token only where the
device waits for it (``idle_with_work_share``)."""

from ._spans import in_window

WORK = ("engine.admit", "engine.dispatch", "engine.process")


def read(r):
    steps = sum(s["attrs"]["steps"]
                for s in in_window(r, "engine.dispatch", program="step"))
    if not steps:
        return None
    busy = sum(s["duration"] for name in WORK for s in in_window(r, name))
    return 1000.0 * busy / steps
