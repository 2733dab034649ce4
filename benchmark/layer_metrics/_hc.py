"""What the residual path's two kernels are called in a trace, their time
inside the admission programs, and the program's own count of what they
mixed.

Mosaic names a custom call after the Pallas kernel
(``kubeml_tpu/ops/hyper_connection.py``: ``name="hc_pre"``,
``name="hc_post"``), so the operations' line shows ``%hc_pre.<n>
custom-call`` and ``%hc_post.<n> custom-call``, once each a sub-layer. A
plain ``jax.numpy`` body cannot be found there by name: XLA fuses it into
``%fusion.<n>`` and the reduced trace keeps an event's instruction name
alone (PERF.md, PR 37). A program with a single residual stream (every
other family, and every commit before PR 37) has no such row and no such
counter: the readers return None."""

from .. import reduce
from ._programs import PREFILL_MODULES

KERNEL_FAMILIES = ("%hc_pre custom-call", "%hc_post custom-call")


def kernel_in_admits(r) -> list:
    """Seconds of the two kernels in each admission-program execution
    wholly inside the trace that ran them."""
    plane = r.device_plane()
    if plane is None:
        return []
    rows = sorted((s, d) for n, s, d in r.trace.rows(plane, reduce.OPS_LINE)
                  if reduce.family(n) in KERNEL_FAMILIES)
    out, i = [], 0
    for start, dur in sorted(reduce.executions(r.trace, plane,
                                               PREFILL_MODULES)):
        while i < len(rows) and rows[i][0] < start:
            i += 1
        seconds = 0.0
        while i < len(rows) and rows[i][0] + rows[i][1] <= start + dur:
            seconds += rows[i][1]
            i += 1
        if seconds > 0.0:
            out.append(seconds)
    return out


def per_admit(r, name: str) -> float | None:
    """Growth of the program's counter ``name`` over the window, an
    admission program; None without the counters."""
    c0, c1 = r.win.counters
    if any(k not in c0 or k not in c1 for k in ("admission_waves", name)):
        return None
    admits = r.counter("admission_waves")
    return r.counter(name) / admits if admits > 0 else None
