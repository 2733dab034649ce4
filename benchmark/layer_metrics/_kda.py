"""What the Kimi Delta Attention mixer's decode kernel is called in a trace,
and its time inside the decode programs. Mosaic names the custom call after
the Pallas kernel (``kubeml_tpu/ops/gated_delta.py``: ``name="kda_update"``
where the gate is per key channel; a head's scalar gate keeps
``gdn_update``, ``_gdn.py``), so the operations' line shows
``%kda_update.<n> custom-call``, once per KDA layer per step. A program
without the kernel (every configuration but the ``kimi_linear`` family, and
every commit before PR 51) has no such row: the readers return None."""

from .. import reduce
from ._programs import step_executions

KERNEL_FAMILY = "%kda_update custom-call"


def kernel_in_steps(r) -> list:
    """(steps, kernel_seconds) of each decode-program execution wholly
    inside the trace that ran the kernel."""
    runs = step_executions(r)
    if not runs:
        return []
    rows = sorted((s, d) for n, s, d
                  in r.trace.rows(r.device_plane(), reduce.OPS_LINE)
                  if reduce.family(n) == KERNEL_FAMILY)
    out, i = [], 0
    for start, dur, steps, _ in runs:
        while i < len(rows) and rows[i][0] < start:
            i += 1
        seconds = 0.0
        while i < len(rows) and rows[i][0] + rows[i][1] <= start + dur:
            seconds += rows[i][1]
            i += 1
        if seconds > 0.0:
            out.append((steps, seconds))
    return out
