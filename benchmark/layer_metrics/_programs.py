"""What the engine's programs and the page-walk kernel are called in a
trace today (PR 23, read off a trace by hand; the program names nothing):

- a decode chunk is ``jit`` of a ``functools.partial``, which the profiler
  shows as module ``jit__unknown(<fingerprint>)``, one fingerprint per
  (chunk size, table width);
- prefill is module ``jit__prefill_admit_impl(<fingerprint>)``;
- the Mosaic page-walk kernel is the custom call ``%attn.<n>``, once per
  layer per step (and once per layer in a prefill).

When the ``tracing`` issue gives them names, only this file changes."""

from .. import reduce

STEP_MODULES = ("jit__unknown(",)
PREFILL_MODULES = ("jit__prefill_admit_impl(",)
KERNEL_FAMILY = "%attn custom-call"


def step_executions(r) -> list:
    """(start_s, duration_s, steps, kernel_seconds) of each decode-chunk
    execution wholly inside the trace; ``steps`` is counted from the
    kernel's calls in it (one per layer per step)."""
    plane = r.device_plane()
    if plane is None:
        return []
    layers = r.cell.config["n_layer"]
    kernel = sorted((s, d) for n, s, d in r.trace.rows(plane, reduce.OPS_LINE)
                    if reduce.family(n) == KERNEL_FAMILY)
    out, i = [], 0
    for start, dur in sorted(reduce.executions(r.trace, plane, STEP_MODULES)):
        while i < len(kernel) and kernel[i][0] < start:
            i += 1
        j, seconds = i, 0.0
        while j < len(kernel) and kernel[j][0] + kernel[j][1] <= start + dur:
            seconds += kernel[j][1]
            j += 1
        calls, i = j - i, j
        if calls and calls % layers == 0:
            out.append((start, dur, calls // layers, seconds))
    return out
