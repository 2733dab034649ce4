"""What the routed experts' grouped products are called in a trace, their
time inside the decode programs, and the program's own counts of them.

Mosaic names the custom call after the Pallas kernel
(``kubeml_tpu/ops/grouped_matmul.py``: ``name="moe_experts"``), so the
operations' line shows ``%moe_experts.<n> custom-call``, twice per expert
layer per step (gate and up fused, then down). The latent page walk
(``kubeml_tpu/ops/mla_attention.py mla_attn``) shows as ``%attn.<n>``, after
the block's ``attn`` scope like the per-head walk, which is what
``_programs.step_executions`` counts a program's steps by. A program without
expert layers (every other family, and every commit before PR 32) has no such
row and no such counter: the readers return None."""

from .. import reduce
from ._programs import step_executions

KERNEL_FAMILY = "%moe_experts custom-call"
COUNTERS = ("moe_experts_touched", "moe_assignments", "device_steps")


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


def per_step(r) -> tuple | None:
    """(experts touched, assignments) a decode step over the window, all
    layers added up, from the program's counters; None without them."""
    c0, c1 = r.win.counters
    if any(k not in c0 or k not in c1 for k in COUNTERS):
        return None
    steps = r.counter("device_steps")
    if steps <= 0:
        return None
    return (r.counter("moe_experts_touched") / steps,
            r.counter("moe_assignments") / steps)
