"""Model step: the whole decode step's share of the chip's peak, %.

The least time the chip could take for what the traced decode programs had
to do (``costs/decode_step.py``: the weights read once at the compute type,
the routed experts the rows chose, the live K/V, latents or state; the larger
of bytes over the HBM peak and operations over the compute peak) over those
programs' device time, the time ``decode_step_dev_ms`` takes its median of.
A kernel's roofline share falls silent when the kernel leaves the path; this
one bounds any claim on the step's time as long as there is a step.

The rows a step served are not in the trace: they are taken from the
requests' own lengths as the client saw them, as ``paged_decode_roofline``
does; how many experts a step touched is the program's count over the
window, as ``moe_decode_roofline`` takes it."""

from .. import reduce, spec
from ._moe import per_step
from ._programs import step_executions
from .paged_decode_roofline import live


def read(r):
    runs = step_executions(r)
    if not runs or r.trace.wall_zero is None:
        return None
    cfg = r.cell.config
    costs = spec.plugin("costs", cfg.get("step_costs", "decode_step"))
    shapes = spec.plugin("models", cfg["builder"]).shapes(cfg)
    touched, assignments = per_step(r) or (0.0, 0.0)
    shift = r.trace.wall_zero - r.win.t_open   # trace time -> window time
    least = device = 0.0
    for start, dur, steps, _ in runs:
        rows, depth = live(r.win.records, shift + start + 0.5 * dur)
        flops, nbytes = costs.decode_step(cfg, shapes, rows, depth,
                                          touched, assignments)
        least += steps * costs.min_seconds(flops, nbytes, r.peaks)[0]
        device += dur
    if device <= 0.0:
        return None
    return reduce.checked_share("decode_step_mfu", 100.0 * least / device)
