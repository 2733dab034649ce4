"""Kernels: the ``moe_experts`` grouped products' share of their roofline
in decode steps, %.

The least time the chip could take to read the weights of the experts the
steps' rows chose once, plus the rows' activations (``costs/moe_experts.py``;
memory-bound at this model's 32 rows), over the kernel's device time in
decode programs. How many experts a step touched is the program's own count
(``moe_experts_touched`` over ``device_steps``, the window's mean: the trace
does not say which experts a step read), times the traced steps."""

from .. import reduce
from ..costs import moe_experts
from ._moe import kernel_in_steps, per_step


def read(r):
    runs, counts = kernel_in_steps(r), per_step(r)
    if not runs or counts is None:
        return None
    cfg = r.cell.config
    steps = sum(s for s, _ in runs)
    kernel = sum(t for _, t in runs)
    flops, nbytes = moe_experts.decode_steps(
        counts[0] * steps, counts[1] * steps, hidden=cfg["hidden_size"],
        width=cfg["moe_intermediate_size"])
    least = moe_experts.min_seconds(flops, nbytes, r.peaks)[0]
    return reduce.checked_share("moe_decode_roofline",
                                100.0 * least / kernel)
