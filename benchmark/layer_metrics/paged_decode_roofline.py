"""Kernels: the page-walk decode kernel's share of its roofline, %.

The least time the chip could take for the live K/V the traced steps had to
read (``costs/paged_attention.py``: bytes over the HBM peak bound it, since
a decode step does one multiply-add per two bytes read) over the kernel's
device time in decode-chunk programs.

The depth of the rows a chunk served is not in the trace, so it is taken
from the requests' own lengths as the client saw them: a request decodes
between its first and its last delta, and its depth grows from its prompt's
length by one token a step."""

from .. import reduce
from ..costs import paged_attention
from ._programs import step_executions


def live(records: list, t: float) -> tuple:
    """(rows, tokens in their caches) of the requests decoding at window
    time ``t``."""
    rows = depth = 0.0
    for x in records:
        if x["error"] or x["first"] is None or x["last"] <= x["first"]:
            continue
        if x["first"] <= t <= x["last"]:
            done = (t - x["first"]) / (x["last"] - x["first"])
            rows += 1.0
            depth += x["prompt_tokens"] + done * len(x["tokens"])
    return rows, depth


def _live_depth(records: list, t: float) -> float:
    """Tokens in the caches of the requests decoding at window time ``t``."""
    return live(records, t)[1]


def read(r):
    runs = step_executions(r)
    if not runs or r.trace.wall_zero is None:
        return None
    cfg = r.cell.config
    shift = r.trace.wall_zero - r.win.t_open   # trace time -> window time
    least = kernel = 0.0
    for start, dur, steps, seconds in runs:
        depth = _live_depth(r.win.records, shift + start + 0.5 * dur)
        flops, nbytes = paged_attention.decode_step(
            depth * steps, layers=cfg["n_layer"], heads=cfg["n_head"],
            head_dim=cfg["n_embd"] // cfg["n_head"])
        least += paged_attention.min_seconds(flops, nbytes, r.peaks)[0]
        kernel += seconds
    if kernel <= 0.0:
        return None
    return reduce.checked_share("paged_decode_roofline",
                                100.0 * least / kernel)
