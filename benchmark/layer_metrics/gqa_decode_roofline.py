"""Kernels: the page-walk decode kernel's share of its roofline under
grouped-query attention, %.

``paged_decode_roofline`` with the K/V bytes counted by K/V heads and the
operations by query heads (``costs/paged_attention_gqa.py``), the head size
read from the configuration's ``head_dim`` and not from ``n_embd // n_head``.
The depth of the rows a step served is taken from the client's records, as
there."""

from .. import reduce
from ..costs import paged_attention_gqa
from ._programs import step_executions
from .paged_decode_roofline import _live_depth


def read(r):
    cfg = r.cell.config
    if "num_key_value_heads" not in cfg:
        return None
    runs = step_executions(r)
    if not runs or r.trace.wall_zero is None:
        return None
    shift = r.trace.wall_zero - r.win.t_open   # trace time -> window time
    least = kernel = 0.0
    for start, dur, steps, seconds in runs:
        depth = _live_depth(r.win.records, shift + start + 0.5 * dur)
        flops, nbytes = paged_attention_gqa.decode_step(
            depth * steps, layers=cfg["n_layer"],
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"])
        least += paged_attention_gqa.min_seconds(flops, nbytes, r.peaks)[0]
        kernel += seconds
    if kernel <= 0.0:
        return None
    return reduce.checked_share("gqa_decode_roofline", 100.0 * least / kernel)
