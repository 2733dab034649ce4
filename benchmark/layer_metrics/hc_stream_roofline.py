"""Kernels: the residual path's share of its roofline in admission
programs, %.

The least time the chip could take to read the streams once and write them
once a sub-layer, the branch's input written and its output read
(``costs/hc_streams.py``; memory-bound), over the device time of ``hc_pre``
and ``hc_post`` in admission programs. How many (position, sub-layer) pairs
an admission mixed is the program's own count (``hc_positions_admit`` over
``admission_waves``, the window's mean: bucket padding included, since the
device mixes it), times the traced admissions."""

from .. import reduce
from ..costs import hc_streams
from ._hc import kernel_in_admits, per_admit


def read(r):
    runs, mixed = kernel_in_admits(r), per_admit(r, "hc_positions_admit")
    cfg = r.cell.config
    if not runs or not mixed or not cfg.get("hc_mult"):
        return None
    flops, nbytes = hc_streams.mixed(
        mixed * len(runs), streams=cfg["hc_mult"], hidden=cfg["hidden_size"])
    least = hc_streams.min_seconds(flops, nbytes, r.peaks)[0]
    return reduce.checked_share("hc_stream_roofline",
                                100.0 * least / sum(runs))
