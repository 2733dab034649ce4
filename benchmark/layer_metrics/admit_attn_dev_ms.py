"""Model step: device time of the K/V page walk (``%attn.<n>``, every layer
added up) in one prefill / admission program, median over the
prefill-program executions of the traced window, ms. The walk's tile body
(``kubeml_tpu/ops/paged_attention.py``: query tiles by chunks of pages) is
what an admit of a K/V-paged model runs; a model whose admit attends without
the kernel (the latent families' expanded prefill attention) has no such
row inside a prefill program, and the reader returns None."""

import statistics

from .. import reduce
from ._programs import KERNEL_FAMILY, PREFILL_MODULES


def kernel_in_admits(r) -> list:
    """Seconds of the page walk in each prefill-program execution wholly
    inside the trace that ran it."""
    plane = r.device_plane()
    if plane is None:
        return []
    rows = sorted((s, d) for n, s, d in r.trace.rows(plane, reduce.OPS_LINE)
                  if reduce.family(n) == KERNEL_FAMILY)
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


def read(r):
    per_admit = [1000.0 * s for s in kernel_in_admits(r)]
    return statistics.median(per_admit) if per_admit else None
