"""Model step: device time of one prefill-program execution, median over
the traced window, ms."""

import statistics

from .. import reduce
from ._programs import PREFILL_MODULES


def read(r):
    plane = r.device_plane()
    if plane is None:
        return None
    runs = [1000.0 * d
            for _, d in reduce.executions(r.trace, plane, PREFILL_MODULES)]
    return statistics.median(runs) if runs else None
