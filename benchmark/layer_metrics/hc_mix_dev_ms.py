"""Model step: device time of the residual path's kernels (``hc_pre`` and
``hc_post``, every sub-layer added up) in one admission program, median over
the admission-program executions of the traced window, ms."""

import statistics

from ._hc import kernel_in_admits


def read(r):
    per_admit = [1000.0 * s for s in kernel_in_admits(r)]
    return statistics.median(per_admit) if per_admit else None
