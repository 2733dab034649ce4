"""Model step: device time of the ``kda_update`` kernel in one decode step,
all KDA layers added up, median over the decode-program executions of the
traced window, ms."""

import statistics

from ._kda import kernel_in_steps


def read(r):
    per_step = [1000.0 * seconds / steps
                for steps, seconds in kernel_in_steps(r)]
    return statistics.median(per_step) if per_step else None
