"""Model step: device time of the ``moe_experts`` grouped products in one
decode step, all expert layers added up, median over the decode-program
executions of the traced window, ms."""

import statistics

from ._moe import kernel_in_steps


def read(r):
    per_step = [1000.0 * seconds / steps
                for steps, seconds in kernel_in_steps(r)]
    return statistics.median(per_step) if per_step else None
