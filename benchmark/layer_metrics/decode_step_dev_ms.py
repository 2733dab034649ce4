"""Model step: device time of a decode-chunk program over the steps in it,
median over the chunk executions of the traced window, ms."""

import statistics

from ._programs import step_executions


def read(r):
    per_step = [1000.0 * dur / steps
                for _, dur, steps, _ in step_executions(r)]
    return statistics.median(per_step) if per_step else None
