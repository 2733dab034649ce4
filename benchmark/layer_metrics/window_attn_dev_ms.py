"""Model step: device time of the WINDOW layers' page walk (``%attn.<n>``,
those layers added up) in one decode step, median over the decode-program
executions of the traced window, ms. The calls are told apart by their order
in a step and the configuration's ``hybrid_layer_pattern`` (``_kinds.py``).
A window layer's walk reads a row's ring (10 pages whatever the row's depth)
where a full layer's reads the whole depth: this is what five of seven
layers' attention costs a step. None for a configuration whose attention
does not differ by layer."""

import statistics

from ._kinds import step_runs


def read(r):
    per_step = [1000.0 * window_s / steps
                for _, _, steps, window_s, _ in step_runs(r)]
    return statistics.median(per_step) if per_step else None
