"""Serving control: the program's ``serving.queue_wait`` spans (one per
request, host clock, submit to slot) of the window, median, ms."""

import numpy as np


def read(r):
    lo, hi = r.win.t_open, r.win.t_open + r.win.seconds
    waits = [1000.0 * s["duration"] for s in r.win.spans
             if s["name"] == "serving.queue_wait" and lo <= s["start"] <= hi]
    return float(np.percentile(waits, 50)) if waits else None
