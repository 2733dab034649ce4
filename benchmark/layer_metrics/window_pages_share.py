"""Serving control: of the ring pages the live rows hold in the window
layers, the share a step's query could read, %: ``window_pages_live`` over
``window_pages_held``, the engine's counts over the window
(``kubeml_tpu/serving/stats.py``): the second kind of lease's bound (``window
/ page_tokens + 2`` pages a row) against its use (the pages the window's keys
lie in: 8 or 9 of 10 at a window of 128 and pages of 16). An engine without
window layers has no such counters: None."""

from ._kinds import counter_share


def read(r):
    return counter_share(r, "window_pages_live", "window_pages_held")
