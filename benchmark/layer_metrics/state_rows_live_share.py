"""Serving control: of the recurrent states the decode steps' state kernel
read and wrote, the share that belonged to a live row and advanced, %:
``state_rows_live`` over ``state_rows_moved``, the engine's counts over the
window (``kubeml_tpu/serving/stats.py``): slab rows a step, in each layer
that keeps a state, against the live rows among them. The kernel moves a dead
row's state too (with ``g = beta = 0``), so this is the share of the kernel's
bytes that served a request. An engine without the counters (a model without
recurrent state, a commit before PR 48): None."""

from ._kinds import counter_share


def read(r):
    return counter_share(r, "state_rows_live", "state_rows_moved")
