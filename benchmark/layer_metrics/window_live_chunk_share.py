"""Kernels: of the programs the WINDOW layers' page walk ran in decode
steps, the share that had pages to read, %: ``walk_chunks_live_window`` over
``walk_chunks_grid_window``, the engine's counts over the window
(``kubeml_tpu/serving/stats.py``). A window layer's grid is a ring a program
row (one program where the ring is at most 16 pages), whatever a row holds:
the share is the share of program rows that are live. An engine without
window layers has no such counters: None."""

from ._kinds import counter_share


def read(r):
    return counter_share(r, "walk_chunks_live_window",
                         "walk_chunks_grid_window")
