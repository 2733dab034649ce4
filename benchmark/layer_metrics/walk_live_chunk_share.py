"""Kernels: of the programs the K/V page walk's decode body ran, the share
that had pages to read, %: ``walk_chunks_live`` over ``walk_chunks_grid``,
the engine's counts over the window (``kubeml_tpu/serving/stats.py``). The
grid of a decode step is every program row by the table width's chunks,
whatever a row holds; a chunk past a row's depth, and every chunk of a row
the host retired, is an empty program that costs its grid step and nothing
else. Low where few rows are live or the deepest row sets a wide table for
shallow ones. An engine whose steps do not take that body (latent pages, a
commit before PR 38) has no such counters: None."""

COUNTERS = ("walk_chunks_live", "walk_chunks_grid")


def read(r):
    c0, c1 = r.win.counters
    if any(k not in c0 or k not in c1 for k in COUNTERS):
        return None
    grid = r.counter("walk_chunks_grid")
    if grid <= 0:
        return None
    return 100.0 * r.counter("walk_chunks_live") / grid
