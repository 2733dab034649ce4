"""Kernels: of the programs the K/V page walk's tile body ran in the window's
prefill and admission programs, the share that had pages to read, %:
``tile_chunks_live`` over ``tile_chunks_grid``, the engine's counts
(``kubeml_tpu/serving/stats.py``). The grid of an admit is its bucket's query
tiles by the table width's chunks of pages; a chunk past a tile's causal
depth (above the diagonal) or past the row's depth is an empty program that
costs its grid step and nothing else. An engine whose admits do not take
that body (latent pages), or a commit before PR 40, has no such counters:
None."""

COUNTERS = ("tile_chunks_live", "tile_chunks_grid")


def read(r):
    c0, c1 = r.win.counters
    if any(k not in c0 or k not in c1 for k in COUNTERS):
        return None
    grid = r.counter("tile_chunks_grid")
    if grid <= 0:
        return None
    return 100.0 * r.counter("tile_chunks_live") / grid
