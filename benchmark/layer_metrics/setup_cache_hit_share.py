"""Device: of the engine programs jax looked up in its persistent compile
cache before the window opened, the share it found, %: ``compile_cache_hits``
over hits + ``compile_cache_misses`` (jax counts a miss where it writes the
entry it has just compiled). 100 is a warm start, 0 a cold one; a process
without a persistent cache counts neither and the metric is left out."""

from ._setup import at_open


def read(r):
    hits = at_open(r, "compile_cache_hits")
    looked = at_open(r, "compile_cache_hits", "compile_cache_misses")
    return 100.0 * hits / looked if looked else None
