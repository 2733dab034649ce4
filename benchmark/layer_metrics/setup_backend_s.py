"""Device: XLA's compile of the engine's programs, or the persistent
cache's read where it hit, s: ``compile_backend_seconds`` at the window's
opening. ``setup_cache_hit_share`` says which of the two a run shows."""

from ._setup import at_open


def read(r):
    return at_open(r, "compile_backend_seconds")
