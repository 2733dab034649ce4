"""Serving control: the engine built, s: its construction (pool, page
tables, the jitted programs' wrappers: ``ps.serving.decoder``) and the
slab's own program (``engine.init_slab``: an abstract trace of the model
for the cache's shapes, then the arena zeroed), as ``startup_decoder_seconds``
+ ``startup_slab_seconds`` at the window's opening."""

from ._setup import at_open


def read(r):
    return at_open(r, "startup_decoder_seconds", "startup_slab_seconds")
