"""Model step: the engine's programs from jaxpr to MLIR, the Pallas
kernels' Mosaic lowering with it, s: ``compile_lower_seconds`` at the
window's opening. No cache holds it either."""

from ._setup import at_open


def read(r):
    return at_open(r, "compile_lower_seconds")
