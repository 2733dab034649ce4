"""Model step: Python tracing of the engine's programs to jaxprs, s:
``compile_trace_seconds`` at the window's opening, the ``trace_s`` of every
first ``engine.dispatch`` summed (a jit traced inside another's trace
counted once). No cache holds it: a warm start pays it again."""

from ._setup import at_open


def read(r):
    return at_open(r, "compile_trace_seconds")
