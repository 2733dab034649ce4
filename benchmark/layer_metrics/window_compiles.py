"""Serving control: programs the engine compiled inside the window (its
``compiled_programs`` count at close less at open). Expected 0: warm-up
covers every shape the mix reaches."""


def read(r):
    return r.counter("compiled_programs")
