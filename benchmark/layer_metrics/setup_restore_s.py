"""Entry points: checkpoint files to held device leaves, s: the parameter
server's ``ps.serving.restore`` (files read and remapped) and
``ps.serving.hold`` (each leaf cast to ``serving_param_dtype`` on the
device), as the decoder's ``startup_restore_seconds`` +
``startup_hold_seconds`` at the window's opening."""

from ._setup import at_open


def read(r):
    return at_open(r, "startup_restore_seconds", "startup_hold_seconds")
