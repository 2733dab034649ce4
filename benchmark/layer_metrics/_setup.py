"""The program's own account of its start (``kubeml_tpu/serving/stats.py``:
the ``startup_*_seconds`` of the parameter server's load path and the slab,
the ``compile_*`` phases of every engine program's first call), read **at the
window's opening**: ``win.counters[0]`` is the decoder's ``telemetry()``
there, which is everything the program spent since it started. A program
without the keys, as every commit before PR 39, gives the readers nothing
to read: they return None and their metrics are left out."""


def at_open(r, *keys):
    """The sum of the counters ``keys`` as they stood when the window
    opened; None where the program has not all of them."""
    c0 = r.win.counters[0]
    if any(k not in c0 for k in keys):
        return None
    return sum(float(c0[k]) for k in keys)
