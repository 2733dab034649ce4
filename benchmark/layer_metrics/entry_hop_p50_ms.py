"""Entry points: from the start of the server span that received a request
to the engine's ``submit`` (the start of its ``serving.request`` span, whose
parent that server span is), median over the window's requests, ms. In the
benchmark's all-in-one cluster the parent is ``controller POST /generate``
and the hop on to the parameter server is a call in the same process."""

from ._spans import in_window, median_ms


def read(r):
    starts = {s["span_id"]: s["start"] for s in r.win.spans}
    return median_ms([s["start"] - starts[s["parent_id"]]
                      for s in in_window(r, "serving.request")
                      if s["parent_id"] in starts])
