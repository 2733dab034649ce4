"""Serving control: decode time the window's requests lost behind other
requests' prefill programs (``hol_stall_seconds`` of their
``serving.request`` spans: each such program's service time, charged to
every row it delayed) over the tokens they produced, ms a token."""

from ._spans import in_window


def read(r):
    spans = in_window(r, "serving.request")
    tokens = sum(s["attrs"].get("tokens", 0) for s in spans)
    if not tokens:
        return None
    return 1000.0 * sum(s["attrs"]["hol_stall_seconds"]
                        for s in spans) / tokens
