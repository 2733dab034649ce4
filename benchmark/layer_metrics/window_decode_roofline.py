"""Kernels: the page walk's share of its roofline in the WINDOW layers'
decode steps, %.

The least time the chip could take to read the keys and values the steps'
queries see in the window layers once (``costs/paged_attention_window.py``:
at most ``sliding_window`` keys a row and layer, 8 K/V heads of 192 + 128
values; the operations by the 64 query heads over 192 + 128) over those
layers' kernel time in the traced decode programs (``_kinds.py``). 128 keys
a row is one program of ten pages and mostly latency: the share reads low,
which is what it is there to hold; the two pages of a ring a step fetches
beyond its window are in the time and not in the count."""

from ._kinds import roofline


def read(r):
    return roofline(r, "window", "window_decode_roofline")
