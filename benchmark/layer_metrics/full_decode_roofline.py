"""Kernels: the page walk's share of its roofline in the FULL layers' decode
steps, %.

The least time the chip could take to read the rows' whole depth of keys and
values in the full layers once (``costs/paged_attention_window.py``: 4 K/V
heads of 192 + 128 values a token and layer; the operations by the 64 query
heads over 192 + 128) over those layers' kernel time in the traced decode
programs (``_kinds.py``). ``gqa_decode_roofline`` takes one head size and one
kind of layer and cannot read this configuration. The arena stores a K/V
head's 320 values in 320 lanes (the K heads' 64-lane tails two to a lane
row), so the bytes counted are the bytes stored."""

from ._kinds import roofline


def read(r):
    return roofline(r, "full", "full_decode_roofline")
