"""Serving control: how long a prefill (admit) program sat behind programs
dispatched before it, from the end of its dispatch to its estimated start
on the device (``wait_s`` of its ``engine.fetch`` span), median over the
window's admit programs, ms. The run-ahead depth's price in first-token
time."""

from ._spans import in_window, median_ms


def read(r):
    return median_ms([s["attrs"]["wait_s"]
                      for s in in_window(r, "engine.fetch", program="admit")])
