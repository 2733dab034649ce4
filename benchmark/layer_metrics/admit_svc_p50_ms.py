"""Model step: a prefill (admit) program's own time, from its estimated
start on the device to its result on the host (``svc_s`` of its
``engine.fetch`` span), median over the window's admit programs, ms. Over
the whole window, where ``prefill_dev_ms`` sees the two of a 4 s trace."""

from ._spans import in_window, median_ms


def read(r):
    return median_ms([s["attrs"]["svc_s"]
                      for s in in_window(r, "engine.fetch", program="admit")])
