"""Device: the share of the profiler's window in which no operation ran on
the device although the engine had work, %.

The window is the one ``device.window_s`` describes: from the trace's zero
to the call that stopped the profiler. Of the seconds no device operation
covers, the part inside the engine's ``engine.wait_work`` spans (nothing
pending, no live row, nothing in flight) is idle for want of requests; the
rest is the host holding the chip back. The two add up to the run's idle
share. Needs the wall-clock tie, and a program that records the engine's
spans: without them there is nothing to tell the two parts apart."""

from .. import reduce


def read(r):
    plane = r.device_plane()
    zero = r.trace.wall_zero
    if plane is None or zero is None or r.win.trace_wall is None:
        return None
    if not any(s["name"] == "engine.dispatch" for s in r.win.spans):
        return None
    waits = [(s["start"] - zero, s["start"] - zero + s["duration"])
             for s in r.win.spans if s["name"] == "engine.wait_work"]
    window = (0.0, r.win.trace_wall[1] - zero)
    idle = excused = 0.0
    for start, dur in reduce.idle_gaps(r.trace.rows(plane, reduce.OPS_LINE),
                                       window):
        idle += dur
        excused += sum(max(0.0, min(hi, start + dur) - max(lo, start))
                       for lo, hi in waits)
    return 100.0 * (idle - excused) / (window[1] - window[0])
