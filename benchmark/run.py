#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (everything before the window opens) is timed as ``setup_s``; the
window measures for ``--seconds``; then the program is freed and the plain
reference decides ``correct``. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` (plus ``breakdown`` in a traced run) and last
``check``, each number compared beside its limit, which are also the last
lines of standard error.

It runs on the machine it is started on and refuses anything but a TPU whose
kind is in ``peaks.json``: no number of a CPU run is ever printed under a
device metric's name."""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import check, layers, reduce, serving, spec  # noqa: E402

DRIVERS = {"open_loop": serving, "closed_loop": serving}


def find_device(chips: int, require_tpu: bool) -> tuple:
    """(device dict for the result line, peaks). Fails without the chips."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise spec.SpecError(f"platform is {d.platform!r}, not 'tpu': the "
                             f"benchmark measures the chip and has no "
                             f"fallback")
    if len(devices) < chips:
        raise spec.SpecError(f"the cell asks for {chips} chips, jax finds "
                             f"{len(devices)}")
    peaks = spec.peaks_for(d.device_kind) if require_tpu else {}
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devices)}, peaks)


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device, peaks = find_device(cell.chips, require_tpu)
    driver = DRIVERS[cell.traffic["kind"]]
    units = spec.units()

    system = driver.ServingSystem(cell, args.seed)
    try:
        return _run(args, cell, device, peaks, driver, units, system)
    finally:
        system.cleanup()


def _run(args, cell, device, peaks, driver, units, system) -> int:
    try:
        notes = system.setup()
        setup_s = time.time() - T_START
        serving.log(f"set-up {setup_s:.1f} s: {json.dumps(notes)}")
        win = system.window(args.seconds, args.seed, bool(args.trace))
    finally:
        left = system.teardown()
    serving.log(f"window closed; {left} bytes still in use on the device")

    records = win.records
    attempted = len(records)
    failed = sum(bool(r["error"]) for r in records)
    for r in [r for r in records if r["error"]][:5]:
        serving.log(f"request {r['id']} failed: {r['error']}")
    late = max(((r["sent"] - r["due"], r["id"]) for r in records
                if r["sent"] is not None), default=None)
    if late is not None:
        serving.log(f"the generator's latest send: {1000.0 * late[0]:.1f} ms "
                    f"after it was due (request {late[1]})")

    # correctness, after the program's state is freed
    t0 = time.time()
    prompts = {r["id"]: r["prompt"] for r in system.last_requests}
    sampled = check.sample(records, args.seed, cell.traffic["check_requests"])
    weights = system.builder.init_weights(cell.config, args.seed)
    got = check.gaps(cell, weights, prompts, sampled)
    del weights
    readings = check.serving_readings(got["served"], win, sampled)
    correct, lines, compared = check.compare(readings,
                                             check.limits_for(cell.name))
    serving.log(f"check: {len(got['served'])} served tokens of "
                f"{len(sampled)} requests against the reference in "
                f"{time.time() - t0:.1f} s")

    device["memory_peak_bytes"] = win.memory_peak_bytes
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if args.trace:
        trace = reduce.load(win.trace_dir, mark_wall=win.trace_mark_wall)
        values = layers.read_all(cell, win, trace, peaks)
        device["busy_s"] = reduce.busy_seconds(trace)
        # the traced window runs from the trace's own zero (no device event
        # can precede it) to the call that stopped the profiler
        device["window_s"] = win.trace_wall[1] - (
            trace.wall_zero if trace.wall_zero is not None
            else win.trace_wall[0])
        result["breakdown"] = layers.breakdown(win, trace)
    else:
        values = {name: driver.end_to_end(name)(win, cell.traffic)
                  for name in cell.end_to_end if name != "setup_s"}
        values["setup_s"] = setup_s
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    result["device"] = device
    # each number compared beside its limit: last in the result's line, and
    # the last lines of standard error
    result["check"] = compared
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
