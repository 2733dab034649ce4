#!/usr/bin/env python3
"""Where a decode step's and a prefill program's device time goes, by
operation: one traced window of a serving cell, its device operations split
by the program they ran in.

    python3 benchmark/step_budget.py --workload falcon-h1-34b.turns --seed 7 \
        --seconds 50

It is not part of a check: ``run.py`` is. ``run.py --trace 1`` adds up a
window's operations whatever program they ran in (``breakdown.device_ops``);
this makes the same window and keeps the two kinds of program apart, so that
"the mixer's kernel is 2.5 of a step's 17 ms" can be read off one line.
Prints one JSON line: per kind of program the number of executions in the
trace, the median device time of one, and the operations that took most of
the kind's time, in ms per execution (PERF.md section 5, PR 27)."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import layers, reduce, run as runmod, serving, spec  # noqa: E402
from benchmark.layer_metrics._programs import (  # noqa: E402
    PREFILL_MODULES, STEP_MODULES)


def by_program(trace: reduce.Trace, plane: str, modules: tuple,
               top: int = 12) -> dict:
    """The operations inside the executions of ``modules`` wholly in the
    trace, each charged its own time (``reduce.self_seconds``)."""
    runs = sorted(reduce.executions(trace, plane, modules))
    rows = sorted(trace.rows(plane, reduce.OPS_LINE), key=lambda r: r[1])
    inside, i = [], 0
    for start, dur in runs:
        while i < len(rows) and rows[i][1] < start:
            i += 1
        while i < len(rows) and rows[i][1] + rows[i][2] <= start + dur:
            inside.append(rows[i])
            i += 1
    if not runs:
        return {"executions": 0}
    own: dict = {}
    for name, seconds in reduce.self_seconds(inside):
        k = reduce.family(name)
        own[k] = own.get(k, 0.0) + seconds
    n = len(runs)
    return {"executions": n,
            "median_ms": 1000.0 * statistics.median(d for _, d in runs),
            "ops_ms_per_execution": [
                [k, 1000.0 * v / n] for k, v in
                sorted(own.items(), key=lambda kv: -kv[1])[:top]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device, peaks = runmod.find_device(cell.chips, True)
    system = serving.ServingSystem(cell, args.seed)
    try:
        try:
            system.setup()
            win = system.window(args.seconds, args.seed, True)
        finally:
            system.teardown()
        trace = reduce.load(win.trace_dir, mark_wall=win.trace_mark_wall)
        plane = layers.Reading(cell, win, trace, peaks).device_plane()
        out = {"workload": args.workload, "seed": args.seed,
               "busy_s": reduce.busy_seconds(trace),
               "end_to_end": {n: serving.end_to_end(n)(win, cell.traffic)
                              for n in cell.end_to_end if n != "setup_s"},
               "per_layer": layers.read_all(cell, win, trace, peaks),
               "step": by_program(trace, plane, STEP_MODULES),
               "prefill": by_program(trace, plane, PREFILL_MODULES)}
    finally:
        system.cleanup()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
