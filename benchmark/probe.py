#!/usr/bin/env python3
"""Many windows on one set-up: how a serving cell's fixed numbers were found.

    python3 benchmark/probe.py --workload gpt2-large.chat --seconds 30 \
        --rates 2,2.5,3,3.5,4           # the sweep for the knee (open loop)
    python3 benchmark/probe.py --workload gpt2-large.chat --seconds 15 \
        --seeds 11,12,13,14,15,16 --control   # readings that set the limits
    python3 benchmark/probe.py --workload glm-4.7-flash.rag --seconds 15 \
        --seeds 21,22,23 --control --weights-per-seed   # ... on 3 weights

It is not part of a check: ``run.py`` is. It exists because set-up is most of
a run, and a sweep or a dozen seeds' readings need only one. Every window's
requests are checked against the reference like a run's (``--control`` also
reads the configuration's lower-precision control on the same positions, and
says whether the cell's limits would call each of the two ``correct``).
One JSON line per window, the last line a summary."""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from benchmark import check, run as runmod, serving, spec  # noqa: E402


def _halves(win) -> list:
    """p90 of ttft over the requests due in each half of the window: a
    backlog that grows shows as a second half far above the first."""
    out = []
    for lo, hi in ((0.0, 0.5), (0.5, 1.0)):
        v = [1000.0 * (r["first"] - r["due"]) for r in win.records
             if not r["error"] and r["first"] is not None
             and lo * win.seconds <= r["due"] < hi * win.seconds]
        out.append(float(np.percentile(v, 90)) if v else None)
    return out


def _one_setup(cell, seeds: list, rates: list, args) -> list:
    """One set-up with the weights of ``seeds[0]``, a window for each rate
    and seed on it, then the reference over every window's sample: a row of
    readings a window."""
    system = serving.ServingSystem(cell, seeds[0])
    wins = []
    try:
        t0 = time.time()
        notes = system.setup()
        print(json.dumps({"setup_s": time.time() - t0, **notes}), flush=True)
        for rate in rates:
            for seed in seeds:
                win = system.window(args.seconds, seed, False,
                                    rate_per_s=rate)
                prompts = {r["id"]: r["prompt"]
                           for r in system.last_requests}
                wins.append((rate, seed, win, prompts))
                row = {k: serving.end_to_end(k)(win, cell.traffic)
                       for k in ("ttft_p50_ms", "ttft_p70_ms", "ttft_p90_ms",
                                 "tpot_p70_ms", "tpot_p90_ms",
                                 "output_tokens_per_s")}
                done = [r for r in win.records if not r["error"]]
                row.update(
                    rate=rate, seed=seed, attempted=len(win.records),
                    failed=len(win.records) - len(done),
                    done_in_window=sum(r["last"] <= win.seconds
                                       for r in done),
                    ttft_p90_halves_ms=_halves(win),
                    memory_peak_bytes=win.memory_peak_bytes,
                    compiles=win.counters[1]["compiled_programs"]
                    - win.counters[0]["compiled_programs"])
                print(json.dumps(row), flush=True)
    finally:
        left = system.teardown()
        system.cleanup()
    print(json.dumps({"bytes_left_on_device": left}), flush=True)
    weights = system.builder.init_weights(cell.config, seeds[0])
    control = cell.config["lower_precision_control"] if args.control else None
    limits = check.limits_for(cell.name)
    rows = []
    for rate, seed, win, prompts in wins:
        sampled = check.sample(win.records, seed,
                               cell.traffic["check_requests"])
        got = check.gaps(cell, weights, prompts, sampled, control=control)
        readings = check.serving_readings(got["served"], win, sampled)
        row = {"rate": rate, "seed": seed, "weights_seed": seeds[0],
               "tokens": len(got["served"]), **readings,
               "correct": check.compare(readings, limits)[0]}
        if control:
            # the control put in the program's place: its gaps through the
            # same readings and limits have to come out as not correct
            low = check.serving_readings(got["control"], win, sampled)
            row.update(control_gap_max=low["logit_gap_max"],
                       control_gap_mean=low["logit_gap_mean"],
                       control_correct=check.compare(low, limits)[0])
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


# the readings of a window's row that the last line lists by window
_SUMMARY = ("logit_gap_max", "logit_gap_mean", "correct", "control_gap_max",
            "control_gap_mean", "control_correct")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--weights-per-seed", action="store_true",
                    help="a set-up of its own, with its own weights, for "
                         "every seed (a limit is set from a dozen weight "
                         "seeds); without it every window runs on the "
                         "weights of the first seed")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    runmod.find_device(cell.chips, True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    groups = [[s] for s in seeds] if args.weights_per_seed else [seeds]
    rows = [row for group in groups
            for row in _one_setup(cell, group, rates, args)]
    summary = {key: [row[key] for row in rows]
               for key in _SUMMARY if key in rows[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
