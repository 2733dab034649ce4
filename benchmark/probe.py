#!/usr/bin/env python3
"""Many windows on one set-up: how a serving cell's fixed numbers were found.

    python3 benchmark/probe.py --workload gpt2-large.chat --seconds 30 \
        --rates 2,2.5,3,3.5,4           # the sweep for the knee (open loop)
    python3 benchmark/probe.py --workload gpt2-large.chat --seconds 15 \
        --seeds 11,12,13,14,15,16 --control   # readings that set the limits

It is not part of a check: ``run.py`` is. It exists because set-up is most of
a run, and a sweep or a dozen seeds' readings need only one. Every window's
requests are checked against the reference like a run's (``--control`` also
reads the configuration's lower-precision control on the same positions).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    runmod.find_device(cell.chips, True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
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
    summary = {"served_max": [], "control_max": []}
    for rate, seed, win, prompts in wins:
        sampled = check.sample(win.records, seed,
                               cell.traffic["check_requests"])
        got = check.gaps(cell, weights, prompts, sampled, control=control)
        row = {"rate": rate, "seed": seed, "tokens": len(got["served"]),
               "served_gap_max": max(got["served"], default=None),
               "served_gap_mean": float(np.mean(got["served"]))
               if got["served"] else None,
               **check.serving_readings(got["served"], win, sampled)}
        summary["served_max"].append(row["served_gap_max"])
        if control:
            row["control_gap_max"] = max(got["control"])
            row["control_gap_mean"] = float(np.mean(got["control"]))
            summary["control_max"].append(row["control_gap_max"])
        print(json.dumps(row), flush=True)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
