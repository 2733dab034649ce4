#!/usr/bin/env python3
"""One traced window of a serving cell, read as a timeline: where the first
token's time and a token's time go, and how well the program's clock and
its service-time estimate agree with the device trace.

    python3 benchmark/timeline.py --workload gpt2-large.chat --seed 7 \
        --seconds 50 --out chiprun_out/timeline
    python3 benchmark/timeline.py --from chiprun_out/timeline/<file>.json

It is not part of a check: ``run.py`` is. It makes the window ``run.py
--trace 1`` makes, keeps what that throws away (the window's spans, the
profiler's host annotations, the load generator's records) in one JSON file
under ``--out``, and prints ``summarize`` of it as one JSON line.
``--from`` summarizes a kept file again, on any machine. PERF.md section 5
has the budgets it was written for (PR 24)."""

from __future__ import annotations

import argparse
import glob
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import layers, reduce, run as runmod, serving, spec  # noqa: E402
from benchmark.layer_metrics._programs import (  # noqa: E402
    PREFILL_MODULES, STEP_MODULES)
from benchmark.layer_metrics._spans import median_ms as _median_ms  # noqa: E402

DISPATCH = "engine.dispatch"


def host_annotations(trace_dir: Path, name: str) -> list:
    """[(seq, start_s, duration_s)] of the profiler's own host events called
    ``name`` (the program's TraceAnnotation around each jitted call)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    seq = dict(e.stats).get("seq")
                    out.append((seq, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def collect(workload: str, seed: int, seconds: float) -> dict:
    cell = spec.load_cell(workload)
    device, peaks = runmod.find_device(cell.chips, True)
    system = serving.ServingSystem(cell, seed)
    try:
        try:
            system.setup()
            win = system.window(seconds, seed, True)
        finally:
            system.teardown()
        trace = reduce.load(win.trace_dir, mark_wall=win.trace_mark_wall)
        plane = layers.Reading(cell, win, trace, peaks).device_plane()
        return {
            "workload": workload, "seed": seed, "device": device,
            "t_open": win.t_open, "seconds": win.seconds,
            "trace_wall": win.trace_wall, "wall_zero": trace.wall_zero,
            "busy_s": reduce.busy_seconds(trace),
            "end_to_end": {n: serving.end_to_end(n)(win, cell.traffic)
                           for n in cell.end_to_end if n != "setup_s"},
            "per_layer": layers.read_all(cell, win, trace, peaks),
            "breakdown": layers.breakdown(win, trace),
            # what the breakdown's idle_gaps were named from: every idle
            # stretch between the first and the last device operation
            "idle_gaps": reduce.idle_gaps(
                trace.rows(plane, reduce.OPS_LINE)),
            "records": [{k: v for k, v in r.items() if k != "tokens"}
                        | {"n_tokens": len(r["tokens"])}
                        for r in win.records],
            "spans": win.spans,
            "annotations": host_annotations(win.trace_dir, DISPATCH),
            "step_modules": reduce.executions(trace, plane, STEP_MODULES),
            "prefill_modules": reduce.executions(trace, plane,
                                                 PREFILL_MODULES),
        }
    finally:
        system.cleanup()


def _matched(modules: list, fetches: list, zero: float) -> list:
    """(device seconds, svc_s, width) of each device execution and the
    program whose estimated start (end of its fetch less ``svc_s``) lies
    nearest, within 10 ms."""
    starts = [(s["start"] + s["duration"] - s["attrs"]["svc_s"] - zero, s)
              for s in fetches]
    out = []
    for start, dur in modules:
        near = min(starts, key=lambda p: abs(p[0] - start), default=None)
        if near is not None and abs(near[0] - start) < 0.010:
            out.append((dur, near[1]["attrs"]["svc_s"], near[1]["width"]))
    return out


def summarize(d: dict) -> dict:
    lo, hi = d["t_open"], d["t_open"] + d["seconds"]
    zero = d["wall_zero"]
    spans = d["spans"]
    named = lambda name: [s for s in spans if s["name"] == name]
    inside = lambda name: [s for s in named(name) if lo <= s["start"] <= hi]
    width = {s["attrs"]["seq"]: s["attrs"]["width"] for s in named(DISPATCH)}
    fetches = [s | {"width": width.get(s["attrs"]["seq"])}
               for s in named("engine.fetch")]
    out = {"workload": d["workload"], "seed": d["seed"],
           "end_to_end": d["end_to_end"], "per_layer": d["per_layer"],
           "idle_share": 1.0 - d["busy_s"] / (d["trace_wall"][1] - zero),
           "idle_gaps": d["breakdown"]["idle_gaps"]}

    # the tie: the tracer's engine.dispatch spans against the profiler's
    # own annotations of the same calls, both as seconds of trace time
    mine = {s["attrs"]["seq"]: s["start"] - zero for s in named(DISPATCH)}
    err = [mine[seq] - start for seq, start, _ in d["annotations"]
           if seq in mine]
    out["clock_tie"] = {
        "calls": len(err), "median_ms": _median_ms(err),
        "worst_ms": 1000.0 * max(err, key=abs) if err else None,
        "spread_ms": 1000.0 * (max(err) - min(err)) if err else None}

    # the estimator: svc_s of a program against the device's time for it
    steps = _matched(d["step_modules"],
                     [f for f in fetches if f["attrs"]["program"] == "step"],
                     zero)
    by_width = {}
    for dev, svc, w in steps:
        by_width.setdefault(w, []).append((dev, svc))
    out["step_by_width"] = {
        str(w): {"programs": len(v),
                 "device_ms": _median_ms([a for a, _ in v]),
                 "svc_ms": _median_ms([b for _, b in v])}
        for w, v in sorted(by_width.items())}
    admits = _matched(d["prefill_modules"],
                      [f for f in fetches if f["attrs"]["program"] == "admit"],
                      zero)
    out["admit_in_trace"] = {
        "programs": len(admits),
        "device_ms": _median_ms([a for a, _, _ in admits]),
        "svc_ms": _median_ms([b for _, b, _ in admits])}
    window_steps = [f for f in fetches if f["attrs"]["program"] == "step"
                    and lo <= f["start"] <= hi]
    out["step_svc_p50_ms"] = _median_ms(
        [f["attrs"]["svc_s"] for f in window_steps])

    # first token: due -> sent -> server -> submit -> slot -> device -> host
    ok = [r for r in d["records"] if not r["error"] and r["first"] is not None]
    terms = {
        "gen_lag": _median_ms([r["sent"] - r["due"] for r in ok]),
        "entry_hop": d["per_layer"].get("entry_hop_p50_ms"),
        "queue_wait": d["per_layer"].get("queue_wait_p50_ms"),
        "admit_wait": d["per_layer"].get("admit_wait_p50_ms"),
        "admit_svc": d["per_layer"].get("admit_svc_p50_ms")}
    ttft = d["end_to_end"].get("ttft_p50_ms")
    if ttft is not None and None not in terms.values():
        terms["residual"] = ttft - sum(terms.values())
    out["ttft_budget_ms"] = {"ttft_p50_ms": ttft, **terms}

    # a token: the step at the widths the window's steps ran at, the stall
    # behind others' prefills, and what is left
    n_by_width = {}
    for f in window_steps:
        n_by_width[f["width"]] = n_by_width.get(f["width"], 0) + 1
    share = {str(w): n / len(window_steps)
             for w, n in sorted(n_by_width.items())} if window_steps else {}
    known = {w: out["step_by_width"][w]["device_ms"] for w in share
             if w in out["step_by_width"]}
    step_ms = (sum(share[w] * known[w] for w in known)
               / sum(share[w] for w in known)) if known else None
    tpot = d["end_to_end"].get("tpot_mean_ms")
    stall = d["per_layer"].get("stall_ms_per_token")
    budget = {"tpot_mean_ms": tpot, "steps_by_width": share,
              "step_dev_ms_weighted": step_ms, "stall_ms_per_token": stall}
    if None not in (tpot, step_ms, stall):
        budget["residual"] = tpot - step_ms - stall
    out["tpot_budget_ms"] = budget

    # the engine thread, by span, over the window
    out["engine_thread_s"] = {
        name: sum(s["duration"] for s in inside(name))
        for name in ("engine.wait_work", "engine.admit", DISPATCH,
                     "engine.wait_result", "engine.process")}
    out["spans_per_s"] = sum(lo <= s["start"] <= hi
                             for s in spans) / d["seconds"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default="chiprun_out/timeline")
    ap.add_argument("--from", dest="kept")
    args = ap.parse_args(argv)
    if args.kept:
        d = json.loads(Path(args.kept).read_text())
    else:
        d = collect(args.workload, args.seed, args.seconds)
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{args.workload}.{args.seed}.json").write_text(json.dumps(d))
    print(json.dumps(summarize(d)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
