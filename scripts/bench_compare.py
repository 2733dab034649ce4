#!/usr/bin/env python
"""Bench regression gate: diff normalized bench rows, fail on >10% regressions.

Usage::

    python scripts/bench_compare.py base.json cand.json
    python scripts/bench_compare.py run_*.json              # trajectory form
    python scripts/bench_compare.py --threshold 0.05 base.json cand.json

Each file is a bench record — the driver's raw one-JSON-line output of
``bench.py`` or the driver's wrapper holding it under ``parsed``.
With two files the first is the baseline and the second the candidate; with
more, the LAST file is the candidate and the second-to-last the baseline (the
"did this change regress the bench" question), and the earlier files print as
trajectory context.

Gate metrics (kubeml_tpu.benchmarks.harness.GATE_METRICS): device throughput,
end-to-end throughput, MFU, the serving fraction, the spec-decode
tokens/step + acceptance ratio, and serving latency — each carries its own
DIRECTION metadata (throughputs/ratios are higher-is-better, latencies
lower-is-better), and a candidate more than ``--threshold`` (default 10%)
WORSE than the baseline on ANY of them exits non-zero, which is how
CI/tier-1 consumes this (tests/test_bench_compare.py). A metric missing on
either side (e.g. MFU on unknown hardware) is skipped with a note, never
failed; a candidate carrying an ``error`` row fails outright. Improvements
always pass. Exit codes: 0 pass, 1 regression/error row, 2 nothing
comparable / bad input.

The report prints as one JSON object on stdout (``--out`` also writes it to a
file); human-readable verdict lines go to stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

# the repo root (scripts/..) so the harness import works from any cwd
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kubeml_tpu.benchmarks.harness import GATE_METRICS, normalize_bench_row  # noqa: E402


def load_row(path: Path) -> dict:
    doc = json.loads(Path(path).read_text())
    row = normalize_bench_row(doc)
    row["file"] = str(path)
    return row


def compare(baseline: dict, candidate: dict, threshold: float) -> dict:
    """The gate verdict: per-metric deltas + the list of regressions."""
    checks = []
    regressions = []
    skipped = []
    if candidate.get("error"):
        regressions.append({
            "metric": "error",
            "detail": f"candidate is an error row: {candidate['error']}"})
    for key, (_field, direction) in GATE_METRICS.items():
        base, cand = baseline.get(key), candidate.get(key)
        if base is None or cand is None or base <= 0:
            skipped.append({"metric": key, "baseline": base,
                            "candidate": cand,
                            "reason": "missing or non-positive on one side"})
            continue
        delta = (cand - base) / base
        # direction-aware: "higher" metrics regress when they DROP past the
        # threshold, "lower" metrics (latencies) when they RISE past it
        worse = -delta if direction == "higher" else delta
        check = {"metric": key, "baseline": base, "candidate": cand,
                 "delta": round(delta, 4), "direction": direction}
        checks.append(check)
        if worse > threshold:
            regressions.append({
                "metric": key,
                "detail": f"{key} regressed {worse:.1%} "
                          f"({base:g} -> {cand:g}; threshold {threshold:.0%};"
                          f" {direction}-is-better)"
            })
    return {
        "baseline_file": baseline.get("file"),
        "candidate_file": candidate.get("file"),
        "threshold": threshold,
        "checks": checks,
        "skipped": skipped,
        "regressions": regressions,
        "pass": not regressions and bool(checks),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="fail on >threshold bench regressions")
    parser.add_argument("files", nargs="+",
                        help="bench JSON records, oldest first; the last is "
                             "the candidate, the second-to-last the baseline")
    parser.add_argument("--threshold", type=float, default=0.10,
                        help="max tolerated fractional regression "
                             "(default 0.10)")
    parser.add_argument("--out", default=None,
                        help="also write the JSON report here")
    args = parser.parse_args(argv)
    if len(args.files) < 2:
        print("error: need at least a baseline and a candidate file",
              file=sys.stderr)
        return 2
    try:
        rows = [load_row(Path(f)) for f in args.files]
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    report = compare(rows[-2], rows[-1], args.threshold)
    if len(rows) > 2:
        report["trajectory"] = [
            {k: r.get(k) for k in ("file", "metric", *GATE_METRICS)}
            for r in rows
        ]
    print(json.dumps(report, indent=1))
    if args.out:
        Path(args.out).write_text(json.dumps(report, indent=1))
    for s in report["skipped"]:
        print(f"note: skipped {s['metric']} ({s['reason']})", file=sys.stderr)
    if report["regressions"]:
        for r in report["regressions"]:
            print(f"FAIL: {r['detail']}", file=sys.stderr)
        return 1
    if not report["checks"]:
        print("error: no comparable gate metric on both sides",
              file=sys.stderr)
        return 2
    for c in report["checks"]:
        print(f"ok: {c['metric']} {c['baseline']:g} -> {c['candidate']:g} "
              f"({c['delta']:+.1%})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
