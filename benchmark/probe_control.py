#!/usr/bin/env python3
"""``probe.py`` with another control in ``lower_precision_control``'s place.

    python3 benchmark/probe_control.py held_zero --workload \
        longcat-flash-omni.agent --seconds 15 --seeds 31,32,33 --control

A configuration names one control, the whole reference in a lower precision.
A reference may know others (``reference/longcat_flash.py``: ``held_zero``,
``held_fp8_e4m3``, a fault in this chip's share of the experts alone): this
runs ``probe.py`` as it is, every argument after the first handed on, on the
cell's configuration with that one key replaced, so a planted fault is read
through the same windows, readings and limits as the stated control. The
control has to come out as not ``correct`` (``control_correct`` false)."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import probe, spec  # noqa: E402


def main(argv=None) -> int:
    control, *rest = sys.argv[1:] if argv is None else argv
    load = spec.load_cell

    def with_control(workload: str) -> spec.Cell:
        cell = load(workload)
        return dataclasses.replace(cell, config={
            **cell.config, "lower_precision_control": control})

    spec.load_cell = with_control
    try:
        return probe.main(rest)
    finally:
        spec.load_cell = load


if __name__ == "__main__":
    sys.exit(main())
