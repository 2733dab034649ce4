"""Where a decode step's expert assignments went, from the program's own
counts over the window: to experts held on this chip (``moe_assignments``:
what enters the grouped product), to identity (zero-compute) experts
(``moe_assignments_zero``), to experts held on other chips
(``moe_assignments_absent``). The three add up to live rows x experts a
token x expert layers a step. A program that does not count the last two
(a commit before PR 41) has no such counter and the readers return None; a
model that holds every expert reads 100 and 0."""

COUNTERS = {"held": "moe_assignments", "zero": "moe_assignments_zero",
            "absent": "moe_assignments_absent"}


def share(r, which: str) -> float | None:
    """100 x the assignments of kind ``which`` over all assignments made in
    the window's decode steps; None without the counters or the steps."""
    c0, c1 = r.win.counters
    if any(k not in c0 or k not in c1 for k in COUNTERS.values()):
        return None
    made = {kind: r.counter(name) for kind, name in COUNTERS.items()}
    total = sum(made.values())
    if total <= 0:
        return None
    return 100.0 * made[which] / total
