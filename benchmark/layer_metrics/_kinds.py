"""The page walk's calls of a decode program told apart by the layer's kind
of attention, for a configuration whose attention differs by layer
(``hybrid_layer_pattern`` in its file: 0 a full layer, 1 a window layer).

Every layer of both kinds calls the one kernel under the one name
(``%attn.<n>``, ``_programs.KERNEL_FAMILY``), once a layer and step, in the
stack's order; so the ``i``-th call of a step is layer ``i mod n_layer``'s,
and the pattern says which kind that is. A configuration without the key
(every other family) gives the readers nothing: they return None."""

from .. import reduce, spec
from ._programs import KERNEL_FAMILY, STEP_MODULES
from .paged_decode_roofline import live


def pattern(cfg: dict) -> list | None:
    """True for each layer as run that is a window layer; None where the
    configuration's attention does not differ by layer."""
    if "hybrid_layer_pattern" not in cfg:
        return None
    return [bool(k) for k in
            cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]]]


def step_runs(r) -> list:
    """(start_s, duration_s, steps, window_seconds, full_seconds) of each
    decode-program execution wholly inside the trace: the kernel's time in
    it by the kind of the layer that called."""
    kinds = pattern(r.cell.config)
    plane = r.device_plane()
    if kinds is None or plane is None:
        return []
    n = len(kinds)
    calls = sorted((s, d) for name, s, d in r.trace.rows(plane,
                                                         reduce.OPS_LINE)
                   if reduce.family(name) == KERNEL_FAMILY)
    out, i = [], 0
    for start, dur in sorted(reduce.executions(r.trace, plane, STEP_MODULES)):
        while i < len(calls) and calls[i][0] < start:
            i += 1
        j = i
        while j < len(calls) and calls[j][0] + calls[j][1] <= start + dur:
            j += 1
        inside, i = calls[i:j], j
        if not inside or len(inside) % n:
            continue
        by_kind = [0.0, 0.0]
        for k, (_, seconds) in enumerate(inside):
            by_kind[0 if kinds[k % n] else 1] += seconds
        out.append((start, dur, len(inside) // n, by_kind[0], by_kind[1]))
    return out


def roofline(r, kind: str, name: str):
    """The least time to read a kind's live keys and values once over the
    kind's kernel time in the traced decode programs, %."""
    runs = step_runs(r)
    if not runs or r.trace.wall_zero is None:
        return None
    cfg = r.cell.config
    costs = spec.plugin("costs", cfg["step_costs"])
    shift = r.trace.wall_zero - r.win.t_open   # trace time -> window time
    least = kernel = 0.0
    for start, dur, steps, window_s, full_s in runs:
        rows, depth = live(r.win.records, shift + start + 0.5 * dur)
        flops, nbytes = costs.cache(cfg, rows, depth)[kind]
        least += steps * costs.min_seconds(flops, nbytes, r.peaks)[0]
        kernel += window_s if kind == "window" else full_s
    if kernel <= 0.0:
        return None
    return reduce.checked_share(name, 100.0 * least / kernel)


def counter_share(r, part: str, whole: str):
    """100 x the growth of counter ``part`` over that of ``whole`` in the
    window; None where the program has no such counters."""
    c0, c1 = r.win.counters
    if any(k not in c0 or k not in c1 for k in (part, whole)):
        return None
    total = r.counter(whole)
    if total <= 0:
        return None
    return 100.0 * r.counter(part) / total
