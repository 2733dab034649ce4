"""Serving control: of the experts a decode step could read, the share its
live rows chose, %: ``moe_experts_touched`` over ``device_steps x moe_layers
x n_routed_experts``, from the engine's counts over the window. The expert
weights a step streams follow it. With every row live and even routing, 32
rows of 4 choices touch 64 x (1 - (63/64)^128) = 55.5 of 64 experts, 87%;
rows that idle or routing that is skewed read lower."""

from .. import reduce
from ._moe import per_step


def read(r):
    counts = per_step(r)
    layers = r.win.counters[1].get("moe_layers", 0)
    if counts is None or not layers:
        return None
    return reduce.checked_share(
        "moe_touched_share", 100.0 * counts[0]
        / (float(layers) * r.cell.config["n_routed_experts"]))
