"""Operations and bytes the ``moe_experts`` grouped products need in decode
steps, from shapes.

An expert is three matrices of ``hidden x width`` (gate, up, down). In a
decode step every live row makes ``top_k`` assignments a layer; the experts
they choose (``touched``, counted by the program a step and layer) have their
weights read once, whatever the rows that share them, and an expert nobody
chose is not read. Each assignment's row goes in ``hidden`` wide, leaves the
gated product ``width`` wide, comes back in and leaves ``hidden`` wide; it
multiplies its row by the three matrices, ``6 x hidden x width`` operations.
At 32 rows of 4 choices over 64 experts the weights are 18.87 MB an expert
against 14 KB an assignment: the bytes over the HBM peak bound it. The router,
the sort and the shared expert are left out (they are not in the kernel), so
a share over 100% is a fault in the count or in the time."""

from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def decode_steps(touched: float, assignments: float, *, hidden: int,
                 width: int, param_bytes: int = 2,
                 act_bytes: int = 2) -> tuple:
    """(flops, bytes) of the kernel for ``touched`` (expert, layer, step)
    triples chosen by ``assignments`` (row, choice, layer, step) tuples."""
    per_expert = 3.0 * hidden * width
    flops = 2.0 * per_expert * assignments
    nbytes = (per_expert * param_bytes * touched
              + 2.0 * (hidden + width) * act_bytes * assignments)
    return flops, nbytes
