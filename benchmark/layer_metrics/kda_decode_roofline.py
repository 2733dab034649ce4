"""Kernels: the ``kda_update`` kernel's share of its roofline, %.

The least time the chip could take for the bytes the traced steps' state
updates have to move (``costs/kda_state.py``: every slab row's state read
once and written once, ``q``, ``k``, ``v``, the gate of ``key_dim`` values a
head and ``beta`` in and ``o`` out; live values, not the lanes of the
kernel's input block; memory-bound) over the kernel's device time in decode
programs. The kernel reads and writes every row of the slab, live or not, so
the rows counted are the deployment's ``serving_slots`` and not the live rows
of the client's records: see the costs file."""

from .. import reduce
from ..costs import decode_step_kimi_linear, kda_state, paged_attention
from ._kda import kernel_in_steps


def read(r):
    runs = kernel_in_steps(r)
    cfg = r.cell.config
    if not runs or "kda_layers" not in cfg.get("linear_attn_config", {}):
        return None
    steps = sum(s for s, _ in runs)
    kernel = sum(t for _, t in runs)
    flops, nbytes = kda_state.decode_step(
        cfg["deployment"]["serving_slots"] * steps,
        **decode_step_kimi_linear.kda_sizes(cfg))
    least = paged_attention.min_seconds(flops, nbytes, r.peaks)[0]
    return reduce.checked_share("kda_decode_roofline",
                                100.0 * least / kernel)
