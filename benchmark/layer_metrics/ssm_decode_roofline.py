"""Kernels: the ``ssm_update`` kernel's share of its roofline, %.

The least time the chip could take for the bytes the traced steps' state
updates have to move (``costs/ssm_state.py``: the state read and written
once, ``x``, the decay, ``y``, ``B``, ``C``; memory-bound) over the kernel's
device time in decode programs. The kernel reads and writes every row of the
slab, live or not, so the rows counted are the deployment's
``serving_slots`` and not the live rows of the client's records: see the
costs file."""

from .. import reduce
from ..costs import paged_attention, ssm_state
from ._ssm import kernel_in_steps


def read(r):
    runs = kernel_in_steps(r)
    if not runs:
        return None
    cfg = r.cell.config
    steps = sum(s for s, _ in runs)
    kernel = sum(t for _, t in runs)
    flops, nbytes = ssm_state.decode_step(
        cfg["deployment"]["serving_slots"] * steps, layers=cfg["n_layer"],
        heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
        state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"])
    least = paged_attention.min_seconds(flops, nbytes, r.peaks)[0]
    return reduce.checked_share("ssm_decode_roofline",
                                100.0 * least / kernel)
