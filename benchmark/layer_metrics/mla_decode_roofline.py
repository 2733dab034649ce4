"""Kernels: the latent page walk's share of its roofline in decode steps, %.

``paged_decode_roofline`` for a latent cache: the least time for the live
latents read once, 1,152 B a live token and layer, with the heads'
operations checked against the compute peak (``costs/mla_latent.py``), over
the walk's device time in decode programs (``mla_attn``, which a trace shows
as ``%attn.<n>``: ``_moe.py``). The depth of the rows a step served is taken
from the client's records, as there."""

from .. import reduce
from ..costs import mla_latent
from ._programs import step_executions
from .paged_decode_roofline import _live_depth


def read(r):
    cfg = r.cell.config
    if "kv_lora_rank" not in cfg:
        return None
    runs = step_executions(r)
    if not runs or r.trace.wall_zero is None:
        return None
    shift = r.trace.wall_zero - r.win.t_open   # trace time -> window time
    least = kernel = 0.0
    for start, dur, steps, seconds in runs:
        depth = _live_depth(r.win.records, shift + start + 0.5 * dur)
        flops, nbytes = mla_latent.decode_step(
            depth * steps, layers=cfg["n_layer"],
            heads=cfg["num_attention_heads"],
            latent=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            value=cfg["kv_lora_rank"])
        least += mla_latent.min_seconds(flops, nbytes, r.peaks)[0]
        kernel += seconds
    if kernel <= 0.0:
        return None
    return reduce.checked_share("mla_decode_roofline", 100.0 * least / kernel)
