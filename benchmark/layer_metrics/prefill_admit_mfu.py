"""Model step: the whole admission program's share of the chip's peak, %.

The least time the chip could take for what the traced admission programs
had to do (``costs/prefill_admit.py``: every matrix product at the bucket's
positions, the routed products by assignment, the expanded attention's
scores and values, against the compute peak; the weights, latents and
streams once against the HBM peak; the larger of the two) over those
programs' device time, the time ``prefill_dev_ms`` takes its median of. It
bounds any claim on the admission's time whatever kernels it is made of, as
``decode_step_mfu`` does for the step.

An admission's positions are the program's own counts over the window
(``prefill_tokens`` and ``prefill_pad_tokens`` over ``admission_waves``: the
mean prompt and the bucket it was padded to). A configuration without
latent attention has no count here: the reader returns None."""

from .. import reduce, spec
from ..costs import prefill_admit
from ._hc import per_admit
from ._programs import PREFILL_MODULES


def read(r):
    cfg = r.cell.config
    plane = r.device_plane()
    real = per_admit(r, "prefill_tokens")
    pad = per_admit(r, "prefill_pad_tokens")
    if plane is None or real is None or pad is None or \
            "kv_lora_rank" not in cfg:
        return None
    runs = [d for _, d in reduce.executions(r.trace, plane, PREFILL_MODULES)]
    if not runs:
        return None
    shapes = spec.plugin("models", cfg["builder"]).shapes(cfg)
    flops, nbytes = prefill_admit.admit(cfg, shapes, real + pad, real)
    least = prefill_admit.min_seconds(flops, nbytes, r.peaks)[0]
    return reduce.checked_share("prefill_admit_mfu",
                                100.0 * least * len(runs) / sum(runs))
