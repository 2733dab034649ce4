"""Operations and bytes a whole admission program needs, from shapes: the
least the chip could do to prefill one row of ``bucket`` positions, ``real``
of them a prompt's (the rest the bucket's padding, which the program's
shape makes it compute everywhere but in the routed experts).

- every matrix the positions multiply by, but the embedding tables, the
  routed experts and the output head (the builder's ``shapes(cfg)``, as
  ``costs/decode_step.py`` reads them): 2 operations an element and bucket
  position, read once at the compute type's bytes;
- the output head at ONE position (the first token is sampled from the last
  real position alone; a program that multiplies the whole bucket by the
  head reads a lower share), its matrix read once;
- the routed experts by assignment: ``top_k`` a real position and expert
  layer, ``6 x hidden x width`` operations each; with thousands of
  assignments every expert is chosen, so every expert's matrices are read
  once;
- latent attention in the expanded form: every head scores a position
  against the positions up to it (``dn + dr`` wide) and sums their values
  (``dv``): ``2 x heads x (dn + dr + dv)`` operations a causal pair and
  layer; the latents are written once, 2 B a value;
- the residual streams as ``costs/hc_streams.py`` counts them, two
  sub-layers a layer (``hc_mult``; nothing for a single stream).

The larger of operations over the compute peak and bytes over the HBM peak
(``min_seconds``): at Xing4.0's widths and 2,048 positions the two lie
close, 11.7 ms of operations against 12.7 ms of bytes (the 64 experts of
five layers are 7 GB read for 8,192 assignments a layer). Activations
between products are left out: a lower bound on the work, so a share over
100% is a fault in the count or in the time."""

import math

from . import hc_streams
from .decode_step import _BYTES, routed_expert_elements
from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def admit(cfg: dict, shapes: dict, bucket: float, real: float) -> tuple:
    """(flops, bytes) of one admission of ``real`` prompt positions in a
    program of ``bucket`` positions."""
    width = _BYTES[cfg["compute_dtype"]]
    head = math.prod(shapes["lm_head"][0])
    dense = sum(math.prod(entry[0]) for entry in shapes.values()
                if entry[1] != "embed") - head - routed_expert_elements(cfg)
    flops = 2.0 * dense * bucket + 2.0 * head
    nbytes = float(dense + head) * width
    layers = cfg["num_hidden_layers"]
    if "n_routed_experts" in cfg:
        expert_layers = layers - cfg["first_k_dense_replace"]
        flops += (6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
                  * cfg["num_experts_per_tok"] * real * expert_layers)
        nbytes += float(routed_expert_elements(cfg)) * width
    pairs = 0.5 * bucket * (bucket + 1.0)
    flops += (2.0 * cfg["num_attention_heads"]
              * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                 + cfg["v_head_dim"]) * pairs * layers)
    nbytes += (float(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * width
               * bucket * layers)
    if cfg.get("hc_mult"):
        f, b = hc_streams.mixed(2.0 * layers * bucket,
                                streams=cfg["hc_mult"],
                                hidden=cfg["hidden_size"], act_bytes=width)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
