"""Operations and bytes a whole decode step needs, from shapes: the least
the chip could do for one step of ``rows`` live rows holding ``depth_tokens``
tokens between them.

A step reads once every weight it multiplies by, and once the cache its rows
attend over or carry:

- the weights: every array of the builder's ``shapes(cfg)`` but the
  embedding tables (kind ``embed``: a step gathers one row a live row, which
  is left out), at the bytes of the configuration's ``compute_dtype``: the
  arithmetic is in that type, so no step needs to read more, whatever type
  the program holds them in (gpt2-large holds float32 and converts every
  step: that shows as a lower share, not as a larger count). Each is
  multiplied by every live row: 2 operations an element and row;
- where experts route (``n_routed_experts``), the routed experts' arrays are
  left out of that and counted by ``costs/moe_experts.py`` from what the
  program says its rows chose (``touched``, ``assignments``, a step): an
  expert nobody chose is not read;
- the live K/V as ``costs/paged_attention.py`` counts it, by K/V heads under
  grouped-query attention (``paged_attention_gqa.py``), or the live latents
  (``kv_lora_rank``: ``mla_latent.py``);
- a mixer's recurrent state (``mamba_n_heads``), read and written once a
  live row (``ssm_state.py``; the kernel moves dead rows too, which a least
  time does not count).

Activations, the page table, the sampled token and the embedding rows are
left out: a lower bound on the work, so a share over 100% is a fault in the
count or in the time. A family whose step needs another count names its own
module in its configuration (``"step_costs": "<module under costs/>"`` with
the same ``decode_step`` function); this one is the default."""

import math

from . import (mla_latent, moe_experts, paged_attention, paged_attention_gqa,
               ssm_state)
from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def routed_expert_elements(cfg: dict) -> int:
    """Elements of the routed experts' matrices, all expert layers."""
    if "n_routed_experts" not in cfg:
        return 0
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return (layers * cfg["n_routed_experts"] * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def weight_elements(cfg: dict, shapes: dict) -> int:
    """Elements every step multiplies by: all but the embedding tables and
    the routed experts."""
    dense = sum(math.prod(entry[0]) for entry in shapes.values()
                if entry[1] != "embed")
    return dense - routed_expert_elements(cfg)


def cache(cfg: dict, rows: float, depth_tokens: float) -> tuple:
    """(flops, bytes) of the rows' attention over their cache and of their
    recurrent state, all layers, by what the configuration has."""
    layers = cfg["n_layer"]
    if "kv_lora_rank" in cfg:
        flops, nbytes = mla_latent.decode_step(
            depth_tokens, layers=layers, heads=cfg["num_attention_heads"],
            latent=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            value=cfg["kv_lora_rank"])
    elif "num_key_value_heads" in cfg:
        flops, nbytes = paged_attention_gqa.decode_step(
            depth_tokens, layers=layers, q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"])
    else:
        flops, nbytes = paged_attention.decode_step(
            depth_tokens, layers=layers, heads=cfg["n_head"],
            head_dim=cfg["n_embd"] // cfg["n_head"])
    if "mamba_n_heads" in cfg:
        f, b = ssm_state.decode_step(
            rows, layers=layers, heads=cfg["mamba_n_heads"],
            head_dim=cfg["mamba_d_head"], state=cfg["mamba_d_state"],
            groups=cfg["mamba_n_groups"])
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def decode_step(cfg: dict, shapes: dict, rows: float, depth_tokens: float,
                touched: float = 0.0, assignments: float = 0.0) -> tuple:
    """(flops, bytes) of one decode step: ``rows`` live rows with
    ``depth_tokens`` tokens in their caches between them; where experts
    route, ``touched`` experts chosen by ``assignments`` (row, choice)
    pairs, all layers added up."""
    elements = weight_elements(cfg, shapes)
    flops = 2.0 * elements * rows
    nbytes = float(elements) * _BYTES[cfg["compute_dtype"]]
    for f, b in (cache(cfg, rows, depth_tokens),
                 moe_experts.decode_steps(
                     touched, assignments, hidden=cfg.get("hidden_size", 0),
                     width=cfg.get("moe_intermediate_size", 0))):
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
