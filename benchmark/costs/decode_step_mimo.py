"""``costs/decode_step.py`` for the ``mimo_v2`` family, whose attention
differs by layer (a configuration names this module as its ``"step_costs"``).

The count is the default's but for the cache: every array of the builder's
``shapes(cfg)`` but the embedding table read once and multiplied by every
live row; the routed experts (``e_gate``, ``e_up``, ``e_down``: the ones
HELD here, ``n_routed_experts`` in the file is this chip's share) left out
of that and counted by ``costs/moe_experts.py`` from what the program says
its rows chose (``assignments`` are those given to held experts: a choice of
an expert on another chip costs this chip nothing); and the live K/V BY
LAYER KIND (``costs/paged_attention_window.py``): a full layer's rows read
their whole depth at ``num_key_value_heads`` x (192 + 128) values a token, a
window layer's at most ``sliding_window`` keys a row at
``swa_num_key_value_heads`` x 320."""

import math

from . import moe_experts, paged_attention_window
from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_ROUTED = ("e_gate", "e_up", "e_down")


def layer_kinds(cfg: dict) -> tuple:
    """(full layers, window layers) as run: the first ``num_hidden_layers``
    entries of ``hybrid_layer_pattern``."""
    pattern = cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]]
    window = sum(1 for k in pattern if k)
    return len(pattern) - window, window


def routed_expert_elements(shapes: dict) -> int:
    """Elements of the held routed experts' matrices, all expert layers."""
    return sum(math.prod(shapes[name][0]) for name in _ROUTED)


def weight_elements(shapes: dict) -> int:
    """Elements every step multiplies by: all but the embedding table and
    the routed experts."""
    return sum(math.prod(entry[0]) for name, entry in shapes.items()
               if entry[1] != "embed" and name not in _ROUTED)


def cache(cfg: dict, rows: float, depth_tokens: float) -> dict:
    """kind -> (flops, bytes) of the rows' attention over their caches, all
    layers of the kind."""
    full, window = layer_kinds(cfg)
    at = dict(q_heads=cfg["num_attention_heads"], k_dim=cfg["head_dim"],
              v_dim=cfg["v_head_dim"])
    return {
        "full": paged_attention_window.decode_step(
            depth_tokens, layers=full, kv_heads=cfg["num_key_value_heads"],
            **at),
        "window": paged_attention_window.decode_step(
            paged_attention_window.seen_keys(rows, depth_tokens,
                                             cfg["sliding_window"]),
            layers=window, kv_heads=cfg["swa_num_key_value_heads"], **at)}


def decode_step(cfg: dict, shapes: dict, rows: float, depth_tokens: float,
                touched: float = 0.0, assignments: float = 0.0) -> tuple:
    """(flops, bytes) of one decode step, as ``costs/decode_step.py
    decode_step`` counts them."""
    elements = weight_elements(shapes)
    flops = 2.0 * elements * rows
    nbytes = float(elements) * _BYTES[cfg["compute_dtype"]]
    for f, b in (*cache(cfg, rows, depth_tokens).values(),
                 moe_experts.decode_steps(
                     touched, assignments, hidden=cfg["hidden_size"],
                     width=cfg["moe_intermediate_size"])):
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
