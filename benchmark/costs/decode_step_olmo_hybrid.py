"""``costs/decode_step.py`` for the ``olmo_hybrid`` family, whose token mixer
differs by layer (a configuration names this module as its ``"step_costs"``):
``layer_types`` says which layers page K and V (``full_attention``) and which
carry a recurrent state and no pages (``linear_attention``).

The count is the default's but for the cache: every array of the builder's
``shapes(cfg)`` but the embedding table read once and multiplied by every
live row; the live K/V of the FULL layers alone (``paged_attention_gqa.py``
at ``num_key_value_heads`` x ``head_dim``); and the LINEAR layers' state read
once and written once a live row (``gdn_state.py``; the kernel moves dead
rows too, which a least time does not count)."""

import math

from . import gdn_state, paged_attention_gqa
from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_kinds(cfg: dict) -> tuple:
    """(full-attention layers, linear-attention layers) as run."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    linear = sum(1 for k in kinds if k == "linear_attention")
    return len(kinds) - linear, linear


def weight_elements(shapes: dict) -> int:
    """Elements every step multiplies by: all but the embedding table."""
    return sum(math.prod(entry[0]) for entry in shapes.values()
               if entry[1] != "embed")


def gdn_sizes(cfg: dict) -> dict:
    """The linear layers' sizes as ``gdn_state.decode_step`` takes them."""
    return dict(layers=layer_kinds(cfg)[1],
                heads=cfg["linear_num_value_heads"],
                key_dim=cfg["linear_key_head_dim"],
                value_dim=cfg["linear_value_head_dim"])


def cache(cfg: dict, rows: float, depth_tokens: float) -> dict:
    """kind -> (flops, bytes): the full layers' attention over the rows'
    caches, the linear layers' state of the rows."""
    return {
        "full": paged_attention_gqa.decode_step(
            depth_tokens, layers=layer_kinds(cfg)[0],
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"]),
        "linear": gdn_state.decode_step(rows, **gdn_sizes(cfg))}


def decode_step(cfg: dict, shapes: dict, rows: float, depth_tokens: float,
                touched: float = 0.0, assignments: float = 0.0) -> tuple:
    """(flops, bytes) of one decode step, as ``costs/decode_step.py
    decode_step`` counts them (``touched`` and ``assignments`` are the
    expert families': this one routes nothing)."""
    elements = weight_elements(shapes)
    flops = 2.0 * elements * rows
    nbytes = float(elements) * _BYTES[cfg["compute_dtype"]]
    for f, b in cache(cfg, rows, depth_tokens).values():
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
