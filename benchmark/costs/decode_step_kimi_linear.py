"""``costs/decode_step.py`` for the ``kimi_linear`` family, whose token mixer
differs by layer UNDER latent attention and whose experts are a chip's share
(a configuration names this module as its ``"step_costs"``):
``linear_attn_config`` says which layers walk a latent arena
(``full_attn_layers``) and which carry a recurrent state and no pages
(``kda_layers``).

The count is the default's but for the cache and the names: every array of
the builder's ``shapes(cfg)`` but the embedding table read once and
multiplied by every live row, the routed experts left out of that and
counted by ``costs/moe_experts.py`` from what the program says its rows
chose (the experts HELD here: ``num_experts`` in the file is this chip's
share, and ``assignments`` are those given to held experts; a choice of an
expert on another chip costs this chip nothing); the live latents of the
latent-attention layers alone (``mla_latent.py``); and the KDA layers' state
read once and written once a live row (``kda_state.py``; the kernel moves
dead rows too, which a least time does not count)."""

import math

from . import kda_state, mla_latent, moe_experts
from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_ROUTED = ("e_gate", "e_up", "e_down")


def layer_kinds(cfg: dict) -> tuple:
    """(latent-attention layers, KDA layers) as run."""
    lin = cfg["linear_attn_config"]
    return len(lin["full_attn_layers"]), len(lin["kda_layers"])


def weight_elements(shapes: dict) -> int:
    """Elements every step multiplies by: all but the embedding table and
    the routed experts."""
    return sum(math.prod(entry[0]) for name, entry in shapes.items()
               if entry[1] != "embed" and name not in _ROUTED)


def kda_sizes(cfg: dict) -> dict:
    """The KDA layers' sizes as ``kda_state.decode_step`` takes them."""
    lin = cfg["linear_attn_config"]
    return dict(layers=layer_kinds(cfg)[1], heads=lin["num_heads"],
                key_dim=lin["head_dim"], value_dim=lin["head_dim"])


def cache(cfg: dict, rows: float, depth_tokens: float) -> dict:
    """kind -> (flops, bytes): the latent layers' walk over the rows'
    latents, the KDA layers' state of the rows."""
    return {
        "latent": mla_latent.decode_step(
            depth_tokens, layers=layer_kinds(cfg)[0],
            heads=cfg["num_attention_heads"],
            latent=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            value=cfg["kv_lora_rank"]),
        "kda": kda_state.decode_step(rows, **kda_sizes(cfg))}


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
