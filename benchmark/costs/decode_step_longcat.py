"""``costs/decode_step.py`` for the ``longcat_flash`` family, whose published
``config`` counts double layers (``num_layers``, none of them a leading dense
one) and names the experts' width ``expert_ffn_hidden_size`` (a configuration
names this module as its ``"step_costs"``).

The count is the default's, read under this family's names: every array of
the builder's ``shapes(cfg)`` but the embedding table read once and
multiplied by every live row, the routed experts left out of that and
counted by ``costs/moe_experts.py`` from what the program says its rows
chose, the live latents of all ``n_layer`` attention sub-layers (two a double
layer) as ``mla_latent.py`` counts them. The experts are the ones HELD here
(``n_routed_experts`` in the file is this chip's share), and ``assignments``
are those given to held experts: a choice of an expert on another chip costs
this chip nothing, and an identity (zero-compute) expert's ``hidden``
multiply-adds a choice are left out (a lower bound on the work)."""

from . import decode_step as default
from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def _as_default(cfg: dict) -> dict:
    """The file under the key names ``costs/decode_step.py`` reads."""
    return {**cfg, "num_hidden_layers": cfg["num_layers"],
            "first_k_dense_replace": 0,
            "moe_intermediate_size": cfg["expert_ffn_hidden_size"]}


def routed_expert_elements(cfg: dict) -> int:
    """Elements of the held routed experts' matrices, all double layers."""
    return default.routed_expert_elements(_as_default(cfg))


def decode_step(cfg: dict, shapes: dict, rows: float, depth_tokens: float,
                touched: float = 0.0, assignments: float = 0.0) -> tuple:
    """(flops, bytes) of one decode step, as ``costs/decode_step.py
    decode_step`` counts them."""
    return default.decode_step(_as_default(cfg), shapes, rows, depth_tokens,
                               touched, assignments)
