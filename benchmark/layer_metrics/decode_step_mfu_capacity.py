"""Model step: ``decode_step_mfu`` in the cells that are judged by their
capacity (it moves ``output_tokens_per_s`` there, not a time per token)."""

from .decode_step_mfu import read  # noqa: F401
