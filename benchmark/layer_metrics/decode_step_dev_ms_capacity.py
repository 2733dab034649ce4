"""Model step: ``decode_step_dev_ms`` in the cells that are judged by their
capacity (it moves ``output_tokens_per_s`` there, not a latency tail)."""

from .decode_step_dev_ms import read  # noqa: F401
