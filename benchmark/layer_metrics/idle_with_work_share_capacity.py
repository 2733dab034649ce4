"""Device: ``idle_with_work_share`` in the cells that are judged by their
capacity (it moves ``output_tokens_per_s`` there)."""

from .idle_with_work_share import read  # noqa: F401
