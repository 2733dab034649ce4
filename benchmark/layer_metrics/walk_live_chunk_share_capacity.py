"""Kernels: ``walk_live_chunk_share`` in the cells that are judged by their
capacity (it moves ``output_tokens_per_s`` there, not a latency)."""

from .walk_live_chunk_share import read  # noqa: F401
