"""Serving control: ``engine_host_ms_per_step`` in the cells that are judged
by their capacity (it moves ``output_tokens_per_s`` there)."""

from .engine_host_ms_per_step import read  # noqa: F401
