"""Operations and bytes the page-walk decode kernel needs under
grouped-query attention, from shapes.

One decode step of one row at depth ``n`` (tokens in its cache, the new one
included), per layer: scores q.K^T and the weighted sum p.V are each
``2 * q_heads * head_dim * n`` floating-point operations (every query head
does its own); the K and V of the ``n`` live tokens are each read once,
``kv_heads * head_dim * kv_bytes`` bytes a token (a K/V head is read once
for the query heads that share it). With ``kv_heads == q_heads`` this is
``costs/paged_attention.py``. Queries, outputs and the page table are left
out (a lower bound on the work: live tokens, not the reserved table), so a
share over 100% is a fault in the count or in the time."""

from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def decode_step(depth_tokens: float, *, layers: int, q_heads: int,
                kv_heads: int, head_dim: int, kv_bytes: int = 2) -> tuple:
    """(flops, bytes) of the kernel for ``depth_tokens`` live tokens summed
    over the rows of a step, all layers."""
    flops = 4.0 * q_heads * head_dim * depth_tokens * layers
    nbytes = 2.0 * kv_heads * head_dim * kv_bytes * depth_tokens * layers
    return flops, nbytes
