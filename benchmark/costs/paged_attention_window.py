"""Operations and bytes the page-walk decode kernel needs for a layer whose
K heads and V heads have widths of their own, and whose queries may see a
window of keys only, from shapes.

One decode step of one row that sees ``n`` keys (its depth, the new token
included; in a window layer at most ``window`` of them), per layer: scores
q.K^T are ``2 * q_heads * k_dim * n`` floating-point operations and the
weighted sum p.V ``2 * q_heads * v_dim * n`` (every query head does its
own); the K and V of the ``n`` keys are each read once, ``kv_heads * (k_dim
+ v_dim) * kv_bytes`` bytes a key (a K/V head is read once for the query
heads that share it). The bytes are the VALUES a key holds (320 a K/V head
at 192 + 128, which is what the arena stores), whatever pages of a ring a
step fetches beyond its window. Queries, outputs, the sink and
the page table are left out (a lower bound on the work), so a share over
100% is a fault in the count or in the time."""

from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def seen_keys(rows: float, depth_tokens: float, window: int = 0) -> float:
    """Keys the queries of ``rows`` live rows see between them in one layer:
    all ``depth_tokens`` of their caches, or under a ``window`` at most that
    many a row (the rows' mean depth stands for each row's: the cell's rows
    are all far past the window or all short of it)."""
    if not window or rows <= 0:
        return depth_tokens
    return rows * min(depth_tokens / rows, float(window))


def decode_step(seen: float, *, layers: int, q_heads: int, kv_heads: int,
                k_dim: int, v_dim: int, kv_bytes: int = 2) -> tuple:
    """(flops, bytes) of the kernel for ``seen`` keys summed over the rows
    of a step, all ``layers`` of the kind."""
    flops = 2.0 * q_heads * (k_dim + v_dim) * seen * layers
    nbytes = float(kv_heads) * (k_dim + v_dim) * kv_bytes * seen * layers
    return flops, nbytes
