"""Operations and bytes the latent page walk (``mla_attn``) needs in decode
steps, from shapes.

One decode step of one row at depth ``n`` (tokens in its cache, the new one
included), per layer: the ``n`` live latents are read once, ``latent x
kv_bytes`` bytes a token (compressed K/V and the shared rope key: 576 x 2 =
1,152 B), for all heads and for scores and values alike. Every head scores
its query against each latent (``2 x latent`` operations) and sums the
latent's value part (``2 x value``): ``2 x heads x (latent + value)``
operations a token, 34 a byte at 20 heads, so unlike the per-head page walks
(2 a byte) the compute peak can bound it and ``min_seconds`` checks both.
Queries, outputs and the page table are left out (a lower bound on the work:
live tokens, not the reserved table), so a share over 100% is a fault in the
count or in the time."""

from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def decode_step(depth_tokens: float, *, layers: int, heads: int, latent: int,
                value: int, kv_bytes: int = 2) -> tuple:
    """(flops, bytes) of the kernel for ``depth_tokens`` live tokens summed
    over the rows of a step, all layers."""
    flops = 2.0 * heads * (latent + value) * depth_tokens * layers
    nbytes = float(latent) * kv_bytes * depth_tokens * layers
    return flops, nbytes
