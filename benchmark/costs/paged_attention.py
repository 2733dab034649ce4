"""Operations and bytes the page-walk decode kernel needs, from shapes.

One decode step of one row at depth ``n`` (tokens in its cache, the new one
included), per layer: scores q.K^T and the weighted sum p.V are each
``2 * heads * head_dim * n`` floating-point operations; the K and V of the
``n`` live tokens are each read once, ``heads * head_dim * kv_bytes`` bytes a
token. Queries, outputs and the page table are left out (a lower bound on the
work: live tokens, not the reserved table), so a share over 100% is a fault
in the count or in the time."""


def decode_step(depth_tokens: float, *, layers: int, heads: int,
                head_dim: int, kv_bytes: int = 2) -> tuple:
    """(flops, bytes) of the kernel for ``depth_tokens`` live tokens summed
    over the rows of a step, all layers."""
    flops = 4.0 * heads * head_dim * depth_tokens * layers
    nbytes = 2.0 * heads * head_dim * kv_bytes * depth_tokens * layers
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
