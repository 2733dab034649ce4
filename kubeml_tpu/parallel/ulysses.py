"""Ulysses-style all-to-all sequence parallelism over the ``sp`` mesh axis.

The second sequence-parallel scheme next to ring attention
(kubeml_tpu.parallel.ring): instead of rotating K/V blocks around a ring,
one ``all_to_all`` re-shards the activations from sequence-sharded
``[B, L/P, H, D]`` to head-sharded ``[B, L, H/P, D]``, every device computes
ordinary full attention for its head group, and a second ``all_to_all`` swaps
back. Two collectives per attention call regardless of sequence length —
cheaper than the ring's P ``ppermute`` hops when heads divide evenly and the
interconnect favors all-to-all (TPU ICI does) — at the cost of requiring
``H % P == 0`` and memory for the full-length scores per head group (so the
local attention itself can be the flash kernel for very long L).

Runs inside ``shard_map`` over ``sp`` (same contract as ring_attention);
arrays here are per-device blocks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp



def ulysses_attention(
    q: jnp.ndarray,  # [B, Lb, H, D] local sequence block
    k: jnp.ndarray,  # [B, Lb, H, D]
    v: jnp.ndarray,  # [B, Lb, H, D]
    axis_name: str = "sp",
    causal: bool = False,
    kv_valid: Optional[jnp.ndarray] = None,  # [B, Lb] True = real token
) -> jnp.ndarray:
    """Exact attention via head<->sequence all-to-all; returns [B, Lb, H, D]."""
    p = jax.lax.psum(1, axis_name)
    h = q.shape[2]
    if h % p != 0:
        # h is the LOCAL head count: when heads are also tensor-parallel
        # sharded this is num_heads/tp, not the model's num_heads
        raise ValueError(
            f"ulysses needs the local (per-tp-shard) head count ({h}) "
            f"divisible by sp ({p})"
        )

    # sequence-sharded -> head-sharded: split the head axis across the group,
    # concatenate the sequence axis. q/k/v are stacked so the re-shard is ONE
    # all-to-all launch over ICI instead of three.
    qkv = jnp.stack((q, k, v))  # [3, B, Lb, H, D]
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=3, concat_axis=2, tiled=True)
    qh, kh, vh = qkv[0], qkv[1], qkv[2]  # [B, L, H/P, D]
    valid_full = (
        jax.lax.all_gather(kv_valid, axis_name, axis=1, tiled=True)
        if kv_valid is not None
        else None
    )

    # ordinary attention on the full sequence for this device's head group;
    # global positions are contiguous after the concat, so causal masking is
    # exactly the single-device semantics
    from ..ops.attention import dot_product_attention

    out = dot_product_attention(qh, kh, vh, causal=causal, kv_valid=valid_full)

    # head-sharded -> sequence-sharded
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)
