"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

Long-context support absent from the reference (SURVEY §5) and required here:
the sequence is sharded over devices; each device keeps its Q block resident
and K/V blocks rotate around the ring via ``jax.lax.ppermute`` over ICI, with
flash-style online-softmax accumulation so no device ever materializes the
full [L, L] score matrix. Compute overlaps the next block's transfer (XLA
pipelines the ppermute with the local matmuls).

Runs inside ``shard_map`` over the ``sp`` axis (see
kubeml_tpu/parallel/trainer.py); arrays here are per-device blocks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


_NEG = -1e30  # large-negative instead of -inf: keeps exp() NaN-free for fully
# masked rows (standard flash-attention trick)


def ring_attention(
    q: jnp.ndarray,  # [B, Lb, H, D] local query block
    k: jnp.ndarray,  # [B, Lb, H, D] local key block
    v: jnp.ndarray,  # [B, Lb, H, D] local value block
    axis_name: str = "sp",
    causal: bool = False,
    kv_valid: Optional[jnp.ndarray] = None,  # [B, Lb] True = real token
) -> jnp.ndarray:
    """Exact attention over the ring; returns the local output block [B, Lb, H, D]."""
    sp = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Lb, H, D = q.shape
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qf = q.astype(jnp.float32)

    q_pos = my * Lb + jnp.arange(Lb)  # global positions of local queries

    def step(carry, s):
        acc, m, l, k_blk, v_blk, valid_blk = carry
        src = (my - s) % sp  # which global block k_blk/v_blk currently is
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)) * scale
        k_pos = src * Lb + jnp.arange(Lb)
        if causal:
            causal_mask = k_pos[None, :] <= q_pos[:, None]  # [Lq, Lk]
            scores = jnp.where(causal_mask[None, None], scores, _NEG)
        if valid_blk is not None:
            scores = jnp.where(valid_blk[:, None, None, :], scores, _NEG)

        m_new = jnp.maximum(m, scores.max(axis=-1))  # [B, H, Lq]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        # rows where everything (incl. running max) is masked stay exactly zero
        p = jnp.where(scores <= _NEG / 2, 0.0, p)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv

        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        valid_nxt = (
            jax.lax.ppermute(valid_blk, axis_name, perm) if valid_blk is not None else None
        )
        return (acc_new, m_new, l_new, k_nxt, v_nxt, valid_nxt), None

    acc0 = jnp.zeros((B, Lb, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lb), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lb), jnp.float32)
    # constants are device-invariant; mark them varying over the ring axis so
    # the scan carry type matches its (device-varying) outputs
    acc0, m0, l0 = (jax.lax.pcast(x, (axis_name,), to="varying") for x in (acc0, m0, l0))
    (acc, m, l, *_), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v, kv_valid), jnp.arange(sp)
    )
    denom = jnp.maximum(l, 1e-9).transpose(0, 2, 1)[..., None]  # [B, Lq, H, 1]
    return (acc / denom).astype(q.dtype)
