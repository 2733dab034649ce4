"""Native int8 weight matmul for the decode path (Pallas TPU + XLA fallback).

Weight-only int8 (serving/quant.py) halves the per-step weight HBM bytes,
but the round-5 decode path dequantized to a dense bf16 tree BEFORE every
matmul — the convert+scale sat between the HBM read and the MXU, and the
measured win stalled at +4-11% at batch 1 (round 5, VERDICT r5 weak-2). These routines contract the activations against the
int8 values DIRECTLY and fold the per-output-channel scale into the f32
accumulator AFTER the contraction:

    y = (x @ Q) * s      ==      x @ (Q * s)        (exact in infinite
                                                     precision; the scale
                                                     is per output column)

so no dense ``W~`` exists even as a fused intermediate — the weight bytes
that transit HBM per step are the int8 bytes, period.

Two implementations behind one signature (``serving.quant.quantized_dot``
dispatches via ``KUBEML_INT8_MATMUL_IMPL``):

* :func:`int8_matmul` — a Pallas TPU kernel. Grid ``(m, n, k)`` with the
  contraction axis innermost (sequential on TPU); the f32 accumulator
  lives in VMEM scratch across the k steps and the output block is
  written once, scaled, at the final k step — the same
  revisit-the-output-block streaming layout as ops/flash_attention.py.
  The int8 block converts to the activation dtype in VMEM (int8 values
  are exact in bf16: 7 magnitude bits vs bf16's 8-bit mantissa), so the
  MXU contracts at full rate and HBM only ever sees s8. Interpret mode
  (automatic off-TPU) runs the identical kernel on CPU for tests.
* :func:`int8_dot` — a portable ``lax.dot_general`` fallback with
  ``preferred_element_type=f32``: the int8->activation-dtype convert is a
  producer XLA fuses into the matmul read, the scale multiplies the f32
  accumulator. Serves CPU tests and any shape the kernel doesn't cover
  (>2-d quantized leaves).

Both accept activations of any leading rank ``[..., K]`` against a 2-d
``Q [K, N]`` with scales broadcastable to ``[1, N]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _mm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """One (m-block, n-block, k-block) program; k is the innermost
    (sequential) grid axis, acc carries across it in VMEM scratch."""
    nk = pl.program_id(2)

    @pl.when(nk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    q = q_ref[...]
    # int8 -> activation dtype in VMEM (exact: |q| <= 127 fits bf16's
    # mantissa); the MXU contracts the storage dtype at full rate with f32
    # accumulation, exactly the flash-attention discipline
    acc_ref[...] += jax.lax.dot_general(
        x, q.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(nk == n_k - 1)
    def _finalize():
        # the per-output-channel scale folds into the f32 accumulator ONCE,
        # after the whole contraction — never into a dense weight
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _blocks_for(m: int, k: int, n: int, block_m: int, block_k: int,
                block_n: int, interpret: bool):
    # Mosaic tile floors: bf16/f32 rows pad to 8 sublanes, int8 to 32, and
    # every minor dim to 128 lanes on real hardware. Decode m is tiny
    # (batch 1-16), so block_m hugs it; k/n blocks stream the weight.
    if interpret:
        min_m, min_kn = 8, 8
    else:
        min_m, min_kn = 8, 128
    bm = max(min(block_m, _round_up(m, 8)), min_m)
    bk = max(min(block_k, _round_up(k, 8)), min_kn)
    bn = max(min(block_n, _round_up(n, 8)), min_kn)
    if not interpret:
        # every hardware block dim must tile: 128 on the lane (minor) axes
        # of q/s/out (bk is also q's int8 second-minor — 128 covers its 32
        # floor), 16 on the bf16 activations' second-minor
        bm = _round_up(bm, 16)
        bk = _round_up(bk, 128)
        bn = _round_up(bn, 128)
    return bm, bk, bn


def int8_matmul(x, q, s, *, block_m: int = 256, block_k: int = 512,
                block_n: int = 512, interpret: Optional[bool] = None,
                out_dtype=None):
    """``(x @ q) * s`` via the Pallas kernel.

    x ``[..., K]`` float (bf16/f32), q ``[K, N]`` int8, s broadcastable to
    ``[1, N]`` f32 (per-output-channel). Returns ``[..., N]`` in
    ``out_dtype`` (default ``x.dtype``) with f32 accumulation throughout.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q.ndim != 2:
        raise ValueError(f"int8_matmul wants a 2-d quantized kernel, "
                         f"got shape {q.shape}")
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K, N = q.shape
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm, bk, bn = _blocks_for(M, K, N, block_m, block_k, block_n, interpret)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    # zero-padding is exact: padded K contributes x*0, padded M/N slice off
    xp = jnp.pad(x2, ((0, Mp - M), (0, Kp - K)))
    qp = jnp.pad(q, ((0, Kp - K), (0, Np - N)))
    sp = jnp.pad(jnp.broadcast_to(s.astype(jnp.float32).reshape(1, -1),
                                  (1, N)), ((0, 0), (0, Np - N)))
    n_k = Kp // bk
    out = pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, qp, sp)
    return out[:M, :N].reshape(*lead, N)


def int8_dot(x, q, s, *, out_dtype=None):
    """``(x @ q) * s`` via plain XLA — the portable fallback.

    The int8->x.dtype convert is a producer fused into the contraction
    (the HBM read stays s8), ``preferred_element_type`` pins an f32
    accumulator for the int8-valued inputs, and the scale applies after.
    Accepts q of any rank (contraction over x's last / q's first axis).
    """
    out_dtype = out_dtype or x.dtype
    acc = jax.lax.dot_general(
        x, q.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # per-output-channel = per LAST axis of q, whatever its rank
    scale = s.astype(jnp.float32).reshape((1,) * (acc.ndim - 1) + (-1,))
    return (acc * scale).astype(out_dtype)
