"""Attention ops.

One functional attention core shared by every transformer model in the zoo, so
the engine can swap implementations without touching model code: the XLA
einsum path here, the Pallas flash-attention kernel
(kubeml_tpu.ops.flash_attention) on TPU, or ring-attention over a sequence
mesh axis (kubeml_tpu.parallel.ring). The reference has no attention anywhere
(CNNs only — SURVEY §5 long-context: absent); this is TPU-native greenfield.

Dispatch: callers that express masking structurally (``causal`` /
``kv_valid``) get the Pallas kernel on TPU automatically; an arbitrary dense
``mask`` forces the XLA path (the kernel handles only the structured forms).

Layout notes: heads stay a separate axis ([B, L, H, D]) until the output
projection so XLA sees clean batched matmuls for the MXU; softmax is computed
in f32 even under bf16 activations (numerics), matching standard TPU practice.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Auto-dispatch threshold for the Pallas flash kernel, tuned on the TRAINING
# path on v5e with a reliable value-fetch barrier. Inside a full
# rematerialized training step (GPT 8x512, jax.checkpoint, 16k-token steps)
# the streaming kernels (Pallas forward AND the FlashAttention-2 Pallas
# backward, ops/flash_attention.py) now win at EVERY measured length after
# the round-3 tuning (bf16 MXU matmuls, 512x1024 blocks, causal copy-skip):
# measured end-to-end tokens/sec 2026-07-31, same-day XLA vs pallas
# (round 3, on a shared chip that is gone; no ledger line holds these):
# L=1024: 127.7k/152.7k, L=2048: 92.3k/144.2k, L=4096: 15.2k/119.0k (7.8x),
# L=8192: 4.0k/84.3k (20.9x), L=16384: 18.2k/53.8k (3.0x), L=32768: XLA OOMs
# (the bf16[8,32k,32k] scores want 16 GB HBM) vs 34.8k. Below 1024 XLA keeps
# the tail and that IS measured: forcing the kernel at BERT-base's seq 128
# dropped training MFU 43.6% -> 32.3% (same round, same chip) — at tiny KV the kernel's per-program overhead beats its locality
# win. Structured-mask callers at KV length >= this threshold get the kernel;
# None disables.
FLASH_MIN_KV_LEN = 1024

# Upper auto-dispatch bound — None since round 3: the streaming rewrite
# (K/V through a sequential grid axis, VMEM O(block^2)) removed the length
# ceiling by design, and the >=16k regime is now chip-MEASURED (see table
# above: 2.9x XLA at 16k, only-survivor at 32k). The knob survives for
# tests/rollback: the original whole-K/V-resident kernels stopped compiling
# between 8k and 16k, and the dispatch gate that protected that ceiling is
# still exercised by test_dispatch_caps_at_max_kv_len.
FLASH_MAX_KV_LEN = None


def dot_product_attention(
    q: jnp.ndarray,  # [B, Lq, H, D]
    k: jnp.ndarray,  # [B, Lk, H, D]
    v: jnp.ndarray,  # [B, Lk, H, D]
    mask: Optional[jnp.ndarray] = None,  # broadcastable to [B, H, Lq, Lk]; True = attend
    *,
    causal: bool = False,
    kv_valid: Optional[jnp.ndarray] = None,  # [B, Lk] True = real token
    impl: Optional[str] = None,  # None=auto | "xla" | "pallas"
    scale: Optional[float] = None,  # None = 1/sqrt(D); else XLA path only
    sink: Optional[jnp.ndarray] = None,  # [H] a learned logit a head; XLA only
) -> jnp.ndarray:
    """Scaled dot-product attention; returns [B, Lq, H, Dv] (V's heads may
    be another width than K's on the XLA path).

    Masking comes either as a dense ``mask`` (XLA path only) or structurally
    as ``causal`` / ``kv_valid`` (eligible for the Pallas flash kernel).
    A softmax ``scale`` of the caller's own (YaRN's, models/mla.py) is
    applied to the float32 scores, on the XLA path. ``sink`` is one more
    logit a head in the softmax's denominator that takes no value (XLA path).
    """
    if scale is not None or sink is not None:
        if impl == "pallas":
            raise ValueError("pallas impl scales by 1/sqrt(D) only and "
                             "knows no sink")
        impl = "xla"
    if impl is None:
        impl = (
            "pallas"
            if FLASH_MIN_KV_LEN is not None
            and mask is None
            and jax.default_backend() == "tpu"
            and k.shape[1] >= FLASH_MIN_KV_LEN
            and (FLASH_MAX_KV_LEN is None or k.shape[1] <= FLASH_MAX_KV_LEN)
            else "xla"
        )
    if impl == "pallas":
        from .flash_attention import flash_attention

        if mask is not None:
            raise ValueError("pallas impl takes causal/kv_valid, not a dense mask")
        return flash_attention(q, k, v, causal=causal, kv_valid=kv_valid)

    if causal or kv_valid is not None:
        lq, lk = q.shape[1], k.shape[1]
        extra = jnp.ones((1, 1, lq, lk), bool)
        if causal:
            extra = extra & (jnp.arange(lk)[None, :] <= jnp.arange(lq)[:, None])[None, None]
        if kv_valid is not None:
            extra = extra & kv_valid[:, None, None, :].astype(bool)
        mask = extra if mask is None else mask & extra
    depth = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if scale is None:
        scores = (scores / jnp.sqrt(depth).astype(q.dtype)).astype(jnp.float32)
    else:
        scores = scores.astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    top = scores.max(axis=-1, keepdims=True)
    if sink is not None:
        logit = sink.astype(jnp.float32)[None, :, None, None]
        top = jnp.maximum(top, logit)
    weights = jnp.exp(scores - top)
    if mask is not None:
        weights = jnp.where(mask, weights, 0.0)
    total = weights.sum(axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(logit - top)
    weights = weights / jnp.maximum(total, 1e-9)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
