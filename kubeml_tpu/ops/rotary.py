"""Rotary position embeddings (RoPE).

Position enters attention by rotating each (q, k) head-dim pair by an angle
proportional to the token's absolute position, so relative offsets appear as
phase differences inside the dot product — no learned position table, and
sequence length is not capped by a table size (the learned ``pos_embed``
path's ``max_len`` coupling). Applied to q/k BEFORE the attention call, so it
composes unchanged with the XLA path, the Pallas flash kernels, and
ring/ulysses sequence parallelism (each shard's rows carry their absolute
rotation).

TPU notes: the rotation is a pure elementwise op over [B, L, H, D] — XLA
fuses it into the surrounding projections; angles are computed in f32
regardless of the activation dtype (bf16 phases drift at long context).

YaRN (``YarnScaling``, the published ``rope_scaling`` keys of a DeepSeek-V3
style ``config``): the slow pairs' frequencies are divided by ``factor``, the
fast ones kept, and a linear ramp over the pair index blends the two between
the pairs that turn ``beta_fast`` and ``beta_slow`` times in the original
context; attention's softmax scale is multiplied by ``softmax_mscale ** 2``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of type ``yarn``, under the published keys."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _mscale(self, m: float) -> float:
        return 1.0 if self.factor <= 1.0 else 0.1 * m * math.log(
            self.factor) + 1.0

    @property
    def softmax_mscale(self) -> float:
        """What the softmax scale is multiplied by, squared:
        ``0.1 mscale_all_dim ln(factor) + 1`` (1 without ``mscale_all_dim``)."""
        return self._mscale(self.mscale_all_dim)

    @property
    def table_mscale(self) -> float:
        """What the cosines and sines are multiplied by (1 when ``mscale``
        equals ``mscale_all_dim``)."""
        return self._mscale(self.mscale) / self._mscale(self.mscale_all_dim)


def rope_frequencies(head_dim: int, theta: float = 10000.0,
                     scaling: Optional[YarnScaling] = None) -> np.ndarray:
    """The ``head_dim / 2`` pair frequencies (float32), YaRN's blend of
    ``theta ** (-2i / head_dim)`` and that over ``factor`` under ``scaling``."""
    half = head_dim // 2
    inv = theta ** (-np.arange(0, half, dtype=np.float64) / half)
    if scaling is None:
        return inv.astype(np.float32)
    s = scaling

    def pair(turns):   # the pair that turns ``turns`` times in the old context
        return head_dim * math.log(s.original_max_position_embeddings
                                   / (turns * 2.0 * math.pi)) / (
                                       2.0 * math.log(theta))

    low = max(math.floor(pair(s.beta_fast)), 0)
    high = min(math.ceil(pair(s.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / s.factor * ramp + inv * (1.0 - ramp)).astype(np.float32)


def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0,
                scaling: Optional[YarnScaling] = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables [..., L, head_dim/2] (f32) for absolute ``positions``."""
    if scaling is None:
        half = head_dim // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        m = 1.0
    else:
        freqs = jnp.asarray(rope_frequencies(head_dim, theta, scaling))
        m = scaling.table_mscale
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., L, half]
    if m == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotary_width(head_dim: int, partial_rotary_factor: float = 1.0) -> int:
    """Lanes of a head that turn under ``partial_rotary_factor`` (a
    published ``config`` key): that share of ``head_dim``, rounded down to
    an even number (0.334 of 192 is 64); the rest of the head passes."""
    return int(head_dim * partial_rotary_factor) // 2 * 2


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
               scaling: Optional[YarnScaling] = None,
               rotary_dim: int = 0) -> jnp.ndarray:
    """Rotate ``x`` [B, L, H, D] by its positions [L] or [B, L]; returns the
    input dtype. Pairs are (x[..., :D/2], x[..., D/2:]) — the "rotate-half"
    convention. ``rotary_dim`` > 0: only the first ``rotary_dim`` lanes of a
    head turn (rotate-half pairs within them, frequencies over
    ``rotary_dim``), the others pass as they are."""
    if rotary_dim and rotary_dim < x.shape[-1]:
        return jnp.concatenate([
            apply_rope(x[..., :rotary_dim], positions, theta, scaling),
            x[..., rotary_dim:]], axis=-1)
    b, l, h, d = x.shape
    cos, sin = rope_angles(positions, d, theta, scaling)  # [..., L, D/2]
    if cos.ndim == 2:  # positions were [L]
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # [B, L]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
