"""Multi-head latent attention (the DeepSeek-V3 form, as GLM-4.7-Flash's
``config`` spells it) as a block's attention kind (``attn="mla"``).

    cq        = RMSNorm(u W_dq)                      # E -> q_rank
    q         = cq W_uq, H heads of [q_nope dn | q_rope dr]
    [c | r]   = u W_dkv;  c = RMSNorm(c);  r = RoPE(r)   # E -> dc + dr
    [k_nope_h | v_h] = c W_ukv, per head             # dc -> H x (dn + dv)
    s_h[t, j] = (q_nope_h[t] . k_nope_h[j] + RoPE(q_rope_h)[t] . r[j]) / sqrt(dn + dr)
    out       = concat_h(softmax(s_h) v_h) W_o       # H dv -> E

``r`` is ONE rope key a token, shared by all heads, so the cache holds one
vector ``[c | r]`` of ``dc + dr`` values a token and layer, once: the paged
arena is ``latent_pages`` ``[kv_pages, page_tokens, R]`` (no head axis, no V
arena), a token's row ``[c | r | zeros]`` with ``R`` = ``dc + dr`` rounded up
to whole 128-lane rows (``MLAConfig.row_width``: 640 for the published 512 +
64, no lane added where ``dc + dr`` is a multiple of 128): the write below
(an XLA scatter) and the kernel's read (a Mosaic call) meet only at whole
lane rows, and XLA answered 4.5 of them with two copies of the pool a layer
in every program (ops/mla_attention.py says what that cost). The write
stores the pad lanes as zeros, the trash page's too, so they are zero for
ever and a reader may multiply over them. Two figures follow: the STORED
bytes, ``R`` a token (``models/cache_spec.py CacheSpec.page_bytes``: what the
pool occupies), and the LIVE bytes, ``dc + dr`` (``latent_width``;
``CacheSpec.token_bytes``, the KV-read counter and the benchmark's ``costs/mla_latent.py``: what
attention needs).
Two forms of the one function read it:

* **expanded** — per-head K and V made from the latents. The whole-sequence
  forward (``decode=False``), and a paged call of more than one position (an
  admission's prefill, a verify window): the rows' tables are gathered after
  the write and expanded, cached prefix and suffix alike, and attention is
  dense under the positional mask.
* **absorbed** — a decode step (one position a row): the query is carried
  into the latent space, ``qa_h = q_nope_h W_uk_h^T``, the page walk scores
  ``[qa_h | q_rope_h]`` (padded with zeros to the row) against ``[c | r |
  zeros]`` and sums ``c`` (ops/mla_attention.py: the ``mla_attn`` kernel, or
  its ``gather`` oracle), and ``W_uv_h`` carries the result out. The cache
  is never expanded in a step.

With ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` (LongCat-Flash) the two
normed latents are multiplied by ``sqrt(E / q_lora_rank)`` and ``sqrt(E /
kv_lora_rank)``, so that the up-projections see an input as wide as the
stream's (``r`` is not scaled). Both are applied in float32 where the norm
ends, before the one rounding to the compute type: the arena stores the
scaled ``c``, and both forms read it as it is.

Two more published keys change two lines (Kimi-Linear). ``q_lora_rank``
null: the query is projected DIRECTLY, ``q = u W_q`` (one ``q_proj``; no
``W_dq``, no query norm). ``mla_use_nope`` true: NOTHING is rotated; ``r``
and ``q_rope`` stay the plain lanes the projections made, and order reaches
the layer through its causal mask alone. The arena's row, both forms and
the page walk are the same: a walk scores ``[qa_h | q_rope_h]`` against ``[c
| r]`` whatever was or was not turned before the write.

Norms are float32; rotary pairs are rotate-half over the ``dr`` rope dims.
With ``rope_scaling`` (YaRN, ops/rotary.py) the pair frequencies are the
blended ones and the softmax scale is ``softmax_mscale ** 2 / sqrt(dn + dr)``,
in both forms alike.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.mla_attention import (latent_row_width, mla_attn,
                                 mla_attn_gather, pad_lanes)
from ..ops.paged_attention import resolve_paged_attn
from ..ops.rotary import YarnScaling, apply_rope
from .layers import QuantizableDense


@dataclass(frozen=True)
class MLAConfig:
    """The latent attention's sizes, under the names of the published
    ``config``."""

    q_lora_rank: Optional[int]   # None: the query is projected directly
    kv_lora_rank: int        # dc: the compressed K/V a token caches
    qk_nope_head_dim: int    # dn
    qk_rope_head_dim: int    # dr: the shared rope key a token caches
    v_head_dim: int          # dv
    norm_eps: float = 1e-5
    rope_scaling: Optional[YarnScaling] = None   # None: plain rotary
    mla_scale_q_lora: bool = False    # cq times sqrt(E / q_lora_rank)
    mla_scale_kv_lora: bool = False   # c times sqrt(E / kv_lora_rank)
    mla_use_nope: bool = False        # no rotation of q_rope and r

    def __post_init__(self):
        if self.q_lora_rank is None and self.mla_scale_q_lora:
            raise ValueError("mla_scale_q_lora scales a query latent; "
                             "q_lora_rank is None")

    @property
    def latent_width(self) -> int:
        """Values one token holds in one layer's arena."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Lanes the arena stores them in: whole 128-lane rows."""
        return latent_row_width(self.latent_width)

    @property
    def softmax_scale(self) -> float:
        m = self.rope_scaling.softmax_mscale if self.rope_scaling else 1.0
        return m * m / math.sqrt(self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


class MLAttention(nn.Module):
    num_heads: int
    cfg: MLAConfig
    dtype: Any = jnp.float32
    rope_theta: float = 10000.0
    page_tokens: int = 0
    kv_pages: int = 0
    paged_attn: str = "auto"

    @nn.compact
    def __call__(self, x, valid, decode: bool = False, positions=None,
                 pages=None, seq_lens=None):
        c = self.cfg
        B, L, E = x.shape
        H, dn, dr, dv, dc = (self.num_heads, c.qk_nope_head_dim,
                             c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank)
        dense = lambda feats, names, name: QuantizableDense(
            feats, name=name, use_bias=False, dtype=self.dtype,
            kernel_init=_part(names)(nn.initializers.lecun_normal()))
        norm = lambda name: nn.RMSNorm(name=name, dtype=jnp.float32,
                                       epsilon=c.norm_eps)
        if c.q_lora_rank is None:
            q = dense(H * (dn + dr), (None, "tp"), "q_proj")(x)
        else:
            cq = norm("q_norm")(
                dense(c.q_lora_rank, (None, None), "q_down")(x))
            if c.mla_scale_q_lora:
                cq = cq * math.sqrt(E / c.q_lora_rank)
            q = dense(H * (dn + dr), (None, "tp"), "q_up")(
                cq.astype(self.dtype))
        q = q.reshape(B, L, H, dn + dr)
        ckr = dense(dc + dr, (None, None), "kv_down")(x)
        ckv = norm("kv_norm")(ckr[..., :dc])
        if c.mla_scale_kv_lora:
            ckv = ckv * math.sqrt(E / dc)
        ckv = ckv.astype(self.dtype)
        kr = ckr[..., None, dc:]                          # [B, L, 1, dr]
        # [dc, H, dn + dv]: head h's W_uk (dc -> dn) and W_uv (dc -> dv)
        w_ukv = self.param(
            "kv_up", _part((None, "tp", None))(nn.initializers.lecun_normal(
                in_axis=0, out_axis=(1, 2))), (dc, H, dn + dv))
        w_ukv = jnp.asarray(w_ukv, self.dtype)
        out_proj = dense(E, ("tp", None), "proj")
        yarn = c.rope_scaling
        # dot_product_attention's own 1 / sqrt(dn + dr) unless YaRN scales it
        own = {} if yarn is None else {"scale": c.softmax_scale}

        def expanded(q, lat, **masking):
            """q [B, L, H, dn + dr] (rope part rotated) against the latents
            lat [B, S, dc + dr]: per-head K and V made from them, then the
            repo's attention (flash where it takes the shapes, else dense)."""
            kv = jnp.einsum("bsc,chd->bshd", lat[..., :dc], w_ukv)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    lat[..., None, dc:dc + dr], kv.shape[:3] + (dr,))],
                axis=-1)
            # the kernel wants one head size for q, k and v
            impl = None if dv == dn + dr else "xla"
            return dot_product_attention(q, k, kv[..., dn:], impl=impl,
                                         **own, **masking)

        def rotated(q, kr, pos):
            if c.mla_use_nope:
                return q, kr[:, :, 0]
            q = jnp.concatenate(
                [q[..., :dn],
                 apply_rope(q[..., dn:], pos, self.rope_theta, yarn)],
                axis=-1)
            return q, apply_rope(kr, pos, self.rope_theta, yarn)[:, :, 0]

        if not decode:
            q, kr = rotated(q, kr, jnp.arange(L))
            lat = jnp.concatenate([ckv, kr], axis=-1)
            out = expanded(q, lat, causal=True, kv_valid=valid)
            return out_proj(out.reshape(B, L, H * dv))
        if pages is None:
            raise ValueError(
                "latent attention decodes through the paged arena only "
                "(pages/positions): it keeps no dense per-row cache")
        if self.page_tokens <= 0 or self.kv_pages <= 0:
            raise ValueError(
                "paged decode needs page_tokens/kv_pages > 0 on the module "
                "(the serving layer clones them in)")
        if positions is None:
            raise ValueError("paged decode needs per-row positions")
        pt, npg, tw = self.page_tokens, self.kv_pages, pages.shape[1]
        arena = self.variable("cache", "latent_pages", jnp.zeros,
                              (npg, pt, c.row_width), self.dtype)
        pos_full = positions[:, None] + jnp.arange(L)          # [B, L]
        q, kr = rotated(q, kr, pos_full)
        # the stored row [c | r | zeros]; the trash page's rows alike
        lat = pad_lanes(jnp.concatenate([ckv, kr], axis=-1), c.row_width)
        # the write, as K's in CausalSelfAttention: invalid positions and
        # positions past the table go to the trash page (physical page 0)
        wvalid = (jnp.arange(L)[None, :] < seq_lens[:, None]
                  if seq_lens is not None else valid.astype(jnp.bool_))
        wvalid = wvalid & (pos_full < tw * pt)
        phys = jnp.take_along_axis(
            pages, jnp.clip(pos_full // pt, 0, tw - 1), axis=1)
        phys = jnp.where(wvalid, phys, 0)
        arena.value = arena.value.at[phys, pos_full % pt].set(lat)
        if L > 1:
            rows = arena.value[pages].reshape(B, tw * pt, c.row_width)
            seen = (jnp.arange(tw * pt)[None, None, None, :]
                    <= pos_full[:, None, :, None])
            out = expanded(q, rows, mask=seen)
            return out_proj(out.reshape(B, L, H * dv))
        qa = jnp.einsum("bhd,chd->bhc", q[:, 0, :, :dn], w_ukv[..., :dn])
        ql = jnp.concatenate([qa, q[:, 0, :, dn:]], axis=-1)   # [B, H, .]
        walk = (mla_attn if resolve_paged_attn(self.paged_attn) == "pallas"
                else mla_attn_gather)
        oa = walk(ql, arena.value, pages, positions, value_dim=dc,
                  scale=c.softmax_scale)
        out = jnp.einsum("bhc,chd->bhd", oa, w_ukv[..., dn:])
        return out_proj(out.reshape(B, 1, H * dv))
