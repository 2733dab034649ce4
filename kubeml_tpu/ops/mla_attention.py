"""Decode attention over a paged *latent* cache (multi-head latent attention
in its absorbed form), and its plain oracle.

The arena holds, per token and layer, ONE vector ``[c | r]`` of ``W = dc +
dr`` values (the normed compressed K/V ``c`` and the rotated shared rope key
``r``), shared by every head and by K and V, stored as a row ``[c | r |
zeros]`` of ``R`` lanes, ``[kv_pages, page_tokens, R]``: ``W`` rounded up to
a whole number of 128-lane rows (:func:`latent_row_width`; 576 -> 640 at the
published ``dc`` 512 and ``dr`` 64, nothing added where ``W`` is a multiple
of 128 already). The arena's layout is a contract between its write (an XLA
scatter, ``models/mla.py``) and its read (the Mosaic call below), and the two
meet only at whole lane rows: at 576 lanes, 4.5 rows, XLA relaid the whole
pool out before the scatter and back after it, twice a layer in every
program (a third of a decode step on the chip, PERF.md PR 43), as it did to
the K/V arena before its rows were whole (``ops/paged_attention
.kv_row_width``). The lanes past ``W`` are zero from the arena's
``jnp.zeros`` on: every write stores them as zeros (:func:`pad_lanes`).

A decode step's query arrives already carried into the latent space
(``models/mla.py``): per head ``[q_nope W_uk^T | q_rope]``, ``W`` wide, and
is padded with zeros to ``R`` here, so

    s[h, j] = q[h] . [c_j | r_j | 0] * scale,   j <= position
    o[h]    = softmax(s[h]) @ c              (dc wide; W_uv carries it out)

the products over the added lanes are exact zeros, and the cache is never
expanded to per-head K and V. :func:`mla_attn` is the
page walk as a Pallas TPU kernel: grid ``(rows, table width / C)``, each
program streams ``C`` of the row's pages (the arena is handed to the kernel
``C`` times, each copy's index map one page of the chunk through the
prefetched table, so Pallas pipelines them), multiplies all heads' queries
against the ``C * page_tokens`` latents at once — the heads are the rows of
one MXU product, which is what sharing the latent buys — and reads the values
from the same block it scored. Chunks past a row's live depth repeat the last
live page's index (no copy) and are skipped. :func:`mla_attn_gather` is the
same arithmetic in ``jnp`` over the gathered table: the parity oracle and
the path off the TPU (``paged_attn="gather"``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one masking value, one carry width, one rounding
from .paged_attention import _LANES, _NEG, _round_up

_CHUNK = 8        # pages a program streams (128 positions at 16 a page)


def latent_row_width(latent: int) -> int:
    """Lanes of one token's arena row: its ``latent`` = ``dc + dr`` values,
    rounded up to a whole number of 128-lane rows."""
    return _round_up(latent, _LANES)


def pad_lanes(x, width: int):
    """``x [..., W]`` with zeros after it up to ``width`` lanes (``x`` itself
    where it is that wide already)."""
    pad = width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def mla_attn_gather(q, arena, pages, positions, *, value_dim: int,
                    scale: float):
    """q ``[B, H, W]``, arena ``[N, pt, R]`` (``R >= W``, zeros past ``W``),
    pages ``[B, P]``, positions ``[B]`` (the query's own, already written)
    -> ``[B, H, dc]``."""
    B, P = pages.shape
    pt = arena.shape[1]
    q = pad_lanes(q, arena.shape[-1])
    lat = arena[pages].reshape(B, P * pt, arena.shape[-1])
    s = jnp.einsum("bhc,bjc->bhj", q, lat,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(P * pt)[None, None, :] <= positions[:, None, None]
    s = jnp.where(seen, s, _NEG)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-9)
    return jnp.einsum("bhj,bjc->bhc", p.astype(lat.dtype),
                      lat[..., :value_dim],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _mla_kernel(pages_ref, pos_ref, live_ref, q_ref, *rest, chunk: int,
                page_tokens: int, n_chunks: int, value_dim: int,
                scale: float):
    lat_refs, (o_ref, acc_ref, m_ref, l_ref) = rest[:chunk], rest[chunk:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    span = chunk * page_tokens

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * chunk < live_ref[b])
    def _chunk():
        q = q_ref[0]                                            # [H, R]
        lat = jnp.concatenate([r[0] for r in lat_refs], axis=0)  # [span, .]
        s = jax.lax.dot_general(q, lat, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = i * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = k_pos <= pos_ref[b]
        s = jnp.where(seen, s, _NEG)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(lat.dtype), lat[:, :value_dim],
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == n_chunks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-9)
                    ).astype(o_ref.dtype)


def mla_attn(q, arena, pages, positions, *, value_dim: int, scale: float,
             interpret: Optional[bool] = None):
    """The page walk of :func:`mla_attn_gather`'s contract (same arguments,
    same result at float32-accumulation tolerance). The caller has already
    written this step's latent into the arena."""
    q = pad_lanes(q, arena.shape[-1])
    B, H, R = q.shape
    pt = int(arena.shape[1])
    P = int(pages.shape[1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    chunk = math.gcd(P, _CHUNK)
    n_chunks = P // chunk
    pages = pages.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    # pages a row occupies, this step's write included; at least one, at
    # most the table (a retired row's frozen cursor re-reads the trash page)
    live = jnp.clip((positions + pt) // pt, 1, P)

    def q_map(b, i, pages_ref, pos_ref, live_ref):
        return (b, 0, 0)

    def page_map(c):
        def index(b, i, pages_ref, pos_ref, live_ref):
            # past the live depth: the last live page again, so no copy
            return (pages_ref[b, jnp.minimum(i * chunk + c,
                                             live_ref[b] - 1)], 0, 0)
        return index

    out = pl.pallas_call(
        functools.partial(_mla_kernel, chunk=chunk, page_tokens=pt,
                          n_chunks=n_chunks, value_dim=value_dim,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_chunks),
            in_specs=[pl.BlockSpec((1, H, R), q_map)]
            + [pl.BlockSpec((1, pt, R), page_map(c)) for c in range(chunk)],
            out_specs=pl.BlockSpec((1, H, value_dim), q_map),
            scratch_shapes=[pltpu.VMEM((H, value_dim), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=interpret,
    )(pages, positions, live, q, *([arena] * chunk))
    return out
