"""Decode attention over a paged *latent* cache (multi-head latent attention
in its absorbed form), and its plain oracle.

The arena holds, per token and layer, ONE vector ``[c | r]`` of ``W = dc +
dr`` values (the normed compressed K/V ``c`` and the rotated shared rope key
``r``), ``[kv_pages, page_tokens, W]``, shared by every head and by K and V.
A decode step's query arrives already carried into that space
(``models/mla.py``): per head ``[q_nope W_uk^T | q_rope]``, so

    s[h, j] = q[h] . [c_j | r_j] * scale,   j <= position
    o[h]    = softmax(s[h]) @ c              (dc wide; W_uv carries it out)

and the cache is never expanded to per-head K and V. :func:`mla_attn` is the
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

from .paged_attention import _LANES, _NEG   # one masking value, one carry width

_CHUNK = 8        # pages a program streams (128 positions at 16 a page)


def mla_attn_gather(q, arena, pages, positions, *, value_dim: int,
                    scale: float):
    """q ``[B, H, W]``, arena ``[N, pt, W]``, pages ``[B, P]``,
    positions ``[B]`` (the query's own, already written) -> ``[B, H, dc]``."""
    B, P = pages.shape
    pt = arena.shape[1]
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
        q = q_ref[0]                                            # [H, W]
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
    B, H, W = q.shape
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
            in_specs=[pl.BlockSpec((1, H, W), q_map)]
            + [pl.BlockSpec((1, pt, W), page_map(c)) for c in range(chunk)],
            out_specs=pl.BlockSpec((1, H, value_dim), q_map),
            scratch_shapes=[pltpu.VMEM((H, value_dim), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=interpret,
    )(pages, positions, live, q, *([arena] * chunk))
    return out
