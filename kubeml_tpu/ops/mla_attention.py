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
page walk as a Pallas TPU kernel whose unit of work is a ROW's live depth:
grid ``(rows,)``, rows in order, and inside a row's program a loop over the
row's live spans only, ``ceil(pages / C)`` trips of ``C`` pages (``_SPAN``
positions: :func:`span_pages`), a bound read from the prefetched depths, so
no step exists for a chunk without pages and the table's width costs
nothing. The arena stays in HBM (``pl.ANY``) and the kernel copies a span's
pages itself, through the prefetched table into one contiguous ``[2, C *
page_tokens, R]`` scratch (a latent page is 20 KB: a grid step for every
eight of them cost five times their bytes' time, PERF.md PR 49), the next
span's copies started before this span's products, a row's first span by the
row above it. A trip multiplies all heads' queries against the span's
latents at once — the heads are the rows of one MXU product, which is what
sharing the latent buys — and reads the values from the block it scored.
:func:`walk_trips` is the host's mirror of that loop, by the kernel's own
two rules (the engine counts trips and pages with it, serving/stats.py).
:func:`mla_attn_gather` is the same arithmetic in ``jnp`` over the gathered
table: the parity oracle and the path off the TPU (``paged_attn="gather"``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one masking value, one lane width, one rounding
from .paged_attention import _LANES, _NEG, _round_up

_SPAN = 512       # positions one trip of a row's loop takes (32 pages of 16)


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


def span_pages(table_width: int, page_tokens: int) -> int:
    """Pages one trip of the walk's loop takes: ``_SPAN`` positions of them,
    or the whole table where it is narrower."""
    return max(1, min(int(table_width), _SPAN // int(page_tokens)))


def _row_pages(positions, first_page, page_tokens: int, table_width: int,
               xp=jnp):
    """Pages a row occupies, this step's write included; at least one, at
    most the table. A row whose table starts at the trash page (page 0)
    holds nothing: the host retired it, or is still prefilling it, and
    zeroed its table while its cursor stays frozen wherever it ended, so
    its depth is one page of trash and not the frozen cursor's (the K/V
    walk's rule, ops/paged_attention.py)."""
    pages = xp.clip((positions + page_tokens) // page_tokens, 1, table_width)
    return xp.where(first_page == 0, 1, pages)


def _row_trips(pages, chunk: int):
    """Trips of ``chunk`` pages over a row's ``pages``."""
    return (pages + chunk - 1) // chunk


def walk_trips(table_width: int, page_tokens: int, positions,
               dead_rows: int = 0) -> tuple:
    """The host's mirror of the kernel's loop over a ``table_width``-page
    table: ``(trips, pages)`` it makes and copies for live rows whose
    queries sit at ``positions`` and ``dead_rows`` rows whose table starts
    at the trash page, by the kernel's own two rules. A live row is
    ``ceil(pages / C)`` trips, whatever the table's width; a dead one is
    one trip of one page, whatever its cursor."""
    pages = _row_pages(np.asarray(positions, np.int64), 1, int(page_tokens),
                       int(table_width), xp=np)
    trips = _row_trips(pages, span_pages(table_width, page_tokens))
    return int(trips.sum()) + dead_rows, int(pages.sum()) + dead_rows


def _mla_kernel(pages_ref, pos_ref, live_ref, q_ref, arena_ref, o_ref,
                buf_ref, sem_ref, slot_ref, *, chunk: int, page_tokens: int,
                value_dim: int, scale: float):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    span = chunk * page_tokens
    trips = _row_trips(live_ref[b], chunk)

    def page_of(slot, c):
        return buf_ref.at[slot, pl.ds(pl.multiple_of(c * page_tokens,
                                                     page_tokens),
                                      page_tokens)]

    def each_page(row, t, slot, do):
        # the pages of row ``row``'s trip ``t``, into or out of ``slot``;
        # returns how many they are
        first = t * chunk
        n = jnp.minimum(chunk, live_ref[row] - first)

        def one(c, carry=0):
            do(pltpu.make_async_copy(arena_ref.at[pages_ref[row, first + c]],
                                     page_of(slot, c), sem_ref.at[slot]))
            return carry

        # a full span's copies are written out: what bounds a span is the
        # scalar unit that issues them, and a loop costs each a branch
        # (25% of a walk, PERF.md PR 49); a row's last span takes the loop
        @pl.when(n == chunk)
        def _full():
            for c in range(chunk):
                one(c)

        @pl.when(n < chunk)
        def _partial():
            jax.lax.fori_loop(0, n, one, 0)

        return n

    def start(row, t, slot):
        each_page(row, t, slot, lambda copy: copy.start())

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0
        start(0, 0, 0)

    slot0 = slot_ref[0]
    q = q_ref[0]                                                # [H, R]
    heads = q.shape[0]

    def trip(t, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + t) % 2
        # the next span's copies go out before this span's products: the
        # row's own next, or after its last the first of the row below
        @pl.when(t + 1 < trips)
        def _next_span():
            start(b, t + 1, 1 - slot)

        @pl.when((t + 1 == trips) & (b + 1 < rows))
        def _next_row():
            start(b + 1, 0, 1 - slot)

        n = each_page(b, t, slot, lambda copy: copy.wait())

        # a span's tail past the row's depth is never copied and its scores
        # are masked, but 0 x what the scratch held (another row's latents,
        # or nothing yet) must be 0
        def blank(c, carry):
            page = page_of(slot, c)
            page[...] = jnp.zeros_like(page)
            return carry

        jax.lax.fori_loop(n, chunk, blank, 0)
        lat = buf_ref[slot]                                     # [span, R]
        s = jax.lax.dot_general(q, lat, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = t * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = k_pos <= pos_ref[b]
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(lat.dtype), lat[:, :value_dim],
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    # the online-softmax carries are the loop's values (6-9% faster than a
    # scratch read and written a span, PERF.md PR 49)
    _, l, acc = jax.lax.fori_loop(
        0, trips, trip, (jnp.full((heads, 1), _NEG, jnp.float32),
                         jnp.zeros((heads, 1), jnp.float32),
                         jnp.zeros((heads, value_dim), jnp.float32)))
    slot_ref[0] = (slot0 + trips) % 2
    o_ref[0] = (acc / jnp.maximum(l, 1e-9)).astype(o_ref.dtype)


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
    chunk = span_pages(P, pt)
    pages = pages.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    live = _row_pages(positions, pages[:, 0], pt, P)

    def q_map(b, pages_ref, pos_ref, live_ref):
        return (b, 0, 0)

    return pl.pallas_call(
        functools.partial(_mla_kernel, chunk=chunk, page_tokens=pt,
                          value_dim=value_dim, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, R), q_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_dim), q_map),
            scratch_shapes=[pltpu.VMEM((2, chunk * pt, R), arena.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        # rows in order: a row starts the copies of the row below it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pages, positions, live, q, arena)
