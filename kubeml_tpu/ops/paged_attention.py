"""Pallas TPU paged-attention kernels: attend straight through the page
table, no contiguous K/V copy, KV traffic that scales with occupancy.

The paged serving engine (serving/kvpool.py + the paged decode branch in
models/gpt.py) stores a layer's K and V in one shared physical arena of
token rows, ``[kv_pages, page_tokens, W]``, addressed through per-row page
tables. The original decode read was gather-then-attend: every step, every
layer, each row's WHOLE table is gathered into a contiguous
``[B, tw*pt, H, D]`` HBM block and plain attention runs over it — so a row
64 tokens into a 1024-token reservation reads (and materializes a copy of)
1024 tokens of K and V per layer per step, because admission reserves the
worst case. This kernel is the vLLM PagedAttention / Flash-Decoding answer
(Kwon et al., SOSP 2023): stream the row's pages through VMEM with the
online-softmax recurrence, so no contiguous copy ever exists and reads stop
at the row's live depth.

Arena layout — TOKEN ROWS of K‖V. A token's row holds the K of all ``Hkv``
K/V heads (the query heads, or fewer under grouped-query attention), then
the V of all of them: ``W = 2 * Hkv * D`` lanes, rounded up to a whole
number of 128-lane rows (:func:`kv_row_width`; exact at GPT-2 large 2,560,
XL 3,200 and Falcon-H1 1,024, zero lanes at the tail of a toy model's row).
The Pallas TPU lowering takes a block only if its last two dims are
(8, 128)-aligned or equal the array's: a page ``(1, pt, W)`` has the
array's own trailing dims, is one contiguous DMA, and a head's K or V is a
static lane slice of it inside the kernel (at offset 64 too, for heads of
64). The rule refuses a page-sized window of ``[N, pt, H, D]`` — which is
why the arena used to be two head-major arrays ``[N, H, pt, D]`` — and not
of ``[N, pt, H*D]``; models/gpt.py says, where the arena is declared, what
XLA did to the head-major arrays around their write.

TWO BODIES, one walk. Both walk a row's page table through
``PrefetchScalarGridSpec`` scalar prefetch (the table, per-row positions and
per-row live-page counts), so the K/V BlockSpec index maps translate a
LOGICAL page index into the row's PHYSICAL arena page before the block is
fetched — the "gather" happens per VMEM block inside the kernel's DMA
stream, never as a materialized HBM tensor. Both stream a CHUNK of ``C`` =
``gcd(P, 16)`` pages a program (256 tokens at 16 a page; :func:`walk_chunk_pages`),
the arena handed to the call ``C`` times with one page's index map each so
that Pallas pipelines the page copies, and both carry the online-softmax
state (acc/m/l) in VMEM scratch across the chunk axis exactly like
ops/flash_attention.py — touched once a chunk, not once a page — the output
block revisited (constant index map along that axis) and written once at
the final step. The choice is made on static shapes the call observes in
its input, never on a knob:

* ``L == 1`` over an arena in the compute type — every decode step — takes
  the DECODE BODY (:func:`_decode_kernel`): grid ``(B, P / C)``. The heads
  are the rows of ONE product: the step's queries are set out against an
  arena row's K lanes (head ``h`` on sublane ``h``, its values where its K/V
  head's K lies, zeros elsewhere), so one ``dot_general`` over the lanes
  gives every head's scores for the block, one more every head's values,
  and under grouped-query attention one K/V read serves the whole group.
  On the chip (PR 38) 11-17 x the page-a-program form's speed at the three
  configurations' decode shapes, 62-73% of the K/V read's roofline at
  GPT-2's rows and 24-30% at Falcon-H1's narrower one.
* everything else — ``L > 1`` (one-row admits, suffix prefill after a prefix
  hit, chunked prefill, speculative verify windows) and int8 pages with
  their scales at any ``L`` — takes the TILE BODY (:func:`_tile_kernel`):
  grid ``(B, Lt, P / C)`` (rows, query tiles of 256, chunks) with the chunk
  index innermost (sequential on TPU), a static loop over the heads inside
  a program, each a ``[tq, Dp] x [C pt, Dp]`` contraction over the chunk's
  rows laid end to end (256 query rows already fill the MXU, so the decode
  body's layout buys nothing here), and the compare-and-select mask only in
  the chunks that meet the causal diagonal or the row's depth. On the chip
  (PR 40) 11-16 x the page-a-program form at the cells' admit shapes: one
  row of 1,024 positions in 116 us a layer at 20 heads of 64 where it took
  1,666.

Per-row depth clamp — grid steps past a row's last live page repeat the
previous physical index (the index maps clamp at ``live[b] - 1``, the same
trick the flash kernels use at the causal diagonal), so Pallas elides their
HBM->VMEM copies, and ``pl.when`` skips their compute: HBM reads and FLOPs
scale with the row's ACTUAL ``positions + L``, not the reserved table
width; the grid step itself remains (``serving/stats.py walk_chunks_live``
over ``walk_chunks_grid`` says how much of a decode step's grid is real,
``tile_chunks_live`` over ``tile_chunks_grid`` how much of an admit's). A
page past the depth is clamped by itself, inside a chunk too: a chunk that
straddles the depth fetches its live pages alone.
Dead rows the host already retired point at the pool's trash page 0; their
output is garbage the engine discards anyway (exactly the gather path's
contract), and the decode body reads one page for them whatever their
frozen cursor says.

The mask is purely positional in both (``k_pos <= positions[b] + l``),
identical to the gather path's, so every logical position at or before the
query is attended and later positions (incl. everything past the live
clamp) are not; a masked probability is exactly 0, so a poisoned trash page
or an unwritten slot cannot leak. ``interpret=True`` (automatic off-TPU) runs
the same kernels on CPU for the parity suite (tests/test_paged_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# large-negative instead of -inf keeps exp() NaN-free for fully masked rows
# (same trick as ops/flash_attention.py)
_NEG = -1e30

# lane width of the m/l carry scratch (scalar-per-row state broadcast across
# the minor dimension so the scratch tiles legally)
_LANES = 128

VALID_IMPLS = ("auto", "pallas", "gather")

VALID_KV_QUANT = ("off", "int8", "auto")

# dequant convention shared with the write path in models/gpt.py: an int8
# page value q reconstructs as q * scale / 127 where scale is the page's
# per-head running absmax (so q = round(x * 127 / scale) saturates at +-127)
_KV_QMAX = 127.0


def resolve_kv_quant(value: Optional[str]) -> str:
    """Resolve a ``KUBEML_KV_QUANT`` value to a concrete storage mode:
    ``off`` (default) keeps the arenas in the compute dtype; ``int8``
    stores pages int8 with per-page-per-head scale arenas (half/quarter
    the KV bytes, bounded-divergence numerics); ``auto`` currently
    resolves to ``off`` everywhere — it is reserved to enable int8 on
    TPU once on-device parity evidence lands (mirrors the
    resolve_paged_attn auto contract)."""
    v = (value or "off").lower()
    if v not in VALID_KV_QUANT:
        raise ValueError(
            f"unknown kv-quant mode {value!r} (valid: "
            f"{', '.join(VALID_KV_QUANT)})")
    if v == "auto":
        return "off"
    return v


def resolve_paged_attn(value: Optional[str]) -> str:
    """Resolve a ``KUBEML_PAGED_ATTN`` value to a concrete implementation:
    ``auto`` (default) takes the Pallas kernel on TPU and the gather path
    everywhere else (interpret-mode Pallas is a numerics oracle, not a
    serving path); ``pallas``/``gather`` force their path (the forced
    kernel runs interpret mode off-TPU — the test configuration)."""
    v = (value or "auto").lower()
    if v not in VALID_IMPLS:
        raise ValueError(
            f"unknown paged-attention impl {value!r} (valid: "
            f"{', '.join(VALID_IMPLS)})")
    if v == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "gather"
    return v


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def kv_row_width(kv_heads: int, head_dim: int) -> int:
    """Lanes of one token's arena row: K of every K/V head, then V of every
    K/V head, rounded up to a whole number of 128-lane rows."""
    return _round_up(2 * kv_heads * head_dim, _LANES)


def pack_kv_rows(k, v):
    """``[..., Hkv, D]`` K and V of the same tokens as arena rows
    ``[..., W]``: K‖V, zeros in whatever lanes :func:`kv_row_width` adds."""
    *lead, kv_heads, head_dim = k.shape
    flat = kv_heads * head_dim
    rows = jnp.concatenate([k.reshape(*lead, flat), v.reshape(*lead, flat)],
                           axis=-1)
    pad = kv_row_width(kv_heads, head_dim) - 2 * flat
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * len(lead) + [(0, pad)])
    return rows


def unpack_kv_rows(rows, kv_heads: int, head_dim: int):
    """Arena rows ``[..., W]`` back to K and V ``[..., Hkv, D]`` (numpy or
    jax arrays alike: slices and reshapes only)."""
    flat = kv_heads * head_dim
    shape = rows.shape[:-1] + (kv_heads, head_dim)
    return (rows[..., :flat].reshape(shape),
            rows[..., flat:2 * flat].reshape(shape))


# queries per program of the tile body: L <= _Q_TILE runs as one tile (verify
# windows, short suffixes, Falcon-H1's 128-position admit); longer prefills
# walk the table once per tile so the acc/m/l scratch stays a fixed few MiB
# whatever the prompt bucket (10 MiB at 25 heads; a 1,024-token prefill as
# ONE tile would need 31 MiB at 20). Chosen on the chip at the cells' admit
# shapes (PR 40, CHANGES.md): 256 beat 128 by 20-24% at 1,024 and at 512
# positions (a K slab is loaded into the MXU once for twice the rows, and
# half as many tiles fetch the table again), for 3 s more of Mosaic compile
# a prefill program on a start that finds no compile cache
_Q_TILE = 256

# pages a program of either body streams, chosen on the chip (PR 38 at the
# three configurations' decode shapes: 16 beat 8 by 0-20% and 4 lost 15-35%;
# PR 40 at their admit shapes, CHANGES.md); a table narrower than this, or no
# multiple of it, walks gcd(P, _CHUNK) pages a program
_CHUNK = 16


def walk_chunk_pages(table_width: int) -> int:
    """Pages one program of the walk streams from a table ``table_width``
    pages wide, in either body (the engine counts both grids by this too)."""
    return math.gcd(table_width, _CHUNK)


def _tile_rows(n_queries: int, itemsize: int) -> int:
    """Query rows of one tile-body program: ``n_queries`` up to the storage
    type's sublane tile (8 rows of float32, 16 of bfloat16), at most
    ``_Q_TILE``."""
    return min(_round_up(n_queries, 32 // itemsize), _Q_TILE)


def _tile_live(pos_b, live_b, j, tq: int, pt: int, least=jax.lax.min,
               most=jax.lax.max):
    """Pages query tile ``j`` of a row can see: the row's live depth,
    further clamped at the tile's own last query position (causality — an
    early tile of a long prefill never streams the pages later tiles
    write). At least one page, like ``live`` itself. On traced scalars, or
    on host integers with Python's ``min`` and ``max``."""
    return most(least(live_b, (pos_b + (j + 1) * tq + pt - 1) // pt), 1)


def tile_chunks(position: int, n_queries: int, table_width: int,
                page_tokens: int, itemsize: int) -> tuple:
    """``(live, grid)`` programs of the tile body for ONE row whose
    ``n_queries`` queries (the bucket, padding and all) start at
    ``position``, a layer: the grid is query tiles by ``table_width / C``
    chunks, and a tile's chunks up to its causal depth (and the row's) are
    the ones with pages to read. The host's twin of the kernel's own clamp,
    for the engine's count (``serving/stats.py tile_chunks_live``)."""
    tq = _tile_rows(n_queries, itemsize)
    tiles = _round_up(n_queries, tq) // tq
    chunk = walk_chunk_pages(table_width)
    depth = min(max(-(-(position + n_queries) // page_tokens), 1),
                table_width)
    live = sum(-(-_tile_live(position, depth, j, tq, page_tokens, min, max)
                 // chunk) for j in range(tiles))
    return live, tiles * (table_width // chunk)


def _slab_width(head_dim: int) -> int:
    """Lanes the kernel reads for one head: the head's own where they are
    whole 128-lane rows (or no divisor of one), else one 128-lane row."""
    return _LANES if head_dim < _LANES and _LANES % head_dim == 0 else head_dim


def _slab(start: int, head_dim: int):
    """``(first lane of the slab, the head's offset inside it)`` for a head
    whose ``head_dim`` lanes start at ``start`` of an arena row."""
    within = start % _slab_width(head_dim)
    return start - within, within


def _lay_rows(kv_refs, lane: int, stop: int, dtype=None):
    """Lanes ``[lane, stop)`` of a chunk's page blocks laid end to end,
    ``[C pt, stop - lane]`` (cast page by page where ``dtype`` is given)."""
    rows = [r[0, :, lane:stop] for r in kv_refs]
    if dtype is not None:
        rows = [x.astype(dtype) for x in rows]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, inline=True, static_argnames=("scale",))
def _attend(q, k, v, acc, m, l, visible, k_scale, v_scale, *, scale: float):
    """One head's online-softmax step over one chunk of the tile body:
    queries ``[tq, Dp]`` against the chunk's slab of K and of V
    ``[C pt, Dp]``, the head's accumulator ``[tq, Dp]`` and its carries
    ``m``, ``l`` (``[tq, 128]``, a row's scalar on every lane) in and out.
    ``visible`` is the chunk's positional mask, None where every key is
    visible to every query; ``k_scale`` / ``v_scale`` are an int8 chunk's
    ``[1, C pt]`` rows of page scales. A jitted function inlined where it is
    called: a program's heads share its one trace, which is what keeps a
    25-head kernel's tracing near the page-a-program form's (PR 40: the
    body written out a head cost a start 3 s a prefill program on the
    chip's host)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [tq, C pt]
    if k_scale is not None:
        s = s * (k_scale / _KV_QMAX)
    if visible is not None:
        s = jnp.where(visible, s, _NEG)
    m_prev, l_prev = m[:, 0:1], l[:, 0:1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if visible is not None:
        p = jnp.where(visible, p, 0.0)  # masked keys stay exactly 0
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    if v_scale is not None:
        # contract p against the raw int8 pages; a page's scale folds into
        # p first (one scalar per page — same sum)
        p = p * (v_scale / _KV_QMAX)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * alpha + pv, jnp.broadcast_to(m_new, m.shape),
            jnp.broadcast_to(l_new, l.shape))


def _tile_kernel(pages_ref, pos_ref, live_ref, q_ref, *rest, chunk: int,
                 page_tokens: int, n_chunks: int, scale: float,
                 kv_heads: int, head_dim: int, quantized: bool):
    """One (batch row, query tile, chunk of ``chunk`` logical pages) program
    covering ALL heads. The chunk axis is the innermost (sequential) grid
    dimension; acc/m/l carry across it in VMEM scratch, touched once a chunk,
    and the output is written at the final chunk. The arena arrives ``chunk``
    times, one page ``[pt, W]`` of token rows each (the decode body's fetch:
    Pallas pipelines the page copies); their rows are laid end to end once,
    ``[C pt, lanes]``, and heads are a static loop of plain 2-d contractions
    over the chunk: a head's K (V) is a static lane slice of those rows,
    ``[C pt, Dp]``, so ``s = q . K^T`` is ``[tq, C pt]`` — 256 keys a
    product at 16 pages of 16 — with one max, one exponential, one sum and
    one rescale of the accumulator a chunk (:func:`_attend`). The loop
    stays unrolled: as a ``fori_loop`` over dynamic lane slices it compiles
    in a fifth of the time and runs at half the speed (PR 40, on the chip:
    one head's softmax no longer overlaps the next one's products). A
    tile's query rows already fill the MXU, so the decode body's
    all-heads-one-product layout buys nothing here.

    A head narrower than a 128-lane row (GPT-2's 64) is read as the ALIGNED
    128 lanes it lies in (``Dp`` = 128, :func:`_slab`): a slice at lane
    offset 64 costs a lane rotation a head, K and V (PR 33: 1.4-1.5 x on
    the chip). The query arrives with zeros in the slab's other lanes, so
    the neighbour's K adds exact zeros to a score; the accumulator and the
    output keep all ``Dp`` lanes, the neighbour's half of them garbage that
    the wrapper drops.

    The compare-and-select mask runs only where it can matter: a chunk that
    ends at or before the tile's first query position, inside the row's
    depth, is visible to every query of the tile (the flash kernels'
    off-diagonal case) and takes the same inner function without it.

    When ``quantized`` the page blocks arrive int8 and the chunk's per-head
    absmax scales ride two extra ``[Hkv, C pt]`` inputs (each page's scalar
    repeated along the page's tokens, so a head's row multiplies a
    ``[tq, C pt]`` score tile as an ordinary row broadcast); dequant happens
    here in VMEM, int8_matmul-style — contract the raw int8 values (the
    cast is exact, |q| <= 127), fold ``s/127`` into the f32 scores (K) and
    the f32 probabilities (V) instead of into a dense page."""
    kv_refs, rest = rest[:chunk], rest[chunk:]
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)
    n_heads, tq, dp = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    # grouped-query attention: a page block holds the K/V heads only and
    # query head h reads K/V head h // share (share 1: a head each)
    share = n_heads // kv_heads
    v0 = kv_heads * head_dim  # lane where the row's V half starts
    k_lanes = _slab(v0 - head_dim, head_dim)[0] + dp  # end of the K slabs
    v_first = _slab(v0, head_dim)[0]                  # first V slab
    span = chunk * page_tokens
    q_first = pos_ref[b] + j * tq  # position of the tile's first query
    tile_live = _tile_live(pos_ref[b], live_ref[b], j, tq, page_tokens)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _chunk(masked: bool):
        visible = None
        if masked:
            # purely positional, identical to the gather path: query l sits
            # at logical position positions[b] + l and attends every key at
            # or before it (prompts are dense, decode writes contiguous —
            # every earlier position is real by construction). A page
            # fetched again past the tile's depth lies past every real
            # query too. Padded query rows (l >= the caller's true L)
            # produce garbage that is sliced off.
            q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32,
                                                       (tq, span), 0)
            k_pos = i * span + jax.lax.broadcasted_iota(jnp.int32,
                                                        (tq, span), 1)
            visible = k_pos <= q_pos
        # the chunk's rows end to end, [C pt, lanes], once for all heads:
        # the K lanes (to the end of the last K slab) and the V lanes (from
        # the first V slab; GPT-2 XL's V starts mid-slab, so both hold
        # lanes [1536, 1664)). An int8 page's rows pack four to a sublane
        # and do not lie end to end as they are: they are cast page by page
        k_all = _lay_rows(kv_refs, 0, k_lanes,
                          q_ref.dtype if quantized else None)
        v_all = _lay_rows(kv_refs, v_first, kv_refs[0].shape[2],
                          jnp.float32 if quantized else None)
        for h in range(n_heads):
            hk = h // share
            # [C pt, Dp]: the slab of the rows' lanes this head's K (V)
            # lies in
            k0 = _slab(hk * head_dim, head_dim)[0]
            u0 = _slab(v0 + hk * head_dim, head_dim)[0] - v_first
            acc_ref[h], m_ref[h], l_ref[h] = _attend(
                q_ref[0, h],     # [tq, Dp] (storage dtype; f32 accumulate)
                jax.lax.slice_in_dim(k_all, k0, k0 + dp, axis=1),
                jax.lax.slice_in_dim(v_all, u0, u0 + dp, axis=1),
                acc_ref[h], m_ref[h], l_ref[h], visible,
                ks_ref[0, 0, hk:hk + 1, :] if quantized else None,
                vs_ref[0, 0, hk:hk + 1, :] if quantized else None,
                scale=scale)

    # chunks at or past the tile's live depth contribute nothing: their
    # copies were elided by the clamped index maps, their compute is skipped
    # here
    live_chunk = i * chunk < tile_live
    clear = jnp.logical_and((i + 1) * span - 1 <= q_first,
                            (i + 1) * chunk <= tile_live)
    pl.when(jnp.logical_and(live_chunk, clear))(
        functools.partial(_chunk, False))
    pl.when(jnp.logical_and(live_chunk, jnp.logical_not(clear)))(
        functools.partial(_chunk, True))

    @pl.when(i == n_chunks - 1)
    def _finalize():
        for h in range(n_heads):
            l = l_ref[h, :, 0:1]
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l, 1e-9)
                           ).astype(o_ref.dtype)


def _slab_pieces(n_heads: int, kv_heads: int, head_dim: int):
    """For each query head, which ``head_dim``-wide piece of its slab its
    K/V head's K is, and which its V is (two ``[n_heads]`` arrays; all
    zeros where a head is whole slabs)."""
    share = n_heads // kv_heads
    return tuple(
        np.asarray([_slab((first + h // share) * head_dim, head_dim)[1]
                    // head_dim for h in range(n_heads)])
        for first in (0, kv_heads))


def _slab_rows(n_heads: int, kv_heads: int, head_dim: int, first: int):
    """``[(lane, lo, hi)]``: the slabs of an arena row that hold the K
    (``first`` 0) or the V (``first`` = ``kv_heads``) of some head, each
    with the query heads ``[lo, hi)`` whose K/V head lies in it."""
    share = n_heads // kv_heads
    rows: dict = {}
    for h in range(n_heads):
        lane = _slab((first + h // share) * head_dim, head_dim)[0]
        lo, hi = rows.get(lane, (h, h))
        rows[lane] = (min(lo, h), max(hi, h + 1))
    return [(lane, lo, hi) for lane, (lo, hi) in sorted(rows.items())]


def _into_slabs(q, at, head_dim: int):
    """Queries ``[..., head_dim]`` at their piece ``at`` (broadcast against
    ``q``) of a slab, zeros in the slab's other lanes; whole-slab heads pass
    as they are."""
    pieces = _slab_width(head_dim) // head_dim
    if pieces == 1:
        return q
    return jnp.concatenate([jnp.where(at == i, q, 0) for i in range(pieces)],
                           axis=-1)


def _out_of_slabs(out, at, head_dim: int):
    """The piece ``at`` of each head's slab of outputs ``[..., Dp]``."""
    n = _slab_width(head_dim) // head_dim
    pieces = [out[..., i * head_dim:(i + 1) * head_dim] for i in range(n)]
    return functools.reduce(
        lambda kept, i: jnp.where(at == i, pieces[i], kept),
        range(1, n), pieces[0])


def _decode_kernel(pages_ref, pos_ref, live_ref, q_ref, *rest, chunk: int,
                   page_tokens: int, n_chunks: int, k_slabs, v_slabs,
                   k_lanes: int, v_first: int, scale: float):
    """One (batch row, chunk of ``chunk`` logical pages) program of a decode
    step: ALL heads as the rows of one product. At the row's first chunk the
    step's queries (``q_ref``, ``[Hp, Dp]``: head ``h`` on sublane ``h``, its
    values at its place in a slab as the tile body's wrapper lays them) are
    set out against an arena row's K lanes, ``qbd`` ``[Hp, k_lanes]``: row
    ``h`` holds head ``h``'s values in the slab where its K/V head's K lies
    and zeros elsewhere, so ``qbd . kv^T`` over the lanes is every head's
    scores at once, the other heads' lanes adding exact zeros (as a slab's
    neighbour does in the tile body), and under grouped-query attention one
    K/V read serves the whole group. The probabilities then multiply the
    rows' lanes from ``v_first`` on, and head ``h``'s output is the slab of
    row ``h`` where its K/V head's V lies, picked at the last chunk. The
    arena arrives ``chunk`` times, one page each, so Pallas pipelines the
    page copies; m/l/acc carry across the chunk axis in scratch like the
    tile body's."""
    kv_refs = rest[:chunk]
    o_ref, qbd_ref, acc_ref, m_ref, l_ref = rest[chunk:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    span = chunk * page_tokens
    dp = q_ref.shape[2]
    head = jax.lax.broadcasted_iota(jnp.int32, q_ref.shape[1:], 0)

    @pl.when(i == 0)
    def _init():
        # the K slabs tile [0, k_lanes), so every lane of qbd is written;
        # the select runs in float32, whose mask has the iota's layout
        q = q_ref[0].astype(jnp.float32)
        for lane, lo, hi in k_slabs:
            qbd_ref[:, lane:lane + dp] = jnp.where(
                (head >= lo) & (head < hi), q, 0.0).astype(qbd_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # chunks past the row's live depth: copies elided by the clamped index
    # maps, compute skipped here
    @pl.when(i * chunk < live_ref[b])
    def _chunk():
        k = _lay_rows(kv_refs, 0, k_lanes)
        v = _lay_rows(kv_refs, v_first, kv_refs[0].shape[2])
        s = jax.lax.dot_general(qbd_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # the tile body's positional mask at L == 1: the query sits at
        # positions[b] and sees every key at or before it; a page fetched
        # again past the live depth lies past it too
        k_pos = i * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        visible = k_pos <= pos_ref[b]
        s = jnp.where(visible, s, _NEG)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)  # masked: exactly 0
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == n_chunks - 1)
    def _finalize():
        out = jnp.zeros(q_ref.shape[1:], jnp.float32)
        for lane, lo, hi in v_slabs:
            at = lane - v_first
            out = jnp.where((head >= lo) & (head < hi),
                            acc_ref[:, at:at + dp], out)
        o_ref[0] = (out / jnp.maximum(l_ref[:, 0:1], 1e-9)
                    ).astype(o_ref.dtype)


def _decode_step(q, kv_rows, pages, positions, kv_heads: int, scale: float,
                 interpret: bool):
    """``paged_attention`` at ``L == 1`` over an arena in the compute type:
    grid ``(B, P / chunk)``, :func:`_decode_kernel` a program."""
    B, _, H, D = q.shape
    pt, W = int(kv_rows.shape[1]), int(kv_rows.shape[2])
    P = int(pages.shape[1])
    Dp = _slab_width(D)
    k_slabs = _slab_rows(H, kv_heads, D, 0)
    v_slabs = _slab_rows(H, kv_heads, D, kv_heads)
    # the lanes the two products take: K's from the row's start to the end
    # of its last slab, V's from its first slab to the row's end (GPT-2
    # XL's V starts mid-slab at lane 1,600, so both take lanes [1536, 1664)
    # and the queries' zeros there, like those at other heads, add nothing)
    k_lanes = k_slabs[-1][0] + Dp
    v_first = v_slabs[0][0]
    # heads on the sublanes, up to the storage type's tile (8 rows of
    # float32, 16 of bfloat16: 32 for GPT-2's 20 and 25): the padding rows
    # are zero queries whose output is dropped; a head narrower than its
    # slab sits at its piece of it, zeros beside it, as in the tile body
    Hp = _round_up(H, 32 // q.dtype.itemsize)
    k_at, v_at = (at[None, :, None] for at in _slab_pieces(H, kv_heads, D))
    qs = jnp.pad(_into_slabs(q[:, 0], k_at, D),
                 ((0, 0), (0, Hp - H), (0, 0)))
    chunk = walk_chunk_pages(P)
    n_chunks = P // chunk
    # pages the row occupies, this step's write included (the tile body's
    # clamp at L == 1). A row whose table starts at the trash page holds
    # nothing: the host retired it (or is still prefilling it) and zeroed
    # its table, while its cursor stays frozen wherever it ended, so its
    # depth is one page of trash and not the frozen cursor's
    live = jnp.where(pages[:, 0] == 0, 1,
                     jnp.clip((positions + pt) // pt, 1, P))

    def q_map(b, i, pages_ref, pos_ref, live_ref):
        return (b, 0, 0)

    def page_map(c):
        def index(b, i, pages_ref, pos_ref, live_ref):
            # past the live depth: the last live page again, so no copy
            return (pages_ref[b, jnp.minimum(i * chunk + c,
                                             live_ref[b] - 1)], 0, 0)
        return index

    out = pl.pallas_call(
        functools.partial(_decode_kernel, chunk=chunk, page_tokens=pt,
                          n_chunks=n_chunks, k_slabs=k_slabs,
                          v_slabs=v_slabs, k_lanes=k_lanes, v_first=v_first,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # pages, positions, live
            grid=(B, n_chunks),
            in_specs=[pl.BlockSpec((1, Hp, Dp), q_map)]
            + [pl.BlockSpec((1, pt, W), page_map(c)) for c in range(chunk)],
            out_specs=pl.BlockSpec((1, Hp, Dp), q_map),
            scratch_shapes=[pltpu.VMEM((Hp, k_lanes), q.dtype),       # qbd
                            pltpu.VMEM((Hp, W - v_first), jnp.float32),
                            pltpu.VMEM((Hp, _LANES), jnp.float32),    # m
                            pltpu.VMEM((Hp, _LANES), jnp.float32)]),  # l
        out_shape=jax.ShapeDtypeStruct((B, Hp, Dp), q.dtype),
        interpret=interpret,
    )(pages, positions, live, qs, *([kv_rows] * chunk))
    return _out_of_slabs(out[:, :H], v_at, D)[:, None]


def paged_attention(
    q: jnp.ndarray,         # [B, L, H, D] this call's queries
    kv_rows: jnp.ndarray,   # [N, pt, W] physical K‖V arena (post-write)
    pages: jnp.ndarray,     # [B, P] int32 per-row page table
    positions: jnp.ndarray,  # [B] int32 logical position of q[:, 0]
    interpret: Optional[bool] = None,
    kv_heads: Optional[int] = None,  # Hkv; None = one per query head
    k_scale: Optional[jnp.ndarray] = None,  # [N, Hkv] f32 per-page absmax (int8)
    v_scale: Optional[jnp.ndarray] = None,  # [N, Hkv] f32 per-page absmax (int8)
) -> jnp.ndarray:
    """Paged decode attention; returns ``[B, L, H, D]``.

    Numerically equivalent (at f32-accumulation tolerance) to gathering
    ``kv_rows[pages]`` into contiguous ``[B, P*pt, Hkv, D]`` blocks of K
    and of V and attending under the positional causal mask — without the
    gather: the kernel walks each row's table 16 pages a program, a decode
    step (``L == 1``, the arena in the compute type) with all heads the
    rows of one product, anything else under tiles of 256 queries a head at
    a time (the module docstring's two bodies; the choice is read off the
    shapes). Callers must have already scattered this call's K/V into the
    arena (the paged decode branch in models/gpt.py writes first, then
    attends).

    With ``k_scale``/``v_scale`` the arena is int8 (KUBEML_KV_QUANT=int8)
    and each page's per-head absmax rides the same clamped page walk as
    its rows; dequant happens in the kernel's VMEM blocks around the
    QK^T/PV matmuls — the arena is never materialized wide.

    The arena may hold fewer heads than ``q`` (grouped-query attention):
    with ``kv_heads`` K/V heads, query head ``h`` reads K/V head
    ``h // (H / kv_heads)``, and a row is the K/V heads' alone
    (:func:`pack_kv_rows` makes one, :func:`kv_row_width` sizes it)."""
    B, L, H, D = q.shape
    Hkv = int(kv_heads or H)
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} K/V heads")
    pt, W = int(kv_rows.shape[1]), int(kv_rows.shape[2])
    if W != kv_row_width(Hkv, D):
        raise ValueError(
            f"arena rows of {W} lanes do not hold K and V of {Hkv} heads of "
            f"{D} (that row is {kv_row_width(Hkv, D)} lanes)")
    P = int(pages.shape[1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale and v_scale must be passed together")
    pages = pages.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    scale = 1.0 / math.sqrt(D)
    if L == 1 and not quantized:
        return _decode_step(q, kv_rows, pages, positions, Hkv, scale,
                            interpret)
    # queries move to [B, H, Lp, D] so a block's trailing dims are a clean
    # (tq, D) tile per head; L pads up to the storage dtype's sublane
    # minimum (padded rows are sliced off) and, past one tile, to whole tiles
    tq = _tile_rows(L, q.dtype.itemsize)
    lqp = _round_up(L, tq)
    qt = jnp.moveaxis(q, 2, 1)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, lqp - L), (0, 0)))
    # a head narrower than a 128-lane row meets the aligned slab of the
    # arena's rows it lies in (_tile_kernel): its query sits at the head's
    # offset in that slab with zeros beside it, and its output is read
    # back from the offset of the head's V (not K's, where the V half of a
    # row starts mid-slab: GPT-2 XL's lane 1,600)
    Dp = _slab_width(D)
    # which D-wide piece of its slab a head's K (V) is, [1, H, 1, 1]
    k_at, v_at = (at.reshape(1, H, 1, 1) for at in _slab_pieces(H, Hkv, D))
    qt = _into_slabs(qt, k_at, D)
    chunk = walk_chunk_pages(P)
    n_chunks = P // chunk
    # pages the row actually occupies after this call's writes: the stream
    # clamp. At least one page (a fresh row still reads its own first
    # write); at most the table width (bucket-padding rows whose nominal
    # positions run past the table just re-read their last page — their
    # output is discarded, matching the gather path's clip).
    live = jnp.clip((positions + L + pt - 1) // pt, 1, P)

    def q_map(b, j, i, pages_ref, pos_ref, live_ref):
        return (b, 0, j, 0)

    def page_map(c):
        def index(b, j, i, pages_ref, pos_ref, live_ref):
            # logical->physical through the prefetched table; a page past
            # the tile's live depth is the last live page again, so Pallas
            # elides its copy (the flash kernels' causal-diagonal trick,
            # applied to per-row occupancy, page by page inside a chunk)
            last = _tile_live(pos_ref[b], live_ref[b], j, tq, pt) - 1
            return (pages_ref[b, jax.lax.min(i * chunk + c, last)], 0, 0)
        return index

    def scale_map(b, j, i, pages_ref, pos_ref, live_ref):
        # scales are pre-gathered per row (below): indexed by LOGICAL
        # chunk, a dead chunk the last live one again
        last = _tile_live(pos_ref[b], live_ref[b], j, tq, pt) - 1
        return (b, jax.lax.min(i, last // chunk), 0, 0)

    in_specs = [pl.BlockSpec((1, H, tq, Dp), q_map)] + [
        pl.BlockSpec((1, pt, W), page_map(c)) for c in range(chunk)]
    operands = [qt] + [kv_rows] * chunk
    span = chunk * pt
    if quantized:
        # a [N, H] arena cannot be blocked one page at a time (a (1, H)
        # block's second-minor dim is neither 8-aligned nor the array's),
        # and a per-head scalar in VMEM would need a lane->sublane
        # relayout to meet its [tq, C pt] score tile. So the row's scales
        # are gathered through its table here (B*P*H floats — noise next
        # to the pages; zero past the row's depth, whose keys are masked
        # anyway), repeated along the page's tokens and laid a chunk's
        # pages end to end as the kernel lays their rows: the block
        # (1, 1, H, C pt) is legal (trailing dims == the array's) and row h
        # of it broadcasts over a score tile as is.
        owned = jnp.arange(P)[None, :, None] < live[:, None, None]

        def rows(s):
            s = jnp.where(owned, s.astype(jnp.float32)[pages], 0.0)
            s = jnp.repeat(s.reshape(B, n_chunks, chunk, Hkv), pt, axis=2)
            return jnp.swapaxes(s, 2, 3)            # [B, P / C, Hkv, C pt]

        in_specs += [pl.BlockSpec((1, 1, Hkv, span), scale_map)] * 2
        operands += [rows(k_scale), rows(v_scale)]
    # what a program holds in VMEM, from the shapes at hand: the query and
    # output blocks and the chunk's pages (and scales) double-buffered,
    # acc/m/l, the chunk's rows laid end to end (int8 rows cast: K in the
    # compute type, V in float32), and a head's scores, probabilities and
    # product in flight. The compiler's default (16 MiB) where that is
    # less: GPT-2 XL's 25 heads under 256 queries hold 22 MiB
    blocks = 2 * (2 * H * tq * Dp * q.dtype.itemsize
                  + span * W * kv_rows.dtype.itemsize
                  + (2 * Hkv * span * 4 if quantized else 0))
    scratch = H * tq * (Dp + 2 * _LANES) * 4
    laid = span * W * (q.dtype.itemsize + 4 if quantized
                       else kv_rows.dtype.itemsize)
    in_flight = 4 * tq * (2 * span + Dp) * 4
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=max(
            16 << 20, (blocks + scratch + laid + in_flight) * 5 // 4))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # pages, positions, live
        grid=(B, lqp // tq, n_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, tq, Dp), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, tq, Dp), jnp.float32),      # acc
            pltpu.VMEM((H, tq, _LANES), jnp.float32),  # m (row max)
            pltpu.VMEM((H, tq, _LANES), jnp.float32),  # l (row sum)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_tile_kernel, chunk=chunk, page_tokens=pt,
                          n_chunks=n_chunks, scale=scale, kv_heads=Hkv,
                          head_dim=D, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, lqp, Dp), q.dtype),
        interpret=interpret, **params,
    )(pages, positions, live, *operands)
    return jnp.moveaxis(_out_of_slabs(out[:, :, :L], v_at, D), 1, 2)
