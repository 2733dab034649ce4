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
K heads and V heads may have widths of their own (MiMo-V2-Flash: 192 and
128). A K head of a lane row and a half is stored in two parts
(:func:`k_tail`): the 128-lane MAIN parts of all K/V heads first, then their
64-lane TAILS two heads to a lane row, then the V heads: ``W = Hkv * (128 +
64 + 128)``, 1,280 lanes at 4 K/V heads and 2,560 at 8, every value in a
lane of its own and every slice the kernels take starting where a lane row
does (a store or a slice at a lane offset inside a row is a rotation on the
chip, PR 40). A query of such a head is laid out the same way (its main
part, then its tail at its place in a lane row, zeros beside it: 256 lanes,
:func:`_lay_queries`), so a head's scores stay ONE product, against the
head's main lanes and its tail's lane row side by side (:func:`_k_parts`);
the output leaves as wide as a V head's. (A width whose remainder divides no
lane row is padded to whole rows instead: :func:`kv_head_stride`.)
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

WHAT A LAYER'S KIND ADDS (PR 44), all of it static and none of it in a call
that does not ask for it (such a call binds the kernels it always did:
``tests/test_hyper_connections.py PARENT_PROGRAMS``). ``window``: a query
sees that many keys, its own among them. The tile body gets a lower clamp
beside the causal one (:func:`_tile_first` beside :func:`_tile_live`; the
host's mirror :func:`tile_chunks`): chunks wholly before a tile's first
query's window are neither fetched nor run, and the mask cuts inside the
first live one. The decode body reads the table as a window layer's RING
(``serving/kvpool.py``: slot ``s`` holds the newest logical page congruent
to ``s`` at or before the query's own; :func:`ring_pages` wide, one program
a row where that is at most 16 pages) and masks by the positions the slots
then hold. ``sink``: one learned logit a head joins the softmax's
denominator at ``_finalize`` (``m`` the maximum with it, ``l`` its term
more) and takes no value. ``value_scale`` multiplies the output once. 64
query heads' carries would be 24 MiB of VMEM under 256 queries, so where
they pass ``_CARRY_BYTES`` the tile body's grid gets an axis over the K/V
heads and a program holds ONE of them and the query heads on it, the arena
arriving as that head's K lanes and V lanes of a page
(:func:`tile_head_groups`).

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


def kv_head_stride(head_dim: int) -> int:
    """Lanes from one head's V (or unsplit K) to the next in an arena row:
    the head's own width, but for a head wider than a 128-lane row that is
    no whole number of them, which starts at the next whole row with zeros
    behind it. A store or a slice at a lane offset inside a row is a
    rotation on the chip (PR 40: 7-38% of the kernel), so every such head
    begins where a lane row does."""
    if head_dim <= _LANES or head_dim % _LANES == 0:
        return head_dim
    return _round_up(head_dim, _LANES)


def k_tail(head_dim: int) -> int:
    """Lanes of a K head stored apart from its whole 128-lane rows: a head
    of 192 is a MAIN part of 128 lanes and a TAIL of 64. The mains of all
    K/V heads lie first, each on whole lane rows, then the tails packed
    ``128 / tail`` heads to a lane row; a head's scores are one product of
    its query laid out the same way (main, then the tail at its place in
    its lane row, zeros beside it) against the head's main lanes and its
    tail's lane row laid side by side. So a row stores the head's 192
    values in 192 lanes and every slice starts where a lane row does; padded
    to 256 (what :func:`kv_head_stride` would do) an arena of such heads is
    a fifth larger and its walk reads a fifth more. 0 where a head is whole
    lane rows, narrower than one, or its remainder divides no lane row (that
    one is padded)."""
    rest = head_dim % _LANES
    return rest if head_dim > _LANES and rest and _LANES % rest == 0 else 0


def _k_stride(head_dim: int) -> int:
    """Lanes from one head's K main part to the next (its whole K where
    the head has no tail)."""
    tail = k_tail(head_dim)
    return head_dim - tail if tail else kv_head_stride(head_dim)


def _k_end(kv_heads: int, head_dim: int) -> int:
    """Lanes of a row's K half: the mains, then the tails' lane rows."""
    return kv_heads * _k_stride(head_dim) + _round_up(
        kv_heads * k_tail(head_dim), _LANES)


def _v_start(kv_heads: int, head_dim: int, v_stride: int) -> int:
    """Lane where a row's V half begins: after the K half, at the next
    multiple of a V head's stride (where it already is when K and V are
    equally wide, and at the published widths)."""
    return _round_up(_k_end(kv_heads, head_dim), v_stride)


def kv_row_width(kv_heads: int, head_dim: int, v_head_dim: int = 0) -> int:
    """Lanes of one token's arena row: K of every K/V head (mains, then
    tails: :func:`k_tail`), then V of every K/V head (``v_head_dim`` wide
    where it differs from K's ``head_dim``) each at its
    :func:`kv_head_stride`, rounded up to a whole number of 128-lane rows."""
    sv = kv_head_stride(v_head_dim or head_dim)
    return _round_up(_v_start(kv_heads, head_dim, sv) + kv_heads * sv, _LANES)


def _flat(x, stride: int, to: int = 0):
    """``[..., Hkv, d]`` with each head padded to ``stride`` lanes, flat,
    zeros up to ``to`` lanes behind."""
    *lead, kv_heads, d = x.shape
    if stride != d:
        x = jnp.pad(x, [(0, 0)] * (len(lead) + 1) + [(0, stride - d)])
    x = x.reshape(*lead, kv_heads * stride)
    if to > x.shape[-1]:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, to - x.shape[-1])])
    return x


def pack_kv_rows(k, v):
    """``[..., Hkv, D]`` K and ``[..., Hkv, Dv]`` V of the same tokens as
    arena rows ``[..., W]``: K‖V, zeros in whatever lanes the strides and
    :func:`kv_row_width` add."""
    kv_heads, head_dim = k.shape[-2:]
    tail, sk = k_tail(head_dim), _k_stride(head_dim)
    sv = kv_head_stride(v.shape[-1])
    v0 = _v_start(kv_heads, head_dim, sv)
    if tail:
        parts = [_flat(k[..., :sk], sk),
                 _flat(k[..., sk:], tail, v0 - kv_heads * sk)]
    else:
        parts = [_flat(k, sk, v0)]
    rows = jnp.concatenate(parts + [_flat(v, sv)], axis=-1)
    pad = kv_row_width(kv_heads, head_dim, v.shape[-1]) - rows.shape[-1]
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
    return rows


def unpack_kv_rows(rows, kv_heads: int, head_dim: int, v_head_dim: int = 0):
    """Arena rows ``[..., W]`` back to K ``[..., Hkv, D]`` and V
    ``[..., Hkv, Dv]`` (numpy or jax arrays alike: slices, reshapes and,
    for a K head with a tail, one concatenation)."""
    v_head_dim = v_head_dim or head_dim
    tail, sk = k_tail(head_dim), _k_stride(head_dim)
    sv = kv_head_stride(v_head_dim)
    lead, flat = rows.shape[:-1], kv_heads * sk
    v0 = _v_start(kv_heads, head_dim, sv)
    k = rows[..., :flat].reshape(lead + (kv_heads, sk))
    if tail:
        tails = rows[..., flat:flat + kv_heads * tail].reshape(
            lead + (kv_heads, tail))
        k = (jnp if isinstance(rows, jax.Array) else np).concatenate(
            [k, tails], axis=-1)
    return (k[..., :head_dim],
            rows[..., v0:v0 + kv_heads * sv].reshape(
                lead + (kv_heads, sv))[..., :v_head_dim])


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


def walk_chunk_pages(table_width: int, ring: bool = False) -> int:
    """Pages one program of the walk streams from a table ``table_width``
    pages wide, in either body (the engine counts both grids by this too).
    A window layer's ``ring`` of at most ``_CHUNK`` pages is one program a
    row: its width is ``window / page_tokens + 2``, seldom a divisor of 16,
    and a row's whole window is less than one chunk of a full layer's."""
    if ring and table_width <= _CHUNK:
        return table_width
    return math.gcd(table_width, _CHUNK)


def ring_pages(window: int, page_tokens: int) -> int:
    """Pages of a window layer's ring a row: the ``window`` keys a query
    sees span at most ``window / page_tokens + 1`` pages, and one more is
    the page a step writes into while the oldest is still read."""
    return -(-int(window) // int(page_tokens)) + 2


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


def _tile_first(pos_b, j, tq: int, pt: int, window: int, most=jax.lax.max):
    """The first page query tile ``j`` of a row can see under a ``window``:
    the page of the oldest key its FIRST query sees (``window`` keys, the
    query's own among them); pages before it hold nothing any query of the
    tile attends to. The lower end of the clamp whose upper end is
    :func:`_tile_live`; 0 without a window."""
    if not window:
        return 0
    return most(pos_b + j * tq - (window - 1), 0) // pt


def tile_chunks(position: int, n_queries: int, table_width: int,
                page_tokens: int, itemsize: int, window: int = 0,
                groups: int = 1) -> tuple:
    """``(live, grid)`` programs of the tile body for ONE row whose
    ``n_queries`` queries (the bucket, padding and all) start at
    ``position``, a layer: the grid is query tiles by ``table_width / C``
    chunks (by ``groups`` where the heads have a grid axis), and a tile's
    chunks from its window's first page (the table's, without a window) up
    to its causal depth (and the row's) are the ones with pages to read.
    The host's twin of the kernel's own clamp, for the engine's count
    (``serving/stats.py tile_chunks_live``)."""
    tq = _tile_rows(n_queries, itemsize)
    tiles = _round_up(n_queries, tq) // tq
    chunk = walk_chunk_pages(table_width)
    depth = min(max(-(-(position + n_queries) // page_tokens), 1),
                table_width)
    live = sum(-(-_tile_live(position, depth, j, tq, page_tokens, min, max)
                 // chunk)
               - _tile_first(position, j, tq, page_tokens, window, max)
               // chunk for j in range(tiles))
    steps = _tile_steps(table_width // chunk, tq, chunk * page_tokens, window)
    return groups * live, groups * tiles * steps


def _tile_steps(n_chunks: int, tq: int, span: int, window: int) -> int:
    """Chunk steps of the tile body's grid a query tile: all ``n_chunks`` of
    the table, or under a ``window`` only as many as a tile's keys can lie
    in (its ``tq`` queries see ``tq + window - 1`` positions between them,
    which touch at most that many chunks of ``span`` however they are
    aligned): the walk then starts at the tile's first live chunk, and a
    4,096-position admit of a window of 128 runs 3 steps a tile where the
    table has 16."""
    if not window:
        return n_chunks
    return min(n_chunks, (tq + window - 2) // span + 2)


def _slab_width(head_dim: int) -> int:
    """Lanes the kernel reads for one head: the head's own where they are
    whole 128-lane rows (or no divisor of one), else one 128-lane row."""
    return _LANES if head_dim < _LANES and _LANES % head_dim == 0 else head_dim


def _slab(start: int, head_dim: int):
    """``(first lane of the slab, the head's offset inside it)`` for a head
    whose ``head_dim`` lanes start at ``start`` of an arena row."""
    within = start % _slab_width(head_dim)
    return start - within, within


def _k_parts(kv_heads: int, head_dim: int, hk: int) -> tuple:
    """``((lane, width), ...)``: the lane ranges of an arena row that, laid
    side by side, a query of K/V head ``hk`` multiplies: the aligned slab
    its K lies in, or (a head with a tail, :func:`k_tail`) its main part and
    the lane row its tail lies in."""
    tail, sk = k_tail(head_dim), _k_stride(head_dim)
    if not tail:
        return ((_slab(hk * sk, sk)[0], _slab_width(sk)),)
    return ((hk * sk, sk),
            (kv_heads * sk + hk * tail // _LANES * _LANES, _LANES))


def _k_width(head_dim: int) -> int:
    """Lanes of a query as it is laid out against its K/V head's K
    (:func:`_k_parts`' widths together)."""
    return sum(w for _, w in _k_parts(1, head_dim, 0))


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
                 kv_heads: int, head_dim: int, quantized: bool,
                 v_dim: int = 0, window: int = 0, sink: bool = False,
                 value_scale: float = 1.0, grouped: bool = False):
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
    the f32 probabilities (V) instead of into a dense page.

    ``head_dim`` and ``v_dim`` are a K head's and a V head's lanes in the
    row (:func:`kv_head_stride`; equal but for a model whose V heads are
    narrower: the query then arrives ``head_dim`` wide, zeros past its own
    width, and the accumulator and the output are ``v_dim`` wide).
    ``window`` > 0: a query sees that many keys, its own among them; chunks
    wholly before the tile's first query's window are neither fetched nor
    run (:func:`_tile_first`) and the mask cuts inside the first live one.
    ``sink``: a ``[heads, 128]`` input, a head's learned logit on every
    lane, joins the softmax's denominator at the last chunk and takes no
    value. ``value_scale`` multiplies the output, once. ``grouped``: the
    grid has an axis over the K/V heads after the rows', a program holds ONE
    K/V head and the query heads on it, and the arena arrives as that
    head's K lanes and its V lanes a page (``chunk`` blocks each), so the
    carries are a K/V head's share of the heads' (64 heads of 128 under
    256 queries would be 25 MiB whole)."""
    if grouped:
        # a K/V head's K lanes of a page (main and tail: two blocks a page
        # where the head has a tail), then its V lanes
        widths = [w for _, w in _k_parts(kv_heads, head_dim, 0)]
        k_refs = [rest[n * chunk:(n + 1) * chunk] for n in range(len(widths))]
        rest = rest[len(widths) * chunk:]
        v_refs, rest = rest[:chunk], rest[chunk:]
    else:
        kv_refs, rest = rest[:chunk], rest[chunk:]
    if quantized:
        ks_ref, vs_ref, *rest = rest
    if sink:
        sink_ref, *rest = rest
    o_ref, acc_ref, m_ref, l_ref = rest
    v_dim = v_dim or head_dim
    b = pl.program_id(0)
    j = pl.program_id(2 if grouped else 1)
    i = pl.program_id(3 if grouped else 2)
    n_heads, tq, dp = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    dv = acc_ref.shape[2]
    # grouped-query attention: a page block holds the K/V heads only and
    # query head h reads K/V head h // share (share 1: a head each)
    share = n_heads if grouped else n_heads // kv_heads
    # lane where the row's V half starts
    v0 = _v_start(kv_heads, head_dim, v_dim)
    # end of the K slabs
    k_lanes = max(lane + width for hk in range(kv_heads)
                  for lane, width in _k_parts(kv_heads, head_dim, hk))
    v_first = _slab(v0, v_dim)[0]                     # first V slab
    span = chunk * page_tokens
    q_first = pos_ref[b] + j * tq  # position of the tile's first query
    tile_live = _tile_live(pos_ref[b], live_ref[b], j, tq, page_tokens)
    tile_first = (_tile_first(pos_ref[b], j, tq, page_tokens, window)
                  if window else 0)
    step = i   # of the grid's chunk axis: the carries begin and end by it
    if window:
        # the grid's chunk steps begin at the tile's first live chunk
        # (_tile_steps): step i is chunk ``first live + i`` of the table
        i = tile_first // chunk + i

    @pl.when(step == 0)
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
            if window:
                visible = visible & (k_pos > q_pos - window)
        if grouped:
            # this program's K/V head alone: its K lanes and its V lanes
            k_one = [_lay_rows(refs, 0, w) for refs, w in zip(k_refs, widths)]
            k_one = (k_one[0] if len(k_one) == 1
                     else jnp.concatenate(k_one, axis=1))
            v_one = _lay_rows(v_refs, 0, dv)
            for h in range(n_heads):
                acc_ref[h], m_ref[h], l_ref[h] = _attend(
                    q_ref[0, h], k_one, v_one, acc_ref[h], m_ref[h],
                    l_ref[h], visible, None, None, scale=scale)
            return
        # the chunk's rows end to end, [C pt, lanes], once for all heads:
        # the K lanes (to the end of the last K slab) and the V lanes (from
        # the first V slab; GPT-2 XL's V starts mid-slab, so both hold
        # lanes [1536, 1664)). An int8 page's rows pack four to a sublane
        # and do not lie end to end as they are: they are cast page by page
        k_all = _lay_rows(kv_refs, 0, k_lanes,
                          q_ref.dtype if quantized else None)
        v_all = _lay_rows(kv_refs, v_first, kv_refs[0].shape[2],
                          jnp.float32 if quantized else None)
        def k_slab(hk):
            parts = [jax.lax.slice_in_dim(k_all, lane, lane + width, axis=1)
                     for lane, width in _k_parts(kv_heads, head_dim, hk)]
            return (parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=1))

        for h in range(n_heads):
            hk = h // share
            # [C pt, Dp]: the slab of the rows' lanes this head's K (V)
            # lies in (a K head's main part and its tail's lane row, side
            # by side, where it has a tail)
            u0 = _slab(v0 + hk * v_dim, v_dim)[0] - v_first
            acc_ref[h], m_ref[h], l_ref[h] = _attend(
                q_ref[0, h],     # [tq, Dp] (storage dtype; f32 accumulate)
                k_slab(hk),
                jax.lax.slice_in_dim(v_all, u0, u0 + dv, axis=1),
                acc_ref[h], m_ref[h], l_ref[h], visible,
                ks_ref[0, 0, hk:hk + 1, :] if quantized else None,
                vs_ref[0, 0, hk:hk + 1, :] if quantized else None,
                scale=scale)

    # chunks at or past the tile's live depth contribute nothing: their
    # copies were elided by the clamped index maps, their compute is skipped
    # here
    live_chunk = i * chunk < tile_live
    if window:
        # a window's chunks all meet an edge of it (a tile is wider than
        # the window is long): every live one is masked, and those wholly
        # before the first query's window are dead like those past the depth
        pl.when(jnp.logical_and(live_chunk, (i + 1) * chunk > tile_first))(
            functools.partial(_chunk, True))
    else:
        clear = jnp.logical_and((i + 1) * span - 1 <= q_first,
                                (i + 1) * chunk <= tile_live)
        pl.when(jnp.logical_and(live_chunk, clear))(
            functools.partial(_chunk, False))
        pl.when(jnp.logical_and(live_chunk, jnp.logical_not(clear)))(
            functools.partial(_chunk, True))

    @pl.when(step == n_chunks - 1)
    def _finalize():
        for h in range(n_heads):
            l = l_ref[h, :, 0:1]
            acc = acc_ref[h]
            if sink:
                # the sink is one more key of logit b_h and no value: the
                # carries move to the maximum with it, the sum gains its
                # term, the accumulator only rescales
                m, logit = m_ref[h], sink_ref[h:h + 1, :]
                m_new = jnp.maximum(m, logit)
                alpha = jnp.exp(m - m_new)[:, 0:1]
                acc = acc * alpha
                l = l * alpha + jnp.exp(logit - m_new)[:, 0:1]
            out = acc / jnp.maximum(l, 1e-9)
            if value_scale != 1.0:
                out = out * value_scale
            o_ref[0, h] = out.astype(o_ref.dtype)


def _slab_pieces(n_heads: int, kv_heads: int, head_dim: int,
                 v_dim: int = 0):
    """For each query head, which ``head_dim``-wide piece of its slab its
    K/V head's K is, and which ``v_dim``-wide piece its V is (two
    ``[n_heads]`` arrays; all zeros where a head is whole slabs)."""
    share = n_heads // kv_heads
    v_dim = v_dim or head_dim
    tail = k_tail(head_dim)
    # a K head with a tail: the piece of its lane row the TAIL is
    k_start, k_dim = ((0, _k_stride(head_dim)) if not tail
                      else (kv_heads * _k_stride(head_dim), tail))
    return tuple(
        np.asarray([_slab(start + (h // share) * dim, dim)[1] // dim
                    for h in range(n_heads)])
        for start, dim in ((k_start, k_dim),
                           (_v_start(kv_heads, head_dim, v_dim), v_dim)))


def _slab_rows(n_heads: int, kv_heads: int, head_dim: int, start: int):
    """``[(lane, lo, hi)]``: the slabs of an arena row that hold some
    head's K (``start`` 0, ``head_dim`` K's) or V (``start`` the lane where
    the row's V half begins, ``head_dim`` V's), each with the query heads
    ``[lo, hi)`` whose K/V head lies in it."""
    share = n_heads // kv_heads
    rows: dict = {}
    for h in range(n_heads):
        lane = _slab(start + (h // share) * head_dim, head_dim)[0]
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


def _lay_queries(q, at, head_dim: int):
    """Queries ``[..., head_dim]`` as wide as the lanes they multiply
    (:func:`_k_parts`): padded to the head's stride and set into its slab at
    piece ``at``; or, a head with a tail, its main part as it is and the
    tail at piece ``at`` of its lane row."""
    tail, sk = k_tail(head_dim), _k_stride(head_dim)
    if tail:
        return jnp.concatenate(
            [q[..., :sk], _into_slabs(q[..., sk:], at, tail)], axis=-1)
    if sk != head_dim:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, sk - head_dim)])
    return _into_slabs(q, at, sk)


def _k_slabs(n_heads: int, kv_heads: int, head_dim: int) -> list:
    """``[(lane, lo, hi, first, width)]``: the slabs that tile a row's K
    half, each with the query heads ``[lo, hi)`` whose K/V head's K (or
    its main part, or its tail) lies in it and the lanes ``[first, first +
    width)`` of a laid-out query (:func:`_lay_queries`) that meet it."""
    tail, sk = k_tail(head_dim), _k_stride(head_dim)
    if not tail:
        width = _slab_width(sk)
        return [(lane, lo, hi, 0, width)
                for lane, lo, hi in _slab_rows(n_heads, kv_heads, sk, 0)]
    share = n_heads // kv_heads
    mains = [(hk * sk, hk * share, (hk + 1) * share, 0, sk)
             for hk in range(kv_heads)]
    return mains + [(lane, lo, hi, sk, _LANES) for lane, lo, hi in _slab_rows(
        n_heads, kv_heads, tail, kv_heads * sk)]


def _out_of_slabs(out, at, head_dim: int):
    """The piece ``at`` of each head's slab of outputs ``[..., Dp]``."""
    n = _slab_width(head_dim) // head_dim
    pieces = [out[..., i * head_dim:(i + 1) * head_dim] for i in range(n)]
    return functools.reduce(
        lambda kept, i: jnp.where(at == i, pieces[i], kept),
        range(1, n), pieces[0])


def _decode_kernel(pages_ref, pos_ref, live_ref, q_ref, *rest, chunk: int,
                   page_tokens: int, n_chunks: int, k_slabs, v_slabs,
                   k_lanes: int, v_first: int, scale: float,
                   window: int = 0, sink: bool = False,
                   value_scale: float = 1.0):
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
    tile body's.

    ``window`` > 0: the table is a window layer's RING (serving/kvpool.py):
    slot ``s`` holds the newest logical page congruent to ``s`` modulo the
    ring's width at or before the query's own, so a slot's keys sit one
    whole turn of the ring earlier from the slot after the query's page on;
    the mask is positional still (at or before the query, and inside its
    ``window`` keys, its own among them), and the order keys are met in is
    nothing to a softmax. ``sink`` (a ``[Hp, 128]`` input, head ``h``'s
    learned logit on sublane ``h``) joins the denominator at the last chunk
    and takes no value; ``value_scale`` multiplies the output, once. The
    queries arrive as wide as a K slab and leave as wide as a V slab."""
    kv_refs, rest = rest[:chunk], rest[chunk:]
    if sink:
        sink_ref, *rest = rest
    o_ref, qbd_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    span = chunk * page_tokens
    dp, dv = q_ref.shape[2], o_ref.shape[2]
    head = jax.lax.broadcasted_iota(jnp.int32, q_ref.shape[1:], 0)

    @pl.when(i == 0)
    def _init():
        # the K slabs tile [0, k_lanes), so every lane of qbd is written;
        # the select runs in float32, whose mask has the iota's layout
        q = q_ref[0].astype(jnp.float32)
        for lane, lo, hi, first, width in k_slabs:
            # a slab as wide as the laid-out query takes it whole
            mine, of = ((q, head) if width == dp else
                        (q[:, first:first + width], head[:, :width]))
            qbd_ref[:, lane:lane + width] = jnp.where(
                (of >= lo) & (of < hi), mine, 0.0).astype(qbd_ref.dtype)
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
        if window:
            # the ring: the slots up to the query's own page hold this turn
            # of it, the ones after it the turn before
            turn = n_chunks * span
            end = (pos_ref[b] // page_tokens + 1) * page_tokens
            k_pos = k_pos + turn * jnp.where(k_pos < end % turn, end // turn,
                                             end // turn - 1)
            visible = (k_pos <= pos_ref[b]) & (
                k_pos > jnp.maximum(pos_ref[b] - window, -1))
        else:
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
        if dv == dp:
            head_v = head
        else:
            head_v = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 0)
        out = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for lane, lo, hi in v_slabs:
            at = lane - v_first
            out = jnp.where((head_v >= lo) & (head_v < hi),
                            acc_ref[:, at:at + dv], out)
        l = l_ref[:, 0:1]
        if sink:
            # the tile body's finalize: one more key of logit b_h, no value
            m_new = jnp.maximum(m_ref[...], sink_ref[...])
            alpha = jnp.exp(m_ref[...] - m_new)[:, 0:1]
            out = out * alpha
            l = l * alpha + jnp.exp(sink_ref[...] - m_new)[:, 0:1]
        out = out / jnp.maximum(l, 1e-9)
        if value_scale != 1.0:
            out = out * value_scale
        o_ref[0] = out.astype(o_ref.dtype)


def _sink_rows(sink, rows: int):
    """A head's learned logit on every lane of its sublane, ``[rows, 128]``
    float32 (zero rows past the heads: padding whose output is dropped)."""
    sink = jnp.pad(sink.astype(jnp.float32), (0, rows - sink.shape[0]))
    return jnp.broadcast_to(sink[:, None], (rows, _LANES))


def _decode_step(q, kv_rows, pages, positions, kv_heads: int, scale: float,
                 interpret: bool, v_head_dim: int = 0, window: int = 0,
                 sink=None, value_scale: float = 1.0):
    """``paged_attention`` at ``L == 1`` over an arena in the compute type:
    grid ``(B, P / chunk)``, :func:`_decode_kernel` a program."""
    B, _, H, D = q.shape
    pt, W = int(kv_rows.shape[1]), int(kv_rows.shape[2])
    P = int(pages.shape[1])
    # the query is set out as wide as the lanes of the row it multiplies
    # (_lay_queries), the output leaves as wide as a V head's slab
    Dv = kv_head_stride(v_head_dim or D)
    Dp, Dvp = _k_width(D), _slab_width(Dv)
    k_slabs = _k_slabs(H, kv_heads, D)
    v_slabs = _slab_rows(H, kv_heads, Dv, _v_start(kv_heads, D, Dv))
    # the lanes the two products take: K's from the row's start to the end
    # of its last slab, V's from its first slab to the row's end (GPT-2
    # XL's V starts mid-slab at lane 1,600, so both take lanes [1536, 1664)
    # and the queries' zeros there, like those at other heads, add nothing)
    k_lanes = k_slabs[-1][0] + k_slabs[-1][4]
    v_first = v_slabs[0][0]
    # heads on the sublanes, up to the storage type's tile (8 rows of
    # float32, 16 of bfloat16: 32 for GPT-2's 20 and 25): the padding rows
    # are zero queries whose output is dropped; a head narrower than its
    # slab sits at its piece of it, zeros beside it, as in the tile body
    Hp = _round_up(H, 32 // q.dtype.itemsize)
    k_at, v_at = (at[None, :, None]
                  for at in _slab_pieces(H, kv_heads, D, Dv))
    qs = jnp.pad(_lay_queries(q[:, 0], k_at, D),
                 ((0, 0), (0, Hp - H), (0, 0)))
    chunk = walk_chunk_pages(P, ring=bool(window))
    n_chunks = P // chunk
    # pages the row occupies, this step's write included (the tile body's
    # clamp at L == 1). A row whose table starts at the trash page holds
    # nothing: the host retired it (or is still prefilling it) and zeroed
    # its table, while its cursor stays frozen wherever it ended, so its
    # depth is one page of trash and not the frozen cursor's. A ring is
    # full once the row has gone round it: the same clamp at its width
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

    in_specs = [pl.BlockSpec((1, Hp, Dp), q_map)] + [
        pl.BlockSpec((1, pt, W), page_map(c)) for c in range(chunk)]
    operands = [qs] + [kv_rows] * chunk
    if sink is not None:
        in_specs.append(pl.BlockSpec(
            (Hp, _LANES), lambda b, i, pages_ref, pos_ref, live_ref: (0, 0)))
        operands.append(_sink_rows(sink, Hp))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, chunk=chunk, page_tokens=pt,
                          n_chunks=n_chunks, k_slabs=k_slabs,
                          v_slabs=v_slabs, k_lanes=k_lanes, v_first=v_first,
                          scale=scale, **_kinds(window, sink, value_scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # pages, positions, live
            grid=(B, n_chunks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hp, Dvp), q_map),
            scratch_shapes=[pltpu.VMEM((Hp, k_lanes), q.dtype),       # qbd
                            pltpu.VMEM((Hp, W - v_first), jnp.float32),
                            pltpu.VMEM((Hp, _LANES), jnp.float32),    # m
                            pltpu.VMEM((Hp, _LANES), jnp.float32)]),  # l
        out_shape=jax.ShapeDtypeStruct((B, Hp, Dvp), q.dtype),
        interpret=interpret,
    )(pages, positions, live, *operands)
    return _out_of_slabs(out[:, :H], v_at, Dv)[..., :v_head_dim or D][:, None]


def _kinds(window: int, sink, value_scale: float) -> dict:
    """The kernels' keywords for what a layer's attention adds to the plain
    causal softmax; none where it adds nothing, so that such a call binds
    the kernel it always did."""
    kw = {}
    if window:
        kw["window"] = int(window)
    if sink is not None:
        kw["sink"] = True
    if value_scale != 1.0:
        kw["value_scale"] = float(value_scale)
    return kw


# the tile body's carries (acc, m and l of every head a program holds) past
# which the heads get a grid axis of their own, one K/V head a program: at
# GPT-2 XL's 25 heads of 64 they are 9.4 MiB and the program holds 22 MiB
# under a raised limit; 64 heads of 128 would carry 24 MiB
_CARRY_BYTES = 12 << 20


def tile_head_groups(n_heads: int, kv_heads: int, head_dim: int,
                     v_head_dim: int, n_queries: int, itemsize: int) -> int:
    """Programs the tile body splits a (row, query tile, chunk) into by
    heads: 1 (all heads a program), or ``kv_heads`` (ONE K/V head and the
    query heads on it a program, under a grid axis over the K/V heads)
    where all heads' carries would pass ``_CARRY_BYTES`` and a head's K and
    V are whole lane rows of their own, so that a block can be one head's
    lanes of a page. Read off the shapes, by the kernel and by the engine's
    count of its grid alike."""
    dk, dv = _k_stride(head_dim), kv_head_stride(v_head_dim or head_dim)
    tq = _tile_rows(n_queries, itemsize)
    if (n_heads * tq * (_slab_width(dv) + 2 * _LANES) * 4 > _CARRY_BYTES
            and dk % _LANES == 0 and dv % _LANES == 0):
        return kv_heads
    return 1


def paged_attention(
    q: jnp.ndarray,         # [B, L, H, D] this call's queries
    kv_rows: jnp.ndarray,   # [N, pt, W] physical K‖V arena (post-write)
    pages: jnp.ndarray,     # [B, P] int32 per-row page table
    positions: jnp.ndarray,  # [B] int32 logical position of q[:, 0]
    interpret: Optional[bool] = None,
    kv_heads: Optional[int] = None,  # Hkv; None = one per query head
    k_scale: Optional[jnp.ndarray] = None,  # [N, Hkv] f32 per-page absmax (int8)
    v_scale: Optional[jnp.ndarray] = None,  # [N, Hkv] f32 per-page absmax (int8)
    v_head_dim: int = 0,    # a V head's width where it is not D
    window: int = 0,        # keys a query sees, its own among them; 0 = all
    sink: Optional[jnp.ndarray] = None,  # [H] a learned logit a head
    value_scale: float = 1.0,
) -> jnp.ndarray:
    """Paged decode attention; returns ``[B, L, H, Dv]``.

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
    (:func:`pack_kv_rows` makes one, :func:`kv_row_width` sizes it); its V
    heads may be ``v_head_dim`` wide where K's are ``D``.

    ``window`` > 0 is a window layer's attention: a query sees the
    ``window`` newest keys at or before it. A decode step (``L == 1``) then
    reads ``pages`` as the row's RING (the module docstring); anything else
    reads it as a plain table of the keys the call itself brought (an
    admit's own bucket, models/gpt.py), chunks before a tile's window
    neither fetched nor run. ``sink``: one learned logit a head in the
    softmax's denominator, no value. ``value_scale`` multiplies the output.
    With none of the three and equal widths both bodies are the kernels
    they were."""
    B, L, H, D = q.shape
    Hkv = int(kv_heads or H)
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} K/V heads")
    pt, W = int(kv_rows.shape[1]), int(kv_rows.shape[2])
    if W != kv_row_width(Hkv, D, v_head_dim):
        raise ValueError(
            f"arena rows of {W} lanes do not hold K and V of {Hkv} heads of "
            f"{D} and {v_head_dim or D} (that row is "
            f"{kv_row_width(Hkv, D, v_head_dim)} lanes)")
    P = int(pages.shape[1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale and v_scale must be passed together")
    if quantized and (window or sink is not None or v_head_dim
                      or value_scale != 1.0):
        raise ValueError("int8 pages under a window, a sink or V heads of "
                         "their own width have no kernel")
    pages = pages.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    scale = 1.0 / math.sqrt(D)
    if L == 1 and not quantized:
        return _decode_step(q, kv_rows, pages, positions, Hkv, scale,
                            interpret, v_head_dim, window, sink, value_scale)
    # the query is set out as wide as the lanes of the row it multiplies
    # (_lay_queries), the output leaves as wide as a V head's slab
    Dv = kv_head_stride(v_head_dim or D)
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
    Dp, Dvp = _k_width(D), _slab_width(Dv)
    # which D-wide piece of its slab a head's K (V) is, [1, H, 1, 1]
    k_at, v_at = (at.reshape(1, H, 1, 1)
                  for at in _slab_pieces(H, Hkv, D, Dv))
    qt = _lay_queries(qt, k_at, D)
    chunk = walk_chunk_pages(P)
    n_chunks = P // chunk
    # the heads a program holds: all of them, or (where their carries would
    # pass _CARRY_BYTES and a head's K and V are whole lane rows of their
    # own) the query heads of ONE K/V head, under a grid axis over those
    grouped = not quantized and tile_head_groups(
        H, Hkv, D, v_head_dim, L, q.dtype.itemsize) > 1
    Hg = H // Hkv if grouped else H
    # pages the row actually occupies after this call's writes: the stream
    # clamp. At least one page (a fresh row still reads its own first
    # write); at most the table width (bucket-padding rows whose nominal
    # positions run past the table just re-read their last page — their
    # output is discarded, matching the gather path's clip).
    live = jnp.clip((positions + L + pt - 1) // pt, 1, P)

    def grid_ids(ids):
        """(row, K/V head or 0, query tile, chunk) of a program."""
        return ids if grouped else (ids[0], 0, ids[1], ids[2])

    def q_map(*ids):
        b, g, j, _ = grid_ids(ids[:-3])
        return (b, g, j, 0)

    def page_of(ids, c):
        # logical->physical through the prefetched table; a page past
        # the tile's live depth is the last live page again, so Pallas
        # elides its copy (the flash kernels' causal-diagonal trick,
        # applied to per-row occupancy, page by page inside a chunk); one
        # before a window's first page is that page, likewise
        b, _, j, i = grid_ids(ids[:-3])
        pages_ref, pos_ref, live_ref = ids[-3:]
        last = _tile_live(pos_ref[b], live_ref[b], j, tq, pt) - 1
        if not window:
            return pages_ref[b, jax.lax.min(i * chunk + c, last)]
        # the grid's steps begin at the tile's first live chunk
        first = _tile_first(pos_ref[b], j, tq, pt, window)
        at = jax.lax.min((first // chunk + i) * chunk + c, last)
        return pages_ref[b, jax.lax.max(at, first)]

    def page_map(c):
        return lambda *ids: (page_of(ids, c), 0, 0)

    def scale_map(b, j, i, pages_ref, pos_ref, live_ref):
        # scales are pre-gathered per row (below): indexed by LOGICAL
        # chunk, a dead chunk the last live one again
        last = _tile_live(pos_ref[b], live_ref[b], j, tq, pt) - 1
        return (b, jax.lax.min(i, last // chunk), 0, 0)

    in_specs = [pl.BlockSpec((1, Hg, tq, Dp), q_map)]
    if grouped:
        # one K/V head a program: its K lanes of a page (its main part,
        # then the lane row of its tail where it has one), then its V
        # lanes: blocks as wide as each, indexed along the row in units of
        # their own width
        parts = _k_parts(Hkv, D, 0)
        tails_to_a_row = _LANES // (k_tail(D) or _LANES)
        for n, (lane, width) in enumerate(parts):
            at0, every = lane // width, (1 if n == 0 else tails_to_a_row)
            in_specs += [pl.BlockSpec(
                (1, pt, width), lambda *ids, c=c, at0=at0, every=every: (
                    page_of(ids, c), 0, at0 + ids[1] // every))
                for c in range(chunk)]
        v_at0 = _v_start(Hkv, D, Dv) // Dv
        in_specs += [pl.BlockSpec(
            (1, pt, Dv), lambda *ids, c=c: (
                page_of(ids, c), 0, v_at0 + ids[1])) for c in range(chunk)]
        operands = [qt] + [kv_rows] * ((len(parts) + 1) * chunk)
    else:
        in_specs += [pl.BlockSpec((1, pt, W), page_map(c))
                     for c in range(chunk)]
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
    if sink is not None:
        in_specs.append(pl.BlockSpec(
            (Hg, _LANES),
            lambda *ids: (grid_ids(ids[:-3])[1], 0)))
        operands.append(_sink_rows(sink, H))
    # what a program holds in VMEM, from the shapes at hand: the query and
    # output blocks and the chunk's pages (and scales) double-buffered,
    # acc/m/l, the chunk's rows laid end to end (int8 rows cast: K in the
    # compute type, V in float32), and a head's scores, probabilities and
    # product in flight. The compiler's default (16 MiB) where that is
    # less: GPT-2 XL's 25 heads under 256 queries hold 22 MiB
    Wg = Dp + Dv if grouped else W     # lanes of a page a program fetches
    blocks = 2 * (Hg * tq * (Dp + Dvp) * q.dtype.itemsize
                  + span * Wg * kv_rows.dtype.itemsize
                  + (2 * Hkv * span * 4 if quantized else 0))
    scratch = Hg * tq * (Dvp + 2 * _LANES) * 4
    laid = span * Wg * (q.dtype.itemsize + 4 if quantized
                        else kv_rows.dtype.itemsize)
    in_flight = 4 * tq * (2 * span + Dvp) * 4
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=max(
            16 << 20, (blocks + scratch + laid + in_flight) * 5 // 4))}
    tiles = lqp // tq
    # chunk steps a tile: the table's, or the few a window's keys lie in
    n_steps = _tile_steps(n_chunks, tq, span, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # pages, positions, live
        grid=(B, Hkv, tiles, n_steps) if grouped else (B, tiles, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hg, tq, Dvp), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hg, tq, Dvp), jnp.float32),     # acc
            pltpu.VMEM((Hg, tq, _LANES), jnp.float32),  # m (row max)
            pltpu.VMEM((Hg, tq, _LANES), jnp.float32),  # l (row sum)
        ],
    )
    kinds = _kinds(window, sink, value_scale)
    if Dv != D:
        kinds["v_dim"] = Dv
    if grouped:
        kinds["grouped"] = True
    out = pl.pallas_call(
        functools.partial(_tile_kernel, chunk=chunk, page_tokens=pt,
                          n_chunks=n_steps, scale=scale, kv_heads=Hkv,
                          head_dim=D, quantized=quantized, **kinds),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, lqp, Dvp), q.dtype),
        interpret=interpret, **params,
    )(pages, positions, live, *operands)
    out = _out_of_slabs(out[:, :, :L], v_at, Dv)[..., :v_head_dim or D]
    return jnp.moveaxis(out, 1, 2)
