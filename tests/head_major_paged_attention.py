"""The page walk as it was before the arena became token rows of K‖V (PR 33):
the parent commit's kernel over two HEAD-MAJOR arenas ``[N, Hkv, pt, D]``,
kept here, under tests/, as the reference the one-arena kernel is held to
bit for bit (tests/test_paged_attention.py): a head does the same
arithmetic on the same numbers whichever way its page reached VMEM. Not a
serving path; the constants and the live-depth clamp are the kernel's own."""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeml_tpu.ops.paged_attention import (_KV_QMAX, _LANES, _NEG, _Q_TILE,
                                            _round_up, _tile_live)


def _pa_kernel(pages_ref, pos_ref, live_ref, q_ref, k_ref, v_ref, *rest,
               page_tokens: int, n_pages: int, scale: float,
               quantized: bool):
    """One (batch row, query tile, logical page) program covering ALL
    heads. The page axis is the innermost (sequential) grid dimension;
    acc/m/l carry across it in VMEM scratch, and the output is written at
    the final page step. Heads are a static loop of plain 2-d
    ``[tq, D] x [pt, D]`` contractions over the head-major page block
    ``[H, pt, D]`` — one contiguous page DMA per step serves every head.

    When ``quantized`` the K/V blocks arrive int8 and the page's per-head
    absmax scales ride two extra ``[H, pt]`` inputs (each head's scalar
    repeated along the page's tokens, so it multiplies a ``[tq, pt]``
    score tile as an ordinary row broadcast); dequant happens here in
    VMEM, int8_matmul-style — contract the raw int8 values (the cast is
    exact, |q| <= 127), fold ``s/127`` into the f32 scores (K) and the
    f32 probabilities (V) instead of into a dense page."""
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)
    n_heads, tq = q_ref.shape[1], q_ref.shape[2]
    # grouped-query attention: the page block holds the K/V heads only and
    # query head h reads K/V head h // share (share 1: a head each)
    share = n_heads // k_ref.shape[1]
    pt = page_tokens

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages at or past the tile's live depth contribute nothing: their
    # copies were elided by the clamped index map, their compute is
    # skipped here
    @pl.when(i < _tile_live(pos_ref[b], live_ref[b], j, tq, pt))
    def _step():
        # purely positional mask, identical to the gather path: query l sits
        # at logical position positions[b] + l and attends every key at or
        # before it (prompts are dense, decode writes contiguous — every
        # earlier position is real by construction). Padded query rows
        # (l >= the caller's true L) produce garbage that is sliced off.
        q_pos = (pos_ref[b] + j * tq
                 + jax.lax.broadcasted_iota(jnp.int32, (tq, pt), 0))
        k_pos = i * pt + jax.lax.broadcasted_iota(jnp.int32, (tq, pt), 1)
        visible = k_pos <= q_pos
        for h in range(n_heads):
            q = q_ref[0, h]      # [tq, D] (storage dtype; f32 accumulate)
            hk = h // share
            k_pg = k_ref[0, hk]  # [pt, D] — one physical page, this head
            v_pg = v_ref[0, hk]
            if quantized:
                k_pg = k_pg.astype(q.dtype)
            s = jax.lax.dot_general(
                q, k_pg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [tq, pt]
            if quantized:
                s = s * (ks_ref[0, 0, hk:hk + 1, :] / _KV_QMAX)
            s = jnp.where(visible, s, _NEG)
            m_prev = m_ref[h, :, 0:1]
            l_prev = l_ref[h, :, 0:1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(visible, p, 0.0)  # masked keys stay exactly 0
            l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            if quantized:
                # contract p against the raw int8 page; the page scale
                # folds into p first (one scalar per page — same sum)
                pv = jax.lax.dot_general(
                    p * (vs_ref[0, 0, hk:hk + 1, :] / _KV_QMAX),
                    v_pg.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                pv = jax.lax.dot_general(
                    p.astype(v_pg.dtype), v_pg, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(i == n_pages - 1)
    def _finalize():
        for h in range(n_heads):
            l = l_ref[h, :, 0:1]
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l, 1e-9)
                           ).astype(o_ref.dtype)


def head_major_paged_attention(
    q: jnp.ndarray,         # [B, L, H, D] this call's queries
    k_pages: jnp.ndarray,   # [N, Hkv, pt, D] physical K arena (post-write)
    v_pages: jnp.ndarray,   # [N, Hkv, pt, D] physical V arena (post-write)
    pages: jnp.ndarray,     # [B, P] int32 per-row page table
    positions: jnp.ndarray,  # [B] int32 logical position of q[:, 0]
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [N, Hkv] f32 per-page absmax (int8)
    v_scale: Optional[jnp.ndarray] = None,  # [N, Hkv] f32 per-page absmax (int8)
) -> jnp.ndarray:
    """Paged decode attention; returns ``[B, L, H, D]``.

    Numerically equivalent (at f32-accumulation tolerance) to gathering
    ``k_pages[pages]`` into a contiguous ``[B, P*pt, H, D]`` block and
    attending under the positional causal mask — without the gather: the
    kernel walks each row's table page by page. Callers must have already
    scattered this call's K/V into the arenas (the paged decode branch in
    models/gpt.py writes first, then attends).

    With ``k_scale``/``v_scale`` the arenas are int8 (KUBEML_KV_QUANT=int8)
    and each page's per-head absmax rides the same clamped page walk as
    its K/V block; dequant happens in the kernel's VMEM blocks around the
    QK^T/PV matmuls — the arenas are never materialized wide.

    The arena may hold fewer heads than ``q`` (grouped-query attention):
    with ``Hkv`` K/V heads, query head ``h`` reads K/V head
    ``h // (H / Hkv)``, and a page's block is the K/V heads' alone."""
    B, L, H, D = q.shape
    Hkv = int(k_pages.shape[1])
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} K/V heads")
    pt = int(k_pages.shape[2])
    P = int(pages.shape[1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # queries move to [B, H, Lp, D] so a block's trailing dims are a clean
    # (tq, D) tile per head; L pads up to the storage dtype's sublane
    # minimum (8 rows of f32, 16 of bf16 — padded rows are sliced off; L
    # is 1 on the decode step path) and, past one tile, to whole tiles
    tq = min(_round_up(L, 32 // q.dtype.itemsize), _Q_TILE)
    lqp = _round_up(L, tq)
    qt = jnp.moveaxis(q, 2, 1)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, lqp - L), (0, 0)))
    pages = pages.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    # pages the row actually occupies after this call's writes: the stream
    # clamp. At least one page (a fresh row still reads its own first
    # write); at most the table width (bucket-padding rows whose nominal
    # positions run past the table just re-read their last page — their
    # output is discarded, matching the gather path's clip).
    live = jnp.clip((positions + L + pt - 1) // pt, 1, P)
    scale = 1.0 / math.sqrt(D)
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale and v_scale must be passed together")

    def q_map(b, j, i, pages_ref, pos_ref, live_ref):
        return (b, 0, j, 0)

    def _logical(b, j, i, pos_ref, live_ref):
        # steps past the tile's live depth repeat the previous page so
        # Pallas elides their copies (the flash kernels' causal-diagonal
        # trick, applied to per-row occupancy)
        return jnp.minimum(
            i, _tile_live(pos_ref[b], live_ref[b], j, tq, pt) - 1)

    def kv_map(b, j, i, pages_ref, pos_ref, live_ref):
        # logical->physical through the prefetched table
        return (pages_ref[b, _logical(b, j, i, pos_ref, live_ref)], 0, 0, 0)

    def scale_map(b, j, i, pages_ref, pos_ref, live_ref):
        # scales are pre-gathered per row (below): indexed by LOGICAL page
        return (b, _logical(b, j, i, pos_ref, live_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, tq, D), q_map),
        pl.BlockSpec((1, Hkv, pt, D), kv_map),
        pl.BlockSpec((1, Hkv, pt, D), kv_map),
    ]
    operands = [qt, k_pages, v_pages]
    if quantized:
        # a [N, H] arena cannot be blocked one page at a time (a (1, H)
        # block's second-minor dim is neither 8-aligned nor the array's),
        # and a per-head scalar in VMEM would need a lane->sublane
        # relayout to meet its [tq, pt] score tile. So the row's scales
        # are gathered through its table here (B*P*H floats — noise next
        # to the pages) and repeated along the page's tokens: the block
        # (1, 1, H, pt) is legal (trailing dims == the array's) and row h
        # of it broadcasts over a score tile as is.
        def rows(s):
            return jnp.broadcast_to(
                s.astype(jnp.float32)[pages][..., None], (B, P, Hkv, pt))

        in_specs += [pl.BlockSpec((1, 1, Hkv, pt), scale_map),
                     pl.BlockSpec((1, 1, Hkv, pt), scale_map)]
        operands += [rows(k_scale), rows(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # pages, positions, live
        grid=(B, lqp // tq, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, tq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, tq, D), jnp.float32),       # acc
            pltpu.VMEM((H, tq, _LANES), jnp.float32),  # m (row max)
            pltpu.VMEM((H, tq, _LANES), jnp.float32),  # l (row sum)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_pa_kernel, page_tokens=pt, n_pages=P, scale=scale,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, lqp, D), q.dtype),
        interpret=interpret,
    )(pages, positions, live, *operands)
    return jnp.moveaxis(out[:, :, :L], 1, 2)
