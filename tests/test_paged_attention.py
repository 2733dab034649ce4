"""Fused paged-attention decode kernel (ISSUE 15): interpret-mode parity
of the Pallas page-walk kernel against the gather oracle, greedy TOKEN
parity through the paged serving engine under ``KUBEML_PAGED_ATTN=pallas``
(mixed lengths, prefix-shared pages, spec verify windows, int8 compose),
the live-table-width clamp's accounting, and the KV-read telemetry.

Correctness bars:

* LOGIT PARITY — ``ops.paged_attention.paged_attention`` must match the
  gather-then-attend reference at f32-accumulation tolerance for every
  caller shape: L == 1 decode steps, L == k+1 verify windows, L > 1
  page-aligned suffix prefill at non-zero base positions.
* NO DEAD-POSITION LEAKS — with the trash page and every non-live arena
  position poisoned with huge values, outputs are unchanged: the
  positional mask plus the live-page clamp must make unwritten state
  unreachable, exactly like the gather path's contract.
* TOKEN PARITY — the paged engine's emitted tokens are identical between
  ``pallas`` and ``gather`` (and the one-shot baseline) across a
  mixed-length workload including shared-prefix admissions, speculative
  self-drafting, and int8 weights.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import generate, init_paged_cache
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.ops.attention import dot_product_attention
from kubeml_tpu.ops.paged_attention import (walk_chunk_pages,
                                            kv_row_width, pack_kv_rows,
                                            paged_attention,
                                            resolve_kv_quant,
                                            resolve_paged_attn)
from kubeml_tpu.serving.batcher import PagedBatchingDecoder, _Row

from head_major_paged_attention import head_major_paged_attention

VOCAB = 101


def tiny(pos="learned", max_len=64):
    return CausalTransformer(vocab_size=VOCAB, max_len=max_len, embed_dim=32,
                             depth=2, num_heads=2, pos=pos)


def gather_reference(q, k_pages, v_pages, pages, positions):
    """The exact fallback read from models/gpt.py: gather the table into a
    contiguous block, attend under the positional causal mask (each K/V
    head once per query head that shares it)."""
    B, L = q.shape[:2]
    P, pt = pages.shape[1], k_pages.shape[1]
    H, D = k_pages.shape[2], k_pages.shape[3]
    share = q.shape[2] // H
    kg = jnp.repeat(k_pages[pages].reshape(B, P * pt, H, D), share, axis=2)
    vg = jnp.repeat(v_pages[pages].reshape(B, P * pt, H, D), share, axis=2)
    k_pos = jnp.arange(P * pt)[None, None, None, :]
    pos_full = positions[:, None] + jnp.arange(L)
    mask = k_pos <= pos_full[:, None, :, None]
    return dot_product_attention(q, kg, vg, mask=mask)


def paged(q, k_tok, v_tok, pages, positions, **kw):
    """The op-level cases build K and V token-major ``[N, pt, Hkv, D]`` (a
    page reads as rows of tokens, like the gather reference above); the
    device arena the kernel takes is their rows of K‖V, ``[N, pt, W]``."""
    return paged_attention(q, pack_kv_rows(k_tok, v_tok), pages, positions,
                           kv_heads=k_tok.shape[2], **kw)


def assert_equals_head_major(out, q, k_tok, v_tok, pages, positions, **kw):
    """``out`` against the same arrays laid out the old way, two arenas
    ``[N, Hkv, pt, D]``, through the kernel as it was: a page a program and
    a head at a time. Both bodies now take a chunk of pages a product (the
    decode body all heads at once over a whole row's K lanes, the tile body
    a head's slab of 128 lanes, zeros in the query beside a narrower head):
    the same products, one maximum and one sum a chunk where there was one a
    page, so equal to a few units in the last place and not bit for bit."""
    old = head_major_paged_attention(q, jnp.swapaxes(k_tok, 1, 2),
                                     jnp.swapaxes(v_tok, 1, 2), pages,
                                     positions, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(old),
                               atol=1e-6, rtol=1e-6)


# (query heads, K/V heads, head size) beside the toy 2 / 2 / 16, whose row
# is half zero lanes: GPT-2 XL's odd count (a head's V starts at lane
# 1,600 + 64 h of a 3,200-lane row), and Falcon-H1's 20 on 4 of 128
HEADS = {"toy": (2, 2, 16), "xl": (25, 25, 64), "falcon-h1": (20, 4, 128)}
# the decode body's three published shapes: gpt2-large's 20 of 64 beside them
DECODE_HEADS = {"large": (20, 20, 64), "xl": HEADS["xl"],
                "falcon-h1": HEADS["falcon-h1"]}


# --- op-level kernel parity (interpret mode) ---


def test_resolve_impl_values():
    assert resolve_paged_attn("gather") == "gather"
    assert resolve_paged_attn("pallas") == "pallas"
    assert resolve_paged_attn(None) in ("pallas", "gather")
    # auto = pallas only on TPU; this suite runs on CPU
    if jax.default_backend() != "tpu":
        assert resolve_paged_attn("auto") == "gather"
    with pytest.raises(ValueError):
        resolve_paged_attn("einsum")


@pytest.mark.kernel
@pytest.mark.parametrize("heads,L,positions", [
    ("toy", 1, [5, 0, 17]),        # per-token decode step at mixed depths
    ("toy", 4, [3, 0, 12]),        # spec verify window (k+1 = 4)
    ("toy", 8, [0, 8, 16]),        # suffix prefill, incl. page-aligned bases
    ("xl", 1, [5, 0, 17]),
    ("xl", 8, [0, 8, 16]),         # suffix prefill through the lane slices
    ("falcon-h1", 1, [5, 0, 17]),
    ("falcon-h1", 8, [3, 8, 16]),
])
def test_kernel_logit_parity(heads, L, positions):
    rng = np.random.default_rng(0)
    H, Hkv, D = HEADS[heads]
    B, pt, P, N = 3, 4, 6, 20
    k_pages = jnp.asarray(rng.normal(size=(N, pt, Hkv, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(N, pt, Hkv, D)), jnp.float32)
    pages = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    out = paged(q, k_pages, v_pages, pages, pos)
    ref = gather_reference(q, k_pages, v_pages, pages, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6 * D / 16, rtol=2e-6 * D / 16)
    assert_equals_head_major(out, q, k_pages, v_pages, pages, pos)


def walk_grid(fn, *args):
    """The grid of the one ``pallas_call`` a traced call holds."""
    calls = [e for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1     # one unnamed call a layer a step
    return tuple(calls[0].params["grid_mapping"].grid)


# page_tokens 4 and a table of 32 pages: a decode program streams 16 pages,
# 64 tokens, so a row's depth crosses a program's edge at 63 / 64 / 65
DECODE_CASES = {
    # (table width, positions, rows whose table is all trash)
    "edges": (32, [0, 62, 63, 64, 127], ()),   # depth 1, C pt - 1, C pt,
                                               # C pt + 1, the full table
    "narrow": (4, [0, 5, 15], ()),             # P = 4: gcd(4, 16) pages
    "retired": (32, [70, 0, 3, 90], (1, 3)),   # the host zeroed rows 1, 3;
                                               # row 3's cursor froze deep
}


@pytest.mark.kernel
@pytest.mark.parametrize("case", sorted(DECODE_CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", sorted(DECODE_HEADS))
def test_decode_body_parity(heads, dtype, case):
    """A decode step (``L == 1``, the arena in the compute type) takes the
    body of its own: all heads the rows of one product over 16 pages a
    program. Against the gather oracle and against the parent's kernel over
    head-major pages, at the three published head shapes, both storage
    types, depths on either side of a program's edge, a table narrower than
    a program's pages, and rows the host retired beside live ones."""
    rng = np.random.default_rng(5)
    H, Hkv, D = DECODE_HEADS[heads]
    P, positions, retired = DECODE_CASES[case]
    B, pt = len(positions), 4
    N = B * P + 1
    dt = jnp.dtype(dtype)
    k_pages = jnp.asarray(rng.normal(size=(N, pt, Hkv, D)), dt)
    v_pages = jnp.asarray(rng.normal(size=(N, pt, Hkv, D)), dt)
    table = 1 + rng.permutation(N - 1)[:B * P].reshape(B, P)
    table[list(retired)] = 0
    pages = jnp.asarray(table, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), dt)
    pos = jnp.asarray(positions, jnp.int32)
    chunk = walk_chunk_pages(P)
    assert chunk == min(P, 16)
    assert walk_grid(lambda *a: paged(*a), q, k_pages, v_pages, pages,
                     pos) == (B, P // chunk)
    out = paged(q, k_pages, v_pages, pages, pos)
    assert out.shape == q.shape and out.dtype == dt
    # a retired row reads one page of trash, whatever its frozen cursor: its
    # output is garbage the engine drops, and it must be a finite one
    assert np.isfinite(np.asarray(out, np.float32)).all()
    live = [b for b in range(B) if b not in retired or positions[b] < pt]
    got = np.asarray(out, np.float32)[live]
    ref = gather_reference(q, k_pages, v_pages, pages, pos)
    old = head_major_paged_attention(q, jnp.swapaxes(k_pages, 1, 2),
                                     jnp.swapaxes(v_pages, 1, 2), pages, pos)
    tol = 0.05 if dtype == "bfloat16" else 2e-6 * D / 16
    np.testing.assert_allclose(got, np.asarray(ref, np.float32)[live],
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(got, np.asarray(old, np.float32)[live],
                               atol=tol, rtol=tol)


# the tile body streams gcd(P, 16) pages a program: a chunk's span in
# positions is that times page_tokens, and a query tile is 256 rows
TILE_CASES = {
    # (table width, page tokens, queries, first positions)
    # a verify window whose depth (position + 5) is 5, C pt - 1, C pt and
    # C pt + 1 at 16 pages of 4: 64 positions a program
    "edges": (64, 4, 5, [0, 58, 59, 60]),
    # half a tile, at a page's edge and from the middle of a page, and one
    # query more
    "128 queries": (64, 4, 128, [0, 2, 64]),
    "129 queries": (64, 4, 129, [0, 30]),
    # exactly one tile; one query past it: a second tile, 255 rows padding
    "one tile": (64, 8, 256, [0, 2, 64]),
    "tile and one": (64, 8, 257, [0, 30]),
    # two tiles (384 queries) over four programs of 128 positions; from
    # position 100 (a prefix hit, mid-page) the second tile sees whole
    # chunks with no mask
    "two tiles": (64, 8, 384, [0, 100]),
    # three tiles over four programs of 256 positions, the published page
    "three tiles": (64, 16, 600, [0, 300]),
    # a table narrower than 16 pages: one program a tile
    "narrow": (8, 4, 5, [0, 10, 27]),
    # 12 pages walk gcd(12, 16) = 4 a program; depths 15, 16, 17 and 45
    "gcd 4": (12, 4, 5, [0, 10, 11, 12, 40]),
    "gcd 4, two tiles": (12, 32, 300, [0, 70]),
    # an odd width: a chunk of one page is the same code
    "odd": (7, 4, 5, [0, 3, 20]),
}
# the published shapes cost the interpreter 10-15 s a call (a program holds
# every head twice, masked and clear), so they take the cases that cross a
# chunk's edge, several tiles and a short chunk; every case runs under three
# small shapes of the same structure: four heads of 64 (two a slab, V on a
# slab's edge), three (V starting mid-slab, as GPT-2 XL's 25) and four
# query heads on two K/V heads of 128
TILE_HEADS = {**DECODE_HEADS, "even": (4, 4, 64), "odd": (3, 3, 64),
              "grouped": (4, 2, 128), "wide": (2, 2, 192)}
SMALL = ("even", "odd", "grouped")
TILE_MATRIX = (
    [(case, "float32", heads) for case in sorted(TILE_CASES)
     for heads in SMALL]
    + [(case, dtype, heads) for dtype in ("bfloat16", "int8")
       for case in ("edges", "two tiles", "gcd 4") for heads in SMALL]
    + [("edges", "float32", heads) for heads in sorted(DECODE_HEADS)]
    + [("two tiles", "bfloat16", "xl"), ("gcd 4", "int8", "falcon-h1"),
       # a head of 192 lanes is its own slab, cut off the 128-lane rows
       ("edges", "float32", "wide")])


@pytest.mark.kernel
@pytest.mark.parametrize("case,dtype,heads", TILE_MATRIX)
def test_tile_body_parity(case, dtype, heads):
    """More than one query a row (an admit, a suffix after a prefix hit, a
    verify window), or int8 pages, takes the tile body: a head at a time
    over a chunk of pages a program, the mask only where a chunk meets the
    diagonal or the row's depth. Against the gather oracle (over the
    dequantized arena for int8) at the three published head shapes, and
    against the parent's page-a-program kernel over head-major pages where
    the call is short enough for the interpreter to run that too."""
    rng = np.random.default_rng(8)
    H, Hkv, D = TILE_HEADS[heads]
    P, pt, L, positions = TILE_CASES[case]
    B = len(positions)
    N = B * P + 1
    quantized = dtype == "int8"
    dt = jnp.dtype("float32" if quantized else dtype)
    kf = rng.normal(size=(N, pt, Hkv, D)).astype(np.float32)
    vf = rng.normal(size=(N, pt, Hkv, D)).astype(np.float32)
    pages = jnp.asarray(1 + rng.permutation(N - 1)[:B * P].reshape(B, P),
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), dt)
    pos = jnp.asarray(positions, jnp.int32)
    if quantized:
        (k_pages, ks), (v_pages, vs) = quantize_pages(kf), quantize_pages(vf)
        kw = {"k_scale": ks, "v_scale": vs}
        k_ref = jnp.asarray(dequantize_pages(k_pages, ks))
        v_ref = jnp.asarray(dequantize_pages(v_pages, vs))
    else:
        k_pages = k_ref = jnp.asarray(kf, dt)
        v_pages = v_ref = jnp.asarray(vf, dt)
        kw = {}
    chunk = walk_chunk_pages(P)
    assert chunk == math.gcd(P, 16)
    tiles = -(-L // 256)
    assert walk_grid(lambda q, k, v: paged(q, k, v, pages, pos, **kw),
                     q, k_pages, v_pages) == (B, tiles, P // chunk)
    out = paged(q, k_pages, v_pages, pages, pos, **kw)
    assert out.shape == q.shape and out.dtype == dt
    ref = gather_reference(q, k_ref, v_ref, pages, pos)
    tol = {"bfloat16": 0.05, "int8": 2e-5 * D / 16}.get(dtype,
                                                         2e-6 * D / 16)
    got = np.asarray(out, np.float32)
    np.testing.assert_allclose(got, np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)
    if L <= 5:
        old = head_major_paged_attention(
            q, jnp.swapaxes(k_pages, 1, 2), jnp.swapaxes(v_pages, 1, 2),
            pages, pos, **kw)
        np.testing.assert_allclose(got, np.asarray(old, np.float32),
                                   atol=tol, rtol=tol)


def kinds_oracle(q, k_tok, v_tok, pages, positions, window, sink, vscale,
                 ring):
    """Plain numpy for what a layer's kind adds (ISSUE 44): K and V heads of
    their own widths, a window of keys, a sink in the denominator, a value
    scale; ``ring``: the table is a window layer's ring (slot s holds the
    newest logical page congruent to s at or before the query's own)."""
    q, k_tok, v_tok = (np.asarray(a, np.float32) for a in (q, k_tok, v_tok))
    B, L, H, D = q.shape
    P, pt, Hkv = pages.shape[1], k_tok.shape[1], k_tok.shape[2]
    out = np.zeros((B, L, H, v_tok.shape[-1]), np.float32)
    for b in range(B):
        k = np.repeat(k_tok[pages[b]].reshape(P * pt, Hkv, -1), H // Hkv, 1)
        v = np.repeat(v_tok[pages[b]].reshape(P * pt, Hkv, -1), H // Hkv, 1)
        col = np.arange(P * pt)
        k_pos = col
        if ring:
            cur = positions[b] // pt
            k_pos = (cur - (cur - col // pt) % P) * pt + col % pt
        q_pos = positions[b] + np.arange(L)
        seen = (k_pos[None] <= q_pos[:, None]) & (k_pos[None] >= 0)
        if window:
            seen &= k_pos[None] > q_pos[:, None] - window
        s = np.einsum("lhd,thd->hlt", q[b], k) / np.sqrt(D)
        s = np.where(seen[None], s, -1e30)
        if sink is not None:
            s = np.concatenate([s, np.broadcast_to(
                np.asarray(sink)[:, None, None], (H, L, 1))], -1)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = (p / p.sum(-1, keepdims=True))[..., :P * pt]
        out[b] = vscale * np.einsum("hlt,thd->lhd", np.where(seen[None], p, 0),
                                    v)
    return out


KIND_CASES = {
    # name: (B, L, H, Hkv, D, Dv, pt, P, window, sink, value scale,
    #        positions, ring)
    # a window layer's decode step over rings of 4 pages of 4: rows short of
    # one turn, on a page's edge, and several turns in
    "ring-first-turn": (4, 1, 8, 2, 24, 16, 4, 4, 8, True, 0.707,
                        [0, 3, 7, 9], True),
    "ring-edges": (4, 1, 8, 2, 24, 16, 4, 4, 8, True, 0.707,
                   [15, 16, 17, 31], True),
    "ring-many-turns": (4, 1, 8, 2, 24, 16, 4, 4, 8, True, 0.707,
                        [40, 41, 63, 100], True),
    # the published widths: 192 / 128 (a K head as 128 + a tail of 64), ring of 10
    "ring-published": (3, 1, 16, 8, 192, 128, 16, 10, 128, True, 0.707,
                       [5, 200, 3000], True),
    "full-published": (2, 1, 16, 4, 192, 128, 16, 32, 0, False, 0.707,
                       [300, 511], False),
    # a K width whose remainder divides no lane row (200 = 128 + 72) is
    # padded to whole rows, in both bodies
    "full-padded-width": (2, 1, 4, 2, 200, 128, 16, 8, 0, False, 1.0,
                          [5, 100], False),
    "tile-padded-width": (1, 40, 4, 2, 200, 128, 16, 8, 0, True, 0.5, [3],
                          False),
    # heads whose V half begins off a multiple of their own width
    "full-odd-widths": (1, 1, 4, 1, 12, 8, 4, 32, 0, False, 1.0, [21],
                        False),
    # the tile body: a window over the bucket's own pages (the mask cuts
    # inside the first live chunk, chunks before it never run), a sink, a
    # value scale; and a full layer at the two widths
    "tile-window": (2, 64, 8, 2, 24, 16, 4, 16, 8, True, 0.707, [0, 0],
                    False),
    "tile-window-published": (1, 512, 4, 2, 192, 128, 16, 32, 128, True,
                              0.707, [0], False),
    "tile-full-published": (1, 300, 4, 2, 192, 128, 16, 32, 0, False, 0.707,
                            [0], False),
    # tiles that start off a chunk's edge: a window's keys then lie in
    # three chunks, all the grid walks from the tile's first live one
    "tile-window-unaligned": (2, 520, 4, 2, 192, 128, 16, 64, 128, True,
                              0.707, [7, 250], False),
    "tile-sink-alone": (1, 40, 4, 2, 16, 16, 4, 16, 0, True, 1.0, [3], False),
    # 64 heads: a K/V head a program under a grid axis over them
    "tile-grouped-full": (1, 512, 64, 4, 192, 128, 16, 32, 0, False, 0.707,
                          [0], False),
    "tile-grouped-window": (1, 512, 64, 8, 192, 128, 16, 32, 128, True, 0.707,
                            [0], False),
}


@pytest.mark.kernel
@pytest.mark.parametrize("case", sorted(KIND_CASES))
def test_attention_kinds_parity(case):
    """Both bodies under what MiMo-V2-Flash's layers add, against plain
    numpy: interpret mode, float32."""
    from kubeml_tpu.ops.paged_attention import (tile_head_groups,
                                                unpack_kv_rows)

    (B, L, H, Hkv, D, Dv, pt, P, window, use_sink, vscale, positions,
     ring) = KIND_CASES[case]
    rng = np.random.default_rng(len(case))
    k_tok = rng.standard_normal((B * P + 1, pt, Hkv, D)).astype(np.float32)
    v_tok = rng.standard_normal((B * P + 1, pt, Hkv, Dv)).astype(np.float32)
    rows = pack_kv_rows(jnp.asarray(k_tok), jnp.asarray(v_tok))
    assert rows.shape[-1] == kv_row_width(Hkv, D, Dv)
    back = unpack_kv_rows(rows, Hkv, D, Dv)
    assert (np.asarray(back[0]) == k_tok).all()
    assert (np.asarray(back[1]) == v_tok).all()
    pages = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    q = rng.standard_normal((B, L, H, D)).astype(np.float32)
    sink = (1.0 + rng.standard_normal((H,))).astype(np.float32) \
        if use_sink else None
    positions = np.asarray(positions, np.int32)
    got = paged_attention(
        jnp.asarray(q), rows, jnp.asarray(pages), jnp.asarray(positions),
        kv_heads=Hkv, v_head_dim=Dv if Dv != D else 0, window=window,
        sink=None if sink is None else jnp.asarray(sink), value_scale=vscale,
        interpret=True)
    want = kinds_oracle(q, k_tok, v_tok, pages, positions, window, sink,
                        vscale, ring)
    assert got.shape == want.shape
    assert float(np.abs(np.asarray(got) - want).max()) < 5e-6
    assert (tile_head_groups(H, Hkv, D, Dv, L, 4) > 1) == ("grouped" in case)
    if (D, Dv) == (192, 128):
        # 320 lanes a K/V head hold its 320 values: the K heads' 128-lane
        # main parts, their 64-lane tails two to a lane row, the V heads
        assert rows.shape[-1] == Hkv * 320
        assert (np.asarray(rows[..., 128:256]) == k_tok[:, :, 1, :128]).all()
        assert (np.asarray(rows[..., Hkv * 128 + 64:Hkv * 128 + 128])
                == k_tok[:, :, 1, 128:]).all()


@pytest.mark.kernel
@pytest.mark.parametrize("position,n,width,window,groups,want", [
    # a 4,096-position admit of a window of 128 at 16 tokens a page: 16
    # tiles of 256 queries, each walking 3 chunk steps from its first live
    # chunk (a window's keys can lie in no more, however aligned) where the
    # table has 16; its window meets its own chunk and the one before (the
    # first tile: one)
    (0, 4096, 256, 128, 1, (31, 48)),
    (0, 4096, 256, 128, 8, (248, 384)),
    # without a window: the causal triangle, as before
    (0, 4096, 256, 0, 1, (136, 256)),
    (0, 4096, 256, 0, 4, (544, 1024)),
    # one tile whose window starts inside the second chunk
    (600, 64, 64, 128, 1, (2, 2)),
])
def test_tile_chunks_mirror_counts_a_window(position, n, width, window,
                                            groups, want):
    """The host's twin of the tile body's clamp knows the window's lower
    end and the heads' grid axis."""
    from kubeml_tpu.ops.paged_attention import tile_chunks

    assert tile_chunks(position, n, width, 16, 2, window=window,
                       groups=groups) == want
    assert walk_chunk_pages(10, ring=True) == 10    # a ring: one program
    assert walk_chunk_pages(10) == 2
    assert walk_chunk_pages(32, ring=True) == 16


@pytest.mark.kernel
@pytest.mark.parametrize("case,L,quantized,grid", [
    ("decode step", 1, False, (3, 2)),          # (rows, P / 16 pages)
    # (rows, query tiles, P / 16 pages)
    ("verify window", 5, False, (3, 1, 2)),
    ("prefill of two tiles", 300, False, (3, 2, 2)),
    ("int8 decode step", 1, True, (3, 1, 2)),
    ("int8 suffix", 8, True, (3, 1, 2)),
])
def test_which_body_a_call_takes(case, L, quantized, grid):
    """The rule is in the call's shapes: one query a row over an arena in
    the compute type takes the decode body, all heads one product; more
    queries, or int8 pages with their scales, take the tile body, a head at
    a time under tiles of 256 queries. Both walk 16 pages a program."""
    rng = np.random.default_rng(6)
    B, H, D, pt, P, N = 3, 2, 16, 4, 32, 40
    kf = rng.normal(size=(N, pt, H, D)).astype(np.float32)
    pages = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    pos = jnp.asarray([5, 0, 100], jnp.int32)
    if quantized:
        kq, ks = quantize_pages(kf)
        fn = lambda q, kv, s: paged(q, kv, kv, pages, pos, k_scale=s,
                                    v_scale=s)
        assert walk_grid(fn, q, kq, ks) == grid
    else:
        fn = lambda q, kv: paged(q, kv, kv, pages, pos)
        assert walk_grid(fn, q, jnp.asarray(kf)) == grid


@pytest.mark.kernel
def test_kernel_query_tiles_long_prefill():
    """A prefill longer than one query tile (128) walks the table once
    per tile, each tile clamped at its own causal depth; rows start at
    different bases so the tile/page boundaries do not line up."""
    rng = np.random.default_rng(3)
    B, H, D, pt, P, N, L = 2, 2, 16, 8, 24, 50, 136
    k_pages = jnp.asarray(rng.normal(size=(N, pt, H, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(N, pt, H, D)), jnp.float32)
    pages = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    pos = jnp.asarray([0, 51], jnp.int32)
    out = paged(q, k_pages, v_pages, pages, pos)
    ref = gather_reference(q, k_pages, v_pages, pages, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.kernel
def test_kernel_bf16_storage_dtype():
    """Production arenas are bf16; the kernel contracts the storage dtype
    with f32 accumulation, so parity holds at bf16 tolerance."""
    rng = np.random.default_rng(1)
    B, H, D, pt, P, N = 2, 2, 16, 4, 4, 12
    k_pages = jnp.asarray(rng.normal(size=(N, pt, H, D)), jnp.bfloat16)
    v_pages = jnp.asarray(rng.normal(size=(N, pt, H, D)), jnp.bfloat16)
    pages = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.bfloat16)
    pos = jnp.asarray([7, 11], jnp.int32)
    out = paged(q, k_pages, v_pages, pages, pos)
    assert out.dtype == jnp.bfloat16
    ref = gather_reference(q, k_pages, v_pages, pages, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=0.05)


@pytest.mark.kernel
@pytest.mark.parametrize("P,positions,L", [
    (4, [5, 9], 1),       # a table narrower than a decode program's pages
    (32, [5, 70], 1),     # two programs a row, the second dead for row 0
    (32, [63, 64], 1),    # a depth on either side of a program's edge
    (4, [5, 8], 4),       # the tile body (a verify window)
    (32, [5, 70], 4),     # its second program dead for row 0
    (32, [59, 60], 5),    # depths 64 and 65 on either side of its edge
    (12, [3, 14], 4),     # four pages a program
    (96, [0, 37], 264),   # two query tiles: the first's later pages elided
])
def test_kernel_poisoned_trash_page_cannot_leak(P, positions, L):
    """Every arena position a live row did NOT legitimately write — the
    reserved trash page 0, unallocated pages, and the slots past each
    row's cursor inside its own last page — is poisoned with huge values;
    the output must be bit-identical to the clean-arena run. This is the
    paged pool's whole safety story (stale writes are trash-redirected):
    the read side must never reach what the write side quarantined. Both
    bodies multiply a block of 16 pages at once, live and masked slots in
    one product: a masked probability is exactly 0 there too."""
    rng = np.random.default_rng(2)
    B, H, D, pt = 2, 2, 8, 4
    positions = np.array(positions)  # row b attends 0..positions[b] + L - 1
    N = 2 * P + 4
    pages = np.zeros((B, P), np.int32)
    # row tables: live pages allocated, the rest left at 0 (trash)
    nxt = 3
    for b in range(B):
        n_live = -(-(positions[b] + L) // pt)
        pages[b, :n_live] = np.arange(nxt, nxt + n_live)
        nxt += n_live
    clean = np.zeros((N, pt, H, D), np.float32)
    written = set()
    for b in range(B):
        for p_log in range(positions[b] + L):
            phys, off = pages[b, p_log // pt], p_log % pt
            clean[phys, off] = rng.normal(size=(H, D))
            written.add((phys, off))
    poisoned = clean.copy()
    for phys in range(N):
        for off in range(pt):
            if (phys, off) not in written:
                poisoned[phys, off] = 1e9
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    pages = jnp.asarray(pages)
    out_clean = paged(q, jnp.asarray(clean), jnp.asarray(clean), pages, pos)
    out_poison = paged(q, jnp.asarray(poisoned), jnp.asarray(poisoned),
                       pages, pos)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_poison))


@pytest.mark.kernel
def test_module_parity_prefill_then_steps():
    """Full CausalTransformer paged decode: prefill then per-token steps —
    pallas and gather clones must produce matching logits and matching
    arena contents (the kernel changes only the read; the write path is
    shared, so arenas differ only by the read impl's rounding propagating
    through deeper layers)."""
    m = tiny(max_len=32)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    pt, tp = 4, 8
    npages = 2 * tp + 1
    prompt = np.arange(1, 11, dtype=np.int32)[None]  # plen 10
    table = jnp.asarray([[1 + j for j in range(tp)]], jnp.int32)
    outs = {}
    for impl in ("gather", "pallas"):
        mod = m.clone(page_tokens=pt, kv_pages=npages, paged_attn=impl)
        cache = init_paged_cache(mod, variables, 1, tp)
        logits, vs = mod.apply(
            {**variables, "cache": cache}, prompt, decode=True,
            positions=jnp.zeros((1,), jnp.int32), pages=table,
            seq_lens=jnp.asarray([10], jnp.int32), mutable=["cache"])
        cache = vs["cache"]
        chain = [logits[:, -1]]
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for i in range(4):
            logits, vs = mod.apply(
                {**variables, "cache": cache}, tok[:, None], decode=True,
                positions=jnp.asarray([10 + i], jnp.int32), pages=table,
                mutable=["cache"])
            cache = vs["cache"]
            chain.append(logits[:, -1])
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        outs[impl] = (np.asarray(jnp.stack(chain)),
                      jax.tree.map(np.asarray, cache))
    np.testing.assert_allclose(outs["pallas"][0], outs["gather"][0],
                               atol=1e-5, rtol=1e-5)
    # the arenas agree at f32 tolerance (layer n's K/V derive from layer
    # n-1's attention OUTPUT, so the read impl's rounding propagates into
    # deeper layers' writes — but never diverges)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4),
        outs["pallas"][1], outs["gather"][1])


# --- live-table-width clamp accounting (host units) ---


def make_row(dec, prompt_len, max_new, pos_cap=None):
    lease = dec._pool.admit(np.arange(1, prompt_len + 1), max_new,
                            max_positions=dec.max_len)
    row = _Row(entry=None, index=0,
               prompt=np.arange(1, prompt_len + 1).astype(np.int32),
               max_new=max_new, temp=0.0, topk=0, eos=-1,
               key=np.zeros(2, np.uint32), lease=lease)
    row.pos_cap = prompt_len if pos_cap is None else pos_cap
    return row


def test_live_table_width_clamps_and_buckets(served_gather):
    dec = served_gather
    assert dec.table_pages == 16  # max_len 64 / pt 4
    # empty engine: the floor bucket (8 pages — sub-8 widths would double
    # the compiled-program set for almost no byte saving)
    assert dec._live_table_width(8) == 8
    rows = []
    try:
        row = make_row(dec, prompt_len=5, max_new=8)  # 12 pos -> 3 pages
        rows.append(row)
        dec._slot_rows[0] = row
        # 5 + 8 positions -> ceil(13/4) = 4 pages -> the 8-page floor
        assert dec._live_table_width(8) == 8
        # a huge advance caps at the row's lease width (3 pages) -> floor
        assert dec._live_table_width(1000) == 8
        # pos_cap never passes the row's final position
        dec._bump_pos_caps(1000)
        assert row.pos_cap == 5 + 8 - 1
        # deep row: bucketing rounds up the pow2 ladder, capped at the table
        deep = make_row(dec, prompt_len=30, max_new=30)  # 59 pos, 15 pages
        rows.append(deep)
        dec._slot_rows[1] = deep
        assert dec._live_table_width(4) == 16
    finally:
        dec._slot_rows[0] = dec._slot_rows[1] = None
        for r in rows:
            dec._pool.release(r.lease)
        dec._pool.check()


def test_chunk_kv_tokens_kernel_below_gather(served_gather):
    """The modeled KV span: gather reads every program row's full clamped
    table; the kernel reads only resident rows' live pages."""
    dec = served_gather
    row = make_row(dec, prompt_len=5, max_new=8)
    dec._slot_rows[0] = row
    try:
        w = dec._live_table_width(4)
        gather_tokens = dec._chunk_kv_tokens(w, 1)
        assert gather_tokens == dec.slots * w * dec.page_tokens
        dec.paged_attn = "pallas"
        kernel_tokens = dec._chunk_kv_tokens(w, 1)
        # one resident row at depth 5 -> ceil(6/4) = 2 pages of 4 tokens
        assert kernel_tokens == 8
        # deeper advance reads more pages: ceil((5+4)/4) = 3 pages
        assert dec._chunk_kv_tokens(w, 4) == 12
        assert kernel_tokens < gather_tokens
    finally:
        dec.paged_attn = "gather"
        dec._slot_rows[0] = None
        dec._pool.release(row.lease)
        dec._pool.check()


@pytest.fixture()
def served_gather():
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=8,
                               page_tokens=4, paged_attn="gather")
    try:
        yield dec
    finally:
        dec.close()


# --- engine-level token parity: pallas vs gather vs one-shot ---


def one_shot(m, variables, prompt, n, **kw):
    out = generate(m, variables, np.asarray(prompt, np.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out.tokens)


def drive(dec, prompts, max_news):
    entries = [dec.submit(GenerateRequest(prompts=p.tolist(),
                                          max_new_tokens=n))
               for p, n in zip(prompts, max_news)]
    return [dec.wait(e, timeout=600) for e in entries]


@pytest.mark.kernel
def test_engine_greedy_parity_pallas_vs_gather():
    """Acceptance: KUBEML_PAGED_ATTN=pallas emits tokens identical to the
    gather path across a mixed-length workload including a shared-prefix
    admission — and both match the one-shot baseline."""
    m = tiny(max_len=48)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(7)
    sysp = rng.integers(1, VOCAB, size=8).astype(np.int32)
    prompts = [
        rng.integers(1, VOCAB, size=(1, 3)).astype(np.int32),
        np.concatenate([sysp, rng.integers(1, VOCAB, size=4).astype(np.int32)])[None],
        np.concatenate([sysp, rng.integers(1, VOCAB, size=2).astype(np.int32)])[None],
        rng.integers(1, VOCAB, size=(1, 11)).astype(np.int32),
    ]
    max_news = [6, 8, 5, 3]
    refs = [one_shot(m, variables, p, n)[0].tolist()
            for p, n in zip(prompts, max_news)]
    outs = {}
    for impl in ("gather", "pallas"):
        dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                                   page_tokens=4, paged_attn=impl)
        try:
            results = drive(dec, prompts, max_news)
            outs[impl] = [r["tokens"][0] for r in results]
            # the second sysp request must have shared prefix pages in
            # both impls (the kernel reads shared pages identically)
            assert results[2]["prefix_cached_tokens"] == 8
            assert dec.telemetry()["paged_attn_kernel"] == (
                1.0 if impl == "pallas" else 0.0)
        finally:
            dec.close()
    assert outs["pallas"] == outs["gather"] == refs


@pytest.mark.kernel
@pytest.mark.spec
def test_engine_spec_verify_parity_pallas():
    """Self-drafting speculative decode through the kernel: the k+1-wide
    verify windows and the drafter's truncated-stack steps both attend
    through the page table; greedy output stays baseline-identical."""
    m = tiny(max_len=48)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, size=(1, l)).astype(np.int32)
               for l in (5, 9)]
    max_news = [7, 5]
    refs = [one_shot(m, variables, p, n)[0].tolist()
            for p, n in zip(prompts, max_news)]
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, paged_attn="pallas",
                               spec="self", spec_k=2, spec_adaptive=False,
                               spec_exit_layer=1)
    try:
        outs = [r["tokens"][0] for r in drive(dec, prompts, max_news)]
    finally:
        dec.close()
    assert outs == refs


@pytest.mark.kernel
def test_engine_int8_compose_parity_pallas():
    """int8 weights + the kernel: quantization changes the WEIGHTS
    identically under both read paths, so pallas vs gather token parity
    must survive the compose."""
    m = tiny(max_len=32)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    p = np.arange(1, 10, dtype=np.int32)[None]
    outs = {}
    for impl in ("gather", "pallas"):
        dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                                   page_tokens=4, paged_attn=impl,
                                   quantize="int8")
        try:
            outs[impl] = dec.wait(dec.submit(GenerateRequest(
                prompts=p.tolist(), max_new_tokens=6)), timeout=600)
        finally:
            dec.close()
    assert outs["pallas"]["tokens"] == outs["gather"]["tokens"]
    assert outs["pallas"]["lengths"] == outs["gather"]["lengths"]


# --- int8 KV-cache pages (ISSUE 16): quantized storage parity ---


def quantize_pages(pages_f32):
    """The write path's storage format, applied offline: per-page-per-head
    absmax scales ``[N, H]``, values ``round(x * 127 / scale)`` int8."""
    amax = np.abs(pages_f32).max(axis=(1, 3))  # [N, H]
    s = np.maximum(amax, 1e-30)
    q = np.clip(np.round(pages_f32 * 127.0 / s[:, None, :, None]),
                -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(amax, jnp.float32)


def dequantize_pages(q_pages, scales):
    return (np.asarray(q_pages, np.float32)
            * (np.asarray(scales) / 127.0)[:, None, :, None])


def test_resolve_kv_quant_values():
    assert resolve_kv_quant(None) == "off"
    assert resolve_kv_quant("off") == "off"
    assert resolve_kv_quant("int8") == "int8"
    # auto is reserved: resolves off everywhere until TPU parity evidence
    assert resolve_kv_quant("auto") == "off"
    with pytest.raises(ValueError):
        resolve_kv_quant("fp8")


@pytest.mark.kernel
@pytest.mark.parametrize("L,positions", [
    (1, [5, 0, 17]),        # per-token decode step at mixed depths
    (4, [3, 0, 12]),        # spec verify window (k+1 = 4)
    (8, [0, 8, 16]),        # suffix prefill, incl. page-aligned bases
])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_kernel_int8_parity_and_bounded_divergence(heads, L, positions):
    """The int8 kernel path against two references: the DEQUANTIZED gather
    (same storage bytes, same q*s/127 reconstruction — must match at
    f32-accumulation tolerance, the storage-format parity oracle) and the
    unquantized f32 gather (divergence bounded by the int8 step size)."""
    rng = np.random.default_rng(10)
    H, Hkv, D = HEADS[heads]
    B, pt, P, N = 3, 4, 6, 20
    kf = rng.normal(size=(N, pt, Hkv, D)).astype(np.float32)
    vf = rng.normal(size=(N, pt, Hkv, D)).astype(np.float32)
    kq, ks = quantize_pages(kf)
    vq, vs = quantize_pages(vf)
    pages = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    out = paged(q, kq, vq, pages, pos, k_scale=ks, v_scale=vs)
    deq_ref = gather_reference(q, jnp.asarray(dequantize_pages(kq, ks)),
                               jnp.asarray(dequantize_pages(vq, vs)),
                               pages, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(deq_ref),
                               atol=2e-5, rtol=2e-5)
    assert_equals_head_major(out, q, kq, vq, pages, pos, k_scale=ks,
                             v_scale=vs)
    f32_ref = gather_reference(q, jnp.asarray(kf), jnp.asarray(vf),
                               pages, pos)
    # bounded divergence: attention outputs are convex combinations of V
    # rows, each off by at most one int8 step (~scale/127 ~ 0.03 for unit
    # normals) plus the softmax shift from the K rounding
    err = float(np.abs(np.asarray(out) - np.asarray(f32_ref)).max())
    assert err < 0.1, f"int8 divergence {err} exceeds the storage bound"


@pytest.mark.kernel
@pytest.mark.parametrize("P", [4, 32])   # one short chunk; two of 16 pages
def test_kernel_int8_poisoned_arena_cannot_leak(P):
    """The poisoned-arena contract holds for quantized storage too: every
    position a live row did not write — trash page 0, unallocated pages,
    slots past each row's cursor — is poisoned with full-scale int8
    values, and unallocated pages' (and trash's) SCALES are poisoned huge.
    The output must be bit-identical to the clean-arena run."""
    rng = np.random.default_rng(11)
    B, H, D, pt, N = 2, 2, 8, 4, 10
    positions = np.array([5, 9])
    L = 1
    pages = np.zeros((B, P), np.int32)
    pages[0, :2] = [3, 4]
    pages[1, :3] = [5, 6, 7]
    dense = np.zeros((N, pt, H, D), np.float32)
    written = set()
    live_pages = {3, 4, 5, 6, 7}
    for b in range(B):
        for p_log in range(positions[b] + L):
            phys, off = pages[b, p_log // pt], p_log % pt
            dense[phys, off] = rng.normal(size=(H, D))
            written.add((phys, off))
    kq, ks = quantize_pages(dense)
    kq_p = np.asarray(kq).copy()
    ks_p = np.asarray(ks).copy()
    for phys in range(N):
        for off in range(pt):
            if (phys, off) not in written:
                kq_p[phys, off] = 127
        if phys not in live_pages:
            ks_p[phys] = 1e9  # incl. trash page 0
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    pages = jnp.asarray(pages)
    out_clean = paged(q, kq, kq, pages, pos, k_scale=ks, v_scale=ks)
    out_poison = paged(q, jnp.asarray(kq_p), jnp.asarray(kq_p), pages, pos,
                       k_scale=jnp.asarray(ks_p), v_scale=jnp.asarray(ks_p))
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_poison))


@pytest.mark.slow
@pytest.mark.kernel
def test_module_int8_kernel_matches_gather_oracle():
    """Full paged decode under KUBEML_KV_QUANT=int8: prefill then steps —
    the kernel and the dequantizing gather read the SAME quantized arena,
    so their logits must agree at f32 tolerance; against the unquantized
    model the divergence stays bounded."""
    m = tiny(max_len=32)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    pt, tp = 4, 8
    npages = 2 * tp + 1
    prompt = np.arange(1, 11, dtype=np.int32)[None]
    table = jnp.asarray([[1 + j for j in range(tp)]], jnp.int32)
    outs = {}
    for name, (impl, kvq) in {"i8-pallas": ("pallas", "int8"),
                              "i8-gather": ("gather", "int8"),
                              "f32": ("gather", "off")}.items():
        mod = m.clone(page_tokens=pt, kv_pages=npages, paged_attn=impl,
                      kv_quant=kvq)
        cache = init_paged_cache(mod, variables, 1, tp)
        if kvq == "int8":
            arena = cache["block_0"]["attn"]
            assert arena["kv_rows"].dtype == jnp.int8
            assert arena["kv_rows"].shape == (npages, pt, 128)
            assert arena["k_scale"].shape == (npages, 2)
        logits, vs = mod.apply(
            {**variables, "cache": cache}, prompt, decode=True,
            positions=jnp.zeros((1,), jnp.int32), pages=table,
            seq_lens=jnp.asarray([10], jnp.int32), mutable=["cache"])
        cache = vs["cache"]
        chain = [logits[:, -1]]
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for i in range(4):
            logits, vs = mod.apply(
                {**variables, "cache": cache}, tok[:, None], decode=True,
                positions=jnp.asarray([10 + i], jnp.int32), pages=table,
                mutable=["cache"])
            cache = vs["cache"]
            chain.append(logits[:, -1])
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        outs[name] = np.asarray(jnp.stack(chain))
    np.testing.assert_allclose(outs["i8-pallas"], outs["i8-gather"],
                               atol=1e-5, rtol=1e-5)
    err = float(np.abs(outs["i8-gather"] - outs["f32"]).max())
    assert 0 < err < 0.2, f"int8 logit divergence {err} out of bounds"


@pytest.mark.slow
def test_engine_int8_capacity_gauge_and_prefix_share():
    """The serving acceptance: at the same arena byte budget int8 mode
    admits >= 1.8x the pages, the kv_quant gauge exports 1, shared-prefix
    pages (whose scales travel with them) still dedupe, and the mixed
    workload's greedy tokens agree with the unquantized engine at the
    token-agreement threshold."""
    m = tiny(max_len=48)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(7)
    sysp = rng.integers(1, VOCAB, size=8).astype(np.int32)
    prompts = [
        rng.integers(1, VOCAB, size=(1, 3)).astype(np.int32),
        np.concatenate([sysp, rng.integers(1, VOCAB, size=4).astype(np.int32)])[None],
        np.concatenate([sysp, rng.integers(1, VOCAB, size=2).astype(np.int32)])[None],
        rng.integers(1, VOCAB, size=(1, 11)).astype(np.int32),
    ]
    max_news = [6, 8, 5, 3]
    outs = {}
    pages_total = {}
    for kvq in ("off", "int8"):
        dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                                   page_tokens=4, pages=25,
                                   paged_attn="gather", kv_quant=kvq)
        try:
            results = drive(dec, prompts, max_news)
            outs[kvq] = np.concatenate(
                [np.asarray(r["tokens"][0]) for r in results])
            assert results[2]["prefix_cached_tokens"] == 8
            t = dec.telemetry()
            pages_total[kvq] = t["pages_total"]
            assert t["kv_quant"] == (1.0 if kvq == "int8" else 0.0)
        finally:
            dec.close()
    # same byte budget, >= 1.8x the pages (f32 arenas actually reach ~4x;
    # the scale arenas' overhead is charged by the derivation)
    assert pages_total["int8"] >= 1.8 * pages_total["off"]
    agreement = float(np.mean(outs["int8"] == outs["off"]))
    assert agreement >= 0.9, f"token agreement {agreement} below threshold"


@pytest.mark.slow
def test_engine_int8_kv_read_bytes_storage_dtype():
    """The accounting acceptance: modeled kv_read_bytes under int8 storage
    is exactly itemsize-ratio smaller (f32 arenas: 4x) than the
    unquantized engine's on the identical workload — the halving story on
    /metrics, per caller."""
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    p = np.arange(1, 8, dtype=np.int32)[None]
    read_bytes = {}
    for kvq in ("off", "int8"):
        dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                                   page_tokens=4, pages=33,
                                   paged_attn="gather", kv_quant=kvq)
        try:
            dec.wait(dec.submit(GenerateRequest(prompts=p.tolist(),
                                                max_new_tokens=6)),
                     timeout=600)
            read_bytes[kvq] = dec.stats.snapshot()["kv_read_bytes"]
            token_bytes = dec._kv_token_bytes
            itemsize = 1 if kvq == "int8" else 4
            assert token_bytes == m.depth * 2 * m.embed_dim * itemsize
        finally:
            dec.close()
    assert read_bytes["off"] == 4 * read_bytes["int8"] > 0


@pytest.mark.slow
@pytest.mark.kernel
@pytest.mark.spec
def test_engine_spec_rollback_over_quantized_pages():
    """Speculative verify windows write k lookahead positions into int8
    pages and the host rolls rejected drafts back by cursor. Rejected
    drafts may have grown page scales (monotone absmax) — that is bounded
    precision loss, never corruption: the kernel and gather engines read
    the same quantized arena and must emit identical tokens."""
    m = tiny(max_len=48)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, size=(1, l)).astype(np.int32)
               for l in (5, 9)]
    max_news = [7, 5]
    outs = {}
    for impl in ("pallas", "gather"):
        dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                                   page_tokens=4, paged_attn=impl,
                                   kv_quant="int8", spec="self", spec_k=2,
                                   spec_adaptive=False, spec_exit_layer=1)
        try:
            outs[impl] = [r["tokens"][0] for r in drive(dec, prompts,
                                                        max_news)]
        finally:
            dec.close()
    assert outs["pallas"] == outs["gather"]


@pytest.mark.slow
@pytest.mark.paged
def test_allocator_chaos_storm_int8_doubled_arena():
    """The PR-12 chaos storm re-run with KUBEML_KV_QUANT=int8: the byte
    budget of 41 f32 pages derives ~4x the page count, and under the
    concurrent cancel/timeout/shed storm the pool invariants must hold
    exactly at that doubled-plus capacity — every page returned once, the
    trie the only holder at drain."""
    import threading
    import time

    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.utils import resilience

    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = PagedBatchingDecoder(m, variables, slots=3, chunk_steps=8,
                               page_tokens=4, pages=41, kv_quant="int8",
                               paged_attn="gather", queue_limit=6,
                               shed_policy="oldest")
    assert dec._pool.num_pages >= 1.8 * 41
    rng = np.random.default_rng(1234)
    sysp = rng.integers(1, VOCAB, size=8).astype(np.int32)
    errors = []

    def client(i):
        r = np.random.default_rng(1000 + i)
        try:
            for _ in range(3):
                if r.random() < 0.4:
                    prompt = np.concatenate(
                        [sysp,
                         r.integers(1, VOCAB, size=int(r.integers(2, 6)))])
                else:
                    prompt = r.integers(1, VOCAB, size=int(r.integers(3, 14)))
                req = GenerateRequest(
                    prompts=[prompt.astype(np.int32).tolist()],
                    max_new_tokens=int(r.integers(2, 24)),
                    temperature=0.7 if r.random() < 0.3 else 0.0,
                    seed=int(r.integers(1, 1 << 30)))
                roll = r.random()
                try:
                    if roll < 0.2:
                        with resilience.bind_deadline(time.time() + 0.01):
                            e = dec.submit(req)
                        dec.wait(e, timeout=30)
                    elif roll < 0.45:
                        e = dec.submit(req)
                        dec.wait(e, timeout=0.01)
                    elif roll < 0.6:
                        e = dec.submit(req)
                        time.sleep(float(r.random()) * 0.05)
                        dec.cancel(e)
                    else:
                        e = dec.submit(req)
                        dec.wait(e, timeout=600)
                except KubeMLError:
                    pass
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        assert not errors
        deadline = time.time() + 60
        while time.time() < deadline:
            with dec._cond:
                idle = (not dec._pending and not dec._busy()
                        and not dec._draining)
            if idle:
                break
            time.sleep(0.05)
        assert idle, "engine did not drain"
        chk = dec._pool.check()
        assert chk["held"] == chk["trie_pages"]
        dec._pool.trie.flush()
        assert dec._pool.free_pages() == dec._pool.capacity
        dec._pool.check()
        with dec._cond:
            assert sorted(dec._free) == [0, 1, 2]
            assert all(r is None for r in dec._slot_rows)
    finally:
        dec.close()


# --- KV-read accounting (satellite: kubeml_serving_kv_read_bytes_total) ---


def test_kv_read_accounting_counts_and_bandwidth():
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    p = np.arange(1, 8, dtype=np.int32)[None]
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, paged_attn="gather")
    try:
        dec.wait(dec.submit(GenerateRequest(prompts=p.tolist(),
                                            max_new_tokens=6)), timeout=600)
        snap = dec.stats.snapshot()
        assert snap["kv_read_bytes"] > 0
        # decode chunks observed achieved bandwidth (prefill is bytes-only)
        assert snap["hist"]["kv_bandwidth"]["count"] >= 1
        # bandwidth observations are bytes/sec — strictly positive
        assert snap["hist"]["kv_bandwidth"]["sum"] > 0
    finally:
        dec.close()


def test_kv_read_clamped_below_full_table():
    """The fallback-path cheap win, measured in the counter: the clamped
    gather reads a small pow2 bucket of the reserved table, so modeled
    bytes land far under the full-table worst case."""
    m = tiny()  # max_len 64 -> 16-page tables
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    p = np.arange(1, 6, dtype=np.int32)[None]  # 5 + 3 tokens -> 2 pages
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, paged_attn="gather")
    try:
        dec.wait(dec.submit(GenerateRequest(prompts=p.tolist(),
                                            max_new_tokens=4)), timeout=600)
        snap = dec.stats.snapshot()
        token_bytes = dec._kv_token_bytes
        # worst case: every decode step + the prefill forward gathers the
        # full 16-page table; the clamp holds this shallow workload in the
        # 8-page floor bucket, halving the modeled reads
        forwards = snap["device_steps"] + snap["admission_waves"]
        full = forwards * dec.slots * dec.table_pages * dec.page_tokens \
            * token_bytes
        assert 0 < snap["kv_read_bytes"] <= full * 0.55
    finally:
        dec.close()
