"""How much of the page walk's grid is real: the engine's two counts
(``walk_chunks_live``, ``walk_chunks_grid``: serving/stats.py, fed by the
paged engine at each chunk dispatch) and the benchmark's reader of them
(``benchmark/layer_metrics/walk_live_chunk_share.py``).

The decode body of ``ops/paged_attention.py`` runs every program row by the
table width's chunks of 16 pages, whatever a row holds; the counts say which
of those programs had pages to read. The tile body's twins (PR 40:
``tile_chunks_live``, ``tile_chunks_grid``, read by
``layer_metrics/tile_live_chunk_share_capacity.py``) count a prefill's query
tiles by the same chunks: those under a tile's causal depth are live. Host
arithmetic only: no kernel runs in the unit cases, and the engine runs are
the tiny model in interpret mode."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.layer_metrics import (  # noqa: E402
    latent_walk_live_share, tile_live_chunk_share_capacity,
    walk_live_chunk_share, walk_live_chunk_share_capacity)
from benchmark.layers import Reading  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models.gpt import CausalTransformer  # noqa: E402
from kubeml_tpu.ops.paged_attention import (tile_chunks,  # noqa: E402
                                            walk_chunk_pages)
from kubeml_tpu.ps.metrics import SERVING_COUNTERS  # noqa: E402
from kubeml_tpu.serving.batcher import PagedBatchingDecoder, _Row  # noqa: E402

VOCAB = 101


def tiny(max_len=256):
    return CausalTransformer(vocab_size=VOCAB, max_len=max_len, embed_dim=32,
                             depth=2, num_heads=2)


def reading(c0, c1):
    return Reading(cell=None, win=SimpleNamespace(counters=(c0, c1)),
                   trace=None, peaks={})


# --- the reader, on made-up snapshots ---------------------------------------


@pytest.mark.parametrize("reader", [walk_live_chunk_share,
                                    walk_live_chunk_share_capacity])
@pytest.mark.parametrize("c0,c1,want", [
    # the window's growth, not the totals: (900 - 300) of (2400 - 400)
    ({"walk_chunks_live": 300.0, "walk_chunks_grid": 400.0},
     {"walk_chunks_live": 900.0, "walk_chunks_grid": 2400.0}, 30.0),
    # every program live
    ({"walk_chunks_live": 0.0, "walk_chunks_grid": 0.0},
     {"walk_chunks_live": 64.0, "walk_chunks_grid": 64.0}, 100.0),
    # a commit before the counters, or a family that walks latents: the
    # snapshot has neither key, and the reader says nothing
    ({"device_steps": 1.0}, {"device_steps": 9.0}, None),
    ({"walk_chunks_grid": 5.0}, {"walk_chunks_grid": 9.0}, None),
    # no step in the window: nothing to divide by
    ({"walk_chunks_live": 3.0, "walk_chunks_grid": 8.0},
     {"walk_chunks_live": 3.0, "walk_chunks_grid": 8.0}, None),
])
def test_the_reader_on_a_made_up_snapshot(reader, c0, c1, want):
    got = reader.read(reading(c0, c1))
    assert got == want
    assert got is None or 0.0 <= got <= 100.0


@pytest.mark.parametrize("c0,c1,want", [
    # the window's growth: 10 of 16 programs an admit and layer, 10 admits
    ({"tile_chunks_live": 360.0, "tile_chunks_grid": 576.0},
     {"tile_chunks_live": 3960.0, "tile_chunks_grid": 6336.0}, 62.5),
    # one tile, one chunk (a prompt bucket of 128 under a table of 8 pages)
    ({"tile_chunks_live": 0.0, "tile_chunks_grid": 0.0},
     {"tile_chunks_live": 6.0, "tile_chunks_grid": 6.0}, 100.0),
    # the parent commit, or a family whose admits do not walk K/V pages
    ({"walk_chunks_live": 1.0, "walk_chunks_grid": 2.0},
     {"walk_chunks_live": 5.0, "walk_chunks_grid": 9.0}, None),
    ({"tile_chunks_grid": 5.0}, {"tile_chunks_grid": 9.0}, None),
    # no admit in the window
    ({"tile_chunks_live": 3.0, "tile_chunks_grid": 8.0},
     {"tile_chunks_live": 3.0, "tile_chunks_grid": 8.0}, None),
])
def test_the_tile_reader_on_a_made_up_snapshot(c0, c1, want):
    got = tile_live_chunk_share_capacity.read(reading(c0, c1))
    assert got == want
    assert got is None or 0.0 <= got <= 100.0


@pytest.mark.parametrize("c0,c1,want", [
    # the latent walk's loop (PR 49): the window's growth, live trips of the
    # trips run; the rest were dead rows' over the trash page
    ({"latent_walk_trips_live": 40.0, "latent_walk_trips_run": 50.0},
     {"latent_walk_trips_live": 2980.0, "latent_walk_trips_run": 3050.0},
     98.0),
    ({"latent_walk_trips_live": 0.0, "latent_walk_trips_run": 0.0},
     {"latent_walk_trips_live": 64.0, "latent_walk_trips_run": 64.0}, 100.0),
    # a K/V engine, or the commit before the counters
    ({"walk_chunks_live": 1.0, "walk_chunks_grid": 2.0},
     {"walk_chunks_live": 5.0, "walk_chunks_grid": 9.0}, None),
    ({"latent_walk_trips_run": 5.0}, {"latent_walk_trips_run": 9.0}, None),
    # no step in the window
    ({"latent_walk_trips_live": 3.0, "latent_walk_trips_run": 8.0},
     {"latent_walk_trips_live": 3.0, "latent_walk_trips_run": 8.0}, None),
])
def test_the_latent_reader_on_a_made_up_snapshot(c0, c1, want):
    got = latent_walk_live_share.read(reading(c0, c1))
    assert got == want
    assert got is None or 0.0 <= got <= 100.0


# (start, queries, table width, page tokens, itemsize) -> (live, grid)
@pytest.mark.parametrize("call,want", [
    # the docs cells' admit: 4 tiles of 256 by 4 chunks of 16 pages of 16;
    # under the diagonal lie 1, 2, 3, 4
    ((0, 1024, 64, 16, 2), (10, 16)),
    # chat's 1 x 512: 2 tiles by 2 chunks
    ((0, 512, 32, 16, 2), (3, 4)),
    # Falcon-H1's 1 x 128 under 8 pages: one program, whole
    ((0, 128, 8, 16, 2), (1, 1)),
    # after a prefix hit 512 deep, the same 512 queries under 64 pages: the
    # prefix's two chunks are live for every tile
    ((512, 512, 64, 16, 2), (3 + 4, 8)),
    # a table of 12 pages walks gcd(12, 16) = 4 a program, of 7 one
    ((0, 128, 12, 16, 2), (2, 3)),
    ((0, 100, 7, 16, 4), (7, 7)),
    # a bucket whose positions run past the table: the depth stops at it
    ((96, 64, 8, 16, 2), (1, 1)),
])
def test_the_tile_bodys_grid_by_host_arithmetic(call, want):
    assert tile_chunks(*call) == want


# --- the engine's two counts ------------------------------------------------


def make_row(dec, prompt_len, max_new):
    lease = dec._pool.admit(np.arange(1, prompt_len + 1), max_new,
                            max_positions=dec.max_len)
    row = _Row(entry=None, index=0,
               prompt=np.arange(1, prompt_len + 1).astype(np.int32),
               max_new=max_new, temp=0.0, topk=0, eos=-1,
               key=np.zeros(2, np.uint32), lease=lease)
    row.pos_cap = prompt_len
    return row


@pytest.fixture()
def engine():
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = PagedBatchingDecoder(m, variables, slots=3, chunk_steps=4,
                               page_tokens=4, paged_attn="pallas")
    try:
        yield dec
    finally:
        dec.close()


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_two_live_rows_and_a_retired_one(engine, steps):
    """Three program rows, a 64-page table (16 pages, 64 positions, a
    program): a row 150 deep has pages in 3 of its 4 programs, a row 5 deep
    in 1, the retired row in none; the grid is all 12, times the model's 2
    attention layers, a step. A deeper step of the same chunk counts its own
    depth: the 193rd position opens the deep row's fourth program."""
    dec = engine
    assert dec.stats.walks_kv_chunks
    deep, shallow = make_row(dec, 150, 60), make_row(dec, 5, 8)
    dec._slot_rows[0], dec._slot_rows[2] = deep, shallow   # slot 1: retired
    try:
        w = dec._live_table_width(steps)
        assert w == 64 and walk_chunk_pages(w) == 16
        live, grid = dec._walk_chunks(w, steps)
        layers = 2
        assert grid == steps * 3 * 4 * layers
        assert live == steps * (3 + 1) * layers
        deep.pos_cap = 191      # its next query is the 192nd position, the
        live, _ = dec._walk_chunks(w, steps)    # one after opens program 4
        assert live == ((3 + 1) + (steps - 1) * (4 + 1)) * layers
    finally:
        dec._slot_rows[0] = dec._slot_rows[2] = None
        for r in (deep, shallow):
            dec._pool.release(r.lease)
        dec._pool.check()


@pytest.mark.parametrize("impl,kv_quant,counted", [
    ("pallas", "off", True),     # steps take the decode body
    ("gather", "off", False),    # no kernel, no grid
    ("pallas", "int8", False),   # int8 pages keep the tile body
])
def test_the_snapshot_carries_the_counts_where_steps_take_the_body(
        impl, kv_quant, counted):
    m = tiny(max_len=64)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, paged_attn=impl,
                               kv_quant=kv_quant)
    try:
        dec.wait(dec.submit(GenerateRequest(
            prompts=[list(range(1, 8))], max_new_tokens=6)), timeout=600)
        snap = dec.telemetry()
    finally:
        dec.close()
    assert ("walk_chunks_live" in snap) == counted
    assert ("walk_chunks_grid" in snap) == counted
    assert ("tile_chunks_live" in snap) == counted
    assert ("tile_chunks_grid" in snap) == counted
    if counted:
        # the admit: a bucket of 8 queries (one tile) under a table of 8
        # pages (one chunk), 2 layers
        assert snap["tile_chunks_grid"] == snap["tile_chunks_live"] == 2
        # 5 steps after the prefill's token, 2 layers, 2 program rows, one
        # program a row (a table of 8 pages): one row live
        assert snap["walk_chunks_grid"] == 5 * 2 * 2
        assert snap["walk_chunks_live"] == 5 * 2


def test_an_engine_that_never_said_so_reports_neither():
    """The counts are the paged engine's to switch on (a latent walk, the
    slot engine and a bare stats object have no K/V grid: the latent case is
    in tests/test_glm_moe_lite.py); and both have a name on a scrape."""
    from kubeml_tpu.serving.stats import DecoderStats

    snap = DecoderStats(slots=2).snapshot()
    assert "walk_chunks_live" not in snap and "walk_chunks_grid" not in snap
    assert "tile_chunks_live" not in snap and "tile_chunks_grid" not in snap
    keys = {key for key, _ in SERVING_COUNTERS.values()}
    assert {"walk_chunks_live", "walk_chunks_grid", "tile_chunks_live",
            "tile_chunks_grid"} <= keys


def test_an_admit_at_position_0_and_one_after_a_prefix_hit():
    """The engine's tile counts on the tiny model, page_tokens 4 and a
    float32 arena: a 300-token prompt admits as a bucket of 512 queries (two
    tiles of 256) under a table of 128 pages (8 chunks of 16 pages, 64
    positions each): the first tile sees 4 chunks, the second all 8. The
    same prompt again shares its 296 whole-page tokens: the suffix's bucket
    is one tile of 8 queries at position 296, under the same table, and
    sees the prefix's chunks, 5 of 8."""
    m = tiny(max_len=1024)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, paged_attn="pallas")
    prompt = [[1 + i % 100 for i in range(300)]]
    layers = 2
    try:
        dec.wait(dec.submit(GenerateRequest(prompts=prompt,
                                            max_new_tokens=2)), timeout=600)
        first = dec.telemetry()
        assert first["tile_chunks_grid"] == 2 * 8 * layers
        assert first["tile_chunks_live"] == (4 + 8) * layers
        out = dec.wait(dec.submit(GenerateRequest(prompts=prompt,
                                                  max_new_tokens=2)),
                       timeout=600)
        assert out["prefix_cached_tokens"] == 296
        second = dec.telemetry()
    finally:
        dec.close()
    assert second["tile_chunks_grid"] - first["tile_chunks_grid"] == 8 * layers
    assert second["tile_chunks_live"] - first["tile_chunks_live"] == 5 * layers
