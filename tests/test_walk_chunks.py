"""How much of the page walk's grid is real: the engine's two counts
(``walk_chunks_live``, ``walk_chunks_grid``: serving/stats.py, fed by the
paged engine at each chunk dispatch) and the benchmark's reader of them
(``benchmark/layer_metrics/walk_live_chunk_share.py``).

The decode body of ``ops/paged_attention.py`` runs every program row by the
table width's chunks of 16 pages, whatever a row holds; the counts say which
of those programs had pages to read. Host arithmetic only: no kernel runs in
the unit cases, and the one engine run is the tiny model in interpret mode."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.layer_metrics import (  # noqa: E402
    walk_live_chunk_share, walk_live_chunk_share_capacity)
from benchmark.layers import Reading  # noqa: E402
from kubeml_tpu.api.types import GenerateRequest  # noqa: E402
from kubeml_tpu.models.gpt import CausalTransformer  # noqa: E402
from kubeml_tpu.ops.paged_attention import decode_chunk_pages  # noqa: E402
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
        assert w == 64 and decode_chunk_pages(w) == 16
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
    if counted:
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
    keys = {key for key, _ in SERVING_COUNTERS.values()}
    assert {"walk_chunks_live", "walk_chunks_grid"} <= keys
