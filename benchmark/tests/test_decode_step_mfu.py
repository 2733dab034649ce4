"""``decode_step_mfu`` (PR 36): the whole decode step's least time, counted
by hand on toy shapes and checked at the published ones, and the reader on a
hand-made trace, on another family's, and on an empty one.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import layers, reduce, spec  # noqa: E402
from benchmark.costs import decode_step  # noqa: E402

PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# a GPT-2 of 2 layers x 8 with 2 heads of 4, MLP 16, 10 positions, 11 tokens
TOY = {"builder": "gpt2", "n_layer": 2, "n_embd": 8, "n_head": 2,
       "n_inner": 16, "n_positions": 10, "vocab_size": 11,
       "compute_dtype": "bfloat16"}
# a layer: 4 projections of 8 x 8 with biases, two LayerNorms, an MLP of
# 8 x 16 and 16 x 8 with biases; then the last LayerNorm and the head.
# The tables (11 x 8 tokens, 10 x 8 positions) are not read by a step.
TOY_ELEMENTS = 2 * (4 * (64 + 8) + 4 * 8 + 2 * 128 + 16 + 8) + 16 + 8 * 11


def _shapes(cfg):
    return spec.plugin("models", cfg["builder"]).shapes(cfg)


def test_a_toy_step_counted_by_hand():
    shapes = _shapes(TOY)
    assert decode_step.weight_elements(TOY, shapes) == TOY_ELEMENTS == 1304
    # 3 live rows holding 30 tokens: every weight read once at 2 B and
    # multiplied by each row; K and V of 30 tokens x 2 layers x 8 wide at
    # 2 B each, and 4 operations a token, layer and element of a head
    flops, nbytes = decode_step.decode_step(TOY, shapes, 3, 30)
    assert nbytes == 2 * 1304 + 2 * 8 * 2 * 30 * 2
    assert flops == 2 * 1304 * 3 + 4 * 8 * 30 * 2
    # float32 arithmetic would need 4 B a weight
    wide = dict(TOY, compute_dtype="float32")
    assert decode_step.decode_step(wide, shapes, 3, 30)[1] == \
        4 * 1304 + 2 * 8 * 2 * 30 * 2
    # no live row: the weights are still read
    assert decode_step.decode_step(TOY, shapes, 0, 0) == (0.0, 2.0 * 1304)


@pytest.mark.parametrize("cell, dense, kv_bytes_a_token", [
    # 838M less the token and position tables (50257 + 1024) x 1280
    ("gpt2-large.chat", 772_719_360, 36 * 2 * 1280 * 2),
    ("gpt2-xl.docs", 1_555_972_800, 48 * 2 * 1600 * 2),
    # K/V by the 4 K/V heads of 128, not the 20 query heads
    ("falcon-h1-34b.turns", 2_747_842_112, 6 * 2 * 4 * 128 * 2),
    # 3.896B less the table and the 5 x 64 routed experts; 1,152 B a latent
    ("glm-4.7-flash.rag", 558_532_416, 6 * 1152),
])
def test_the_published_shapes(cell, dense, kv_bytes_a_token):
    cfg = spec.load_cell(cell).config
    shapes = _shapes(cfg)
    assert decode_step.weight_elements(cfg, shapes) == dense
    # one row at depth 0 against one at depth 1000: the cache's bytes
    b0 = decode_step.decode_step(cfg, shapes, 1, 0)[1]
    b1 = decode_step.decode_step(cfg, shapes, 1, 1000)[1]
    assert b1 - b0 == 1000 * kv_bytes_a_token
    # memory bounds a step at every cell's rows
    rows = cfg["deployment"]["serving_slots"]
    flops, nbytes = decode_step.decode_step(cfg, shapes, rows, rows * 1000)
    assert decode_step.min_seconds(flops, nbytes, PEAKS)[1] == "memory"


def test_state_and_routed_experts_are_counted_once_by_their_own_costs():
    falcon = spec.load_cell("falcon-h1-34b.turns").config
    shapes = _shapes(falcon)
    one = decode_step.decode_step(falcon, shapes, 1, 0)[1]
    two = decode_step.decode_step(falcon, shapes, 2, 0)[1]
    # a row's state, read and written: 6 layers x 32 heads x 128 x 256 x 4 B
    assert two - one == pytest.approx(2 * 6 * 32 * 128 * 256 * 4, rel=0.01)
    glm = spec.load_cell("glm-4.7-flash.rag").config
    shapes = _shapes(glm)
    assert decode_step.routed_expert_elements(glm) == \
        5 * 64 * 3 * 2048 * 1536
    none = decode_step.decode_step(glm, shapes, 32, 0)[1]
    some = decode_step.decode_step(glm, shapes, 32, 0, touched=10,
                                   assignments=0)[1]
    assert some - none == 10 * 3 * 2048 * 1536 * 2   # 18.87 MB an expert


def _reading(cfg, ops, modules, records, counters=({}, {}), wall_zero=100.0):
    trace = reduce.Trace(lines={(PLANE, reduce.OPS_LINE): ops,
                                (PLANE, reduce.MODULES_LINE): modules},
                         wall_zero=wall_zero)
    win = SimpleNamespace(t_open=100.0, seconds=10.0, records=list(records),
                          counters=counters)
    return layers.Reading(cell=SimpleNamespace(config=cfg), win=win,
                          trace=trace, peaks=PEAKS)


def _two_programs():
    """Two decode programs of one step over the toy's two layers, 1 us
    each, at trace time 1 and 2."""
    ops, modules = [], []
    for t in (1.0, 2.0):
        modules.append(("jit__unknown(123)", t, 1e-6))
        ops += [(f"%attn.{layer} custom-call", t + 2e-7 * (1 + layer), 1e-7)
                for layer in range(2)]
    return ops, modules


def test_the_reader_on_a_hand_made_trace():
    ops, modules = _two_programs()
    # one request decoding all through the window: 20 tokens of prompt and
    # one more a second; one that ended before the trace began
    records = [
        {"error": None, "first": 0.0, "last": 10.0, "prompt_tokens": 20,
         "tokens": list(range(10))},
        {"error": None, "first": 0.0, "last": 0.5, "prompt_tokens": 20,
         "tokens": list(range(10))}]
    r = _reading(TOY, ops, modules, records)
    read = spec.plugin("layer_metrics", "decode_step_mfu").read
    least = sum((2 * 1304 + 2 * 8 * 2 * 2 * (20 + t)) / 819e9
                for t in (1.0000005, 2.0000005))
    assert read(r) == pytest.approx(100 * least / 2e-6)
    assert spec.plugin("layer_metrics",
                       "decode_step_mfu.capacity").read is read
    # a step faster than the chip could be is a fault, not a reading
    slow_chip = _reading(TOY, ops, modules, records)
    slow_chip.peaks = {k: v / 1000 for k, v in PEAKS.items()}
    with pytest.raises(ValueError, match="over 105%"):
        read(slow_chip)
    # a configuration may name its own count (costs/<step_costs>.py)
    named = _reading(dict(TOY, step_costs="decode_step"), ops, modules,
                     records)
    assert read(named) == read(r)


def test_the_reader_returns_none_where_there_is_nothing_to_read():
    read = spec.plugin("layer_metrics", "decode_step_mfu").read
    ops, modules = _two_programs()
    assert read(_reading(TOY, [], [], [])) is None
    # decode programs but no tie to the wall clock: the rows are unknown
    assert read(_reading(TOY, ops, modules, [], wall_zero=None)) is None
    # prefill programs only
    assert read(_reading(TOY, ops, [("jit__prefill_admit_impl(1)", 1.0,
                                     1e-6)], [])) is None
