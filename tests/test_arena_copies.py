"""Does a paged program copy the KV pool? (ROADMAP S1)

The arena's layout is a contract between its write (an XLA scatter) and its
read (a Mosaic custom call), and XLA answers a layout neither likes with
copies of the whole pool, every layer, every step: the head-major arenas
cost six a layer, 26-31 ms of a 48-62 ms GPT-2 decode step on the chip, and
an unrelated edit once doubled their count (PR 29). Interpret mode cannot
see any of it. This compiles the paged forward pass for a described v5e, in
a subprocess (it loads the TPU plugin, which the test session must not), at
the three configurations' attention shapes, and holds the count of
pool-sized copies at zero (tests/arena_copies_proc.py says what it counts).
"""

import subprocess
import sys
from pathlib import Path

import pytest

from arena_copies_proc import SHAPES, pool_sized

CASES = {f"{name}-{case}" for name in SHAPES for case in ("step", "admit")}


@pytest.mark.kernel
def test_paged_programs_hold_no_pool_sized_copy():
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "arena_copies_proc.py")],
        capture_output=True, text=True, timeout=900)
    if proc.returncode == 77:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    lines = proc.stdout.splitlines()
    found = [l for l in lines if l.startswith("COPIES ")]
    assert proc.returncode == 0 and not found, (
        "\n".join(found) or proc.stderr[-2000:])
    assert {l[3:] for l in lines if l.startswith("OK ")} == CASES


@pytest.mark.parametrize("line,want", [
    # the head-major arena's relayout for its scatter
    ("  %copy.3 = bf16[513,20,16,64]{3,1,2,0:T(8,128)(2,1)} copy(%p.1)",
     [("copy", "513,20,16,64")]),
    ("  %transpose.1 = bf16[513,16,2560]{2,1,0} transpose(%p), "
     "dimensions={0,1,2}", [("transpose", "513,16,2560")]),
    # a relayout started asynchronously is a relayout all the same
    ("  %copy-start.1 = (bf16[513,20,16,64]{3,1,2,0:T(8,128)(2,1)}, "
     "bf16[513,20,16,64]{3,2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
     "copy-start(%p)", [("copy-start", "513,20,16,64")]),
    # fast memory and back, nothing else changed
    ("  %copy-start.3 = (bf16[8208,2560]{1,0:T(8,128)(2,1)}, "
     "bf16[8208,2560]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
     "copy-start(%fusion.2)", [("move", "8208,2560")]),
    # smaller than the pool: a weight, an activation
    ("  %copy.9 = f32[1280,1280]{0,1:T(8,128)} copy(%w)", []),
    ("  %fusion.2 = bf16[8208,2560]{1,0} fusion(%a, %b), kind=kCustom", []),
])
def test_pool_sized_reads_an_hlo_line(line, want):
    assert pool_sized(line, 513 * 20 * 16 * 64) == want
