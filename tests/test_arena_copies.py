"""Does a paged program copy the KV pool, or the latent one? (ROADMAP S1)

The arena's layout is a contract between its write (an XLA scatter) and its
read (a Mosaic custom call), and XLA answers a layout neither likes with
copies of the whole pool, every layer, every step: the head-major arenas
cost six a layer, 26-31 ms of a 48-62 ms GPT-2 decode step on the chip, and
an unrelated edit once doubled their count (PR 29). Interpret mode cannot
see any of it. The latent arena had the same disease at 576 lanes a token,
4.5 lane rows: two copies of the pool a layer in every program, a third of a
decode step, until its rows were whole ones too (PR 43). This compiles the
paged forward pass for a described v5e, in a subprocess (it loads the TPU
plugin, which the test session must not), once for all cases, at the six
configurations' attention shapes, and holds the count of pool-sized copies
at zero in each (tests/arena_copies_proc.py says what it counts).
"""

import subprocess
import sys
from pathlib import Path

import pytest

from arena_copies_proc import CASES, pool_sized


@pytest.fixture(scope="module")
def compiled():
    """The subprocess's report: case -> its line."""
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "arena_copies_proc.py")],
        capture_output=True, text=True, timeout=1200)
    if proc.returncode == 77:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    lines = [l for l in proc.stdout.splitlines()
             if l.startswith(("OK ", "COPIES "))]
    assert proc.returncode in (0, 1) and lines, proc.stderr[-2000:]
    return {l.split()[1].rstrip(":"): l for l in lines}


@pytest.mark.kernel
@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_programs_hold_no_pool_sized_copy(compiled, case):
    assert compiled.get(case) == f"OK {case}", compiled


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
