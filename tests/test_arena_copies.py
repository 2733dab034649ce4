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

The same compiles hold the admits to a head of one row (PR 46): an admit
is compiled as the engine calls it, the sampled position handed to the
module, GLM's and Xing's at their published vocabularies, and an array of
``[2048, vocabulary]`` in the compiled program fails the admit's case
(with the argument left out both programs hold a float32 fusion of
``[1, 2048, vocabulary]``: the check was seen to find it).

And they hold the token lookup to the rows it reads (PR 50): GPT-2's
published table at 1,600 and at 1,280 wide, handed over as the server's hold
lays it out, in a step and in an admit; no copy of the table in either.
"""

import subprocess
import sys
from pathlib import Path

import pytest

from arena_copies_proc import CASES, bucket_by_vocab, pool_sized


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


_FUSED = ("%fused_computation.20 (param_0.7: bf16[2048,154880], "
          "param_1.7: bf16[2048]) -> bf16[154880] {\n"
          "  %param_0.7 = bf16[2048,154880]{1,0:T(8,128)(2,1)} parameter(0)\n"
          "  %broadcast.313 = bf16[2048,154880]{1,0} broadcast(%param_1.7), "
          "dimensions={0}\n"
          "  ROOT %reduce.1 = bf16[154880]{0} reduce(%multiply.2, %c), "
          "dimensions={0}\n}\n\n"
          "ENTRY %main.9 (p.1: bf16[2048,154880]) -> f32[1,1,154880] {\n"
          "  %p.1 = bf16[2048,154880]{1,0:T(8,128)(2,1)} parameter(0)\n")


@pytest.mark.parametrize("entry,want", [
    # the head over the whole bucket, and its float32 logits
    ("  %fusion.9 = f32[1,2048,154880]{2,1,0:T(8,128)} fusion(%x, %p.1), "
     "kind=kOutput, calls=%fused_computation.9\n"
     "  %convert.3 = f32[2048,154880]{1,0:T(8,128)} convert(%fusion.8)\n",
     [("fusion", "1,2048,154880"), ("convert", "2048,154880")]),
    # one row: the weight (GLM's is itself 2,048 wide) is a parameter, and
    # the row broadcast against it inside a fused computation is no array
    ("  %fusion.9 = bf16[154880]{0:T(1024)(128)(2,1)} fusion(%p.1, %row), "
     "kind=kInput, calls=%fused_computation.20\n"
     "  ROOT %convert.3 = f32[1,1,154880]{2,1,0:T(1,128)} convert(%b.8)\n",
     []),
    # a relayout of the head's weight is an array of that shape all the same
    ("  %copy.4 = bf16[2048,154880]{0,1:T(8,128)(2,1)} copy(%p.1)\n",
     [("copy", "2048,154880")]),
])
def test_bucket_by_vocab_reads_the_entry_computation(entry, want):
    assert bucket_by_vocab(_FUSED + entry + "}\n", 2048, 154880) == want
