"""Do the Pallas kernels reach the TPU compiler at the shapes the chip runs?

Interpret mode (every other kernel test) checks numerics and says nothing
about whether the TPU toolchain accepts the program: the paged-attention
kernel passed its whole parity suite while its K/V blocks were a shape the
Pallas TPU lowering refuses outright. Two checks, neither needs a chip:

* cross-lowering — trace with ``interpret=False`` and lower for the TPU
  platform from this CPU session: catches block-shape refusals in under a
  second per kernel;
* Mosaic — compile the same programs ahead of time with the real TPU
  compiler against a chipless topology description (a subprocess: it loads
  the TPU plugin). Catches what only Mosaic knows (tiling, layout, VMEM).
"""

import subprocess
import sys
from pathlib import Path

import jax
import pytest

from kernel_shapes import kernel_cases

CASES = kernel_cases()


@pytest.mark.kernel
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu(name):
    fn, args = CASES[name]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.kernel
def test_kernels_compile_with_mosaic():
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "mosaic_compile_proc.py")],
        capture_output=True, text=True, timeout=600)
    if proc.returncode == 77:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    lines = proc.stdout.splitlines()
    failed = [l for l in lines if l.startswith("FAIL ")]
    assert proc.returncode == 0 and not failed, (
        "\n".join(failed) or proc.stderr[-2000:])
    assert {l[3:] for l in lines if l.startswith("OK ")} == set(CASES)
