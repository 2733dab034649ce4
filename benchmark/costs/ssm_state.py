"""Operations and bytes the ``ssm_update`` kernel needs, from shapes.

One decode step of one row, per layer: the row's state (``heads`` matrices
of ``state x head_dim`` float32) is read once and written once; ``x`` (times
``dt``) and the decay come in and ``y`` goes out as ``heads x head_dim``
float32 each; ``B`` and ``C`` are ``groups x state`` float32 each. Per state
element the update is a multiply by the decay, a multiply of ``B`` by ``x``
and an add, and ``y`` a multiply-add against ``C``: 5 operations on 8 bytes
moved, so the bytes over the HBM peak bound it.

The kernel (``kubeml_tpu/ops/ssm.py``) does not skip a row that is not
live: it reads and writes every row of the slab, a dead one with ``dt = 0``.
So ``rows`` here is the slab's rows (the deployment's ``serving_slots``),
not the live ones: the bytes counted are the bytes the kernel's contract
makes it move, and a share over 100% is a fault in the count or the time."""


def decode_step(rows: float, *, layers: int, heads: int, head_dim: int,
                state: int, groups: int) -> tuple:
    """(flops, bytes) of the kernel for ``rows`` rows of one step (or the
    rows of several steps added up), all layers."""
    per_row = heads * head_dim * state
    flops = 5.0 * per_row * rows * layers
    nbytes = 4.0 * (2 * per_row              # the state, in and out
                    + 3 * heads * head_dim   # dt * x, the decay, y
                    + 2 * groups * state     # B, C
                    ) * rows * layers
    return flops, nbytes
