"""Operations and bytes the ``kda_update`` kernel needs, from shapes.

``gdn_state.py`` for a delta rule gated per key channel (Kimi Delta
Attention). One decode step of one row, per KDA layer: the row's state
(``heads`` matrices of ``key_dim x value_dim`` float32) is read once and
written once; ``q`` and ``k`` come in as ``heads x key_dim`` float32 each,
``v`` as ``heads x value_dim``, **the gate as ``heads x key_dim``** (one decay
a key channel, where ``gdn_state.py`` counts one a head), ``beta`` as
``heads``, and ``o`` goes out as ``heads x value_dim``. Per state element the
kernel takes ``u = S^T (e^g k)`` and ``w = S^T (e^g q)`` (a multiply and an
add each), decays the state's row and adds the rank-one correction (two
multiplies and an add): 7 operations on 8 bytes moved, so the bytes over the
HBM peak bound it.

The bytes are the LIVE values, not what the kernel's input block holds (the
program hands it ``e^g k``, ``e^g q`` and the scalars spread over lanes: a
layout that moves more reads as a lower share, not as a larger count). The
kernel (``kubeml_tpu/ops/gated_delta.py``) does not skip a row that is not
live: it reads and writes every row of the slab, a dead one with ``g = beta =
0``. So ``rows`` for the kernel's roofline is the slab's rows (the
deployment's ``serving_slots``), and a share over 100% is a fault in the
count or the time. A whole step's least time
(``decode_step_kimi_linear.py``) counts the live rows alone."""


def decode_step(rows: float, *, layers: int, heads: int, key_dim: int,
                value_dim: int) -> tuple:
    """(flops, bytes) of the kernel for ``rows`` rows of one step (or the
    rows of several steps added up), all ``layers`` KDA layers."""
    per_row = heads * key_dim * value_dim
    flops = 7.0 * per_row * rows * layers
    nbytes = 4.0 * (2 * per_row                  # the state, in and out
                    + 3 * heads * key_dim        # q, k and the gate
                    + 2 * heads * value_dim      # v, o
                    + heads                      # beta
                    ) * rows * layers
    return flops, nbytes
