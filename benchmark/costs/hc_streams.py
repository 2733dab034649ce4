"""Operations and bytes a residual path of ``n`` streams needs, from shapes
(hyper-connections; ``kubeml_tpu/ops/hyper_connection.py``).

One sub-layer at one position: the ``n`` streams are read once (the norm,
the maps' projection, the branch's input and the mixing all want them) and
written once, the branch's input ``u`` is written and its output ``y`` read:
``(2 n + 2) x hidden`` values at the compute type's bytes, 71,680 B at four
streams of 3584 in bfloat16. The operations are the projection onto ``2 n +
n n`` columns, the branch's weighted input, the mixing and the branch's
output written into the streams: 12 a byte at four streams, far under the
chip's 240, so the bytes over the HBM peak bound it; the 40 normalisations
of an ``n x n`` matrix are left out. It counts the work,
not the implementation: kernels that read the streams twice a sub-layer
(once to make the maps, once to mix) read a lower share, and a share over
100% is a fault in the count or in the time."""

from .paged_attention import min_seconds  # noqa: F401  (one roofline rule)


def mixed(positions: float, *, streams: int, hidden: int,
          act_bytes: int = 2) -> tuple:
    """(flops, bytes) for ``positions`` (position, sub-layer) pairs."""
    n = streams
    maps = 2 * n + n * n
    flops = (2.0 * n * hidden * maps        # the projection
             + 2.0 * n * hidden             # the branch's input
             + 2.0 * n * n * hidden         # the mixing
             + 2.0 * n * hidden) * positions   # the branch's output, written
    nbytes = (2.0 * n + 2.0) * hidden * act_bytes * positions
    return flops, nbytes
