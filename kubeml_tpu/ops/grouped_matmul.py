"""Grouped matrix products for routed experts: rows sorted by expert, one
weight matrix per expert, work in proportion to the rows.

    out[r] = lhs[r] @ rhs[g]      for the rows r of group g
    (with ``rhs_up``: out[r] = silu(lhs[r] @ rhs[g]) * (lhs[r] @ rhs_up[g]))

``lhs`` ``[m, k]`` holds the groups' rows one after another, ``group_sizes``
``[G]`` says how many each has (rows past their sum belong to nobody: their
output is unspecified), ``rhs`` is ``[G, k, n]``. Two implementations of the
one contract:

* :func:`grouped_matmul` with ``kernel=False`` is ``jax.lax.ragged_dot``:
  XLA's own, differentiable, the parity oracle and the path of the
  whole-sequence forward;
* with ``kernel=True`` the Pallas TPU kernel ``moe_experts`` (the name a
  device trace shows): the serving programs' path.

The kernel walks *work items*, not experts x tiles. A work item is one (group,
row tile) pair whose rows overlap; sorted rows make them at most ``tiles +
G - 1``, which is the static grid, and the few items past the live count
repeat the last block indices, so Pallas issues no copy for them and
``pl.when`` skips their product. Each item multiplies its whole ``[tm, k]``
row tile by the group's ``[k, tn]`` weight block and stores only the rows
that belong to the group; the output tile stays in VMEM while consecutive
items share it (the megablox scheme, jax.experimental.pallas.ops.tpu
.megablox, with a static grid and the gate's activation fused in). An empty
group is no item: its weights are never read. So a decode step reads each
*touched* expert's weights once, and a prefill's products grow with its
assignments, not with ``tokens x experts``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _round_up

# a weight block's budget in VMEM: Pallas double-buffers it, and the gated
# form holds two; 2 MiB keeps the kernel's blocks under the 16 MiB a v5e
# program gets by default
_BLOCK_BYTES = 2 << 20


def row_tile(m: int, itemsize: int = 2) -> int:
    """Rows a work item multiplies: the whole of a decode step's assignments
    in one tile, 256 of a prefill's (more rows a tile mean fewer re-reads of
    an expert's weights and more products of rows that are not the
    group's)."""
    return min(256, _round_up(m, 32 // itemsize))


def _work_items(group_sizes, tm: int, tiles_m: int):
    """(item_group, item_tile, starts, ends, live) for the kernel's scalar
    prefetch: item i multiplies row tile ``item_tile[i]`` by group
    ``item_group[i]``; ``live`` items are real."""
    G = group_sizes.shape[0]
    n_items = tiles_m + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(count)                       # items through group g
    live = upto[-1]
    i = jnp.minimum(jnp.arange(n_items, dtype=jnp.int32),
                    jnp.maximum(live - 1, 0))
    g = jnp.searchsorted(upto, i, side="right").astype(jnp.int32)
    g = jnp.minimum(g, G - 1)
    t = first[g] + i - (upto[g] - count[g])
    t = jnp.clip(t, 0, tiles_m - 1).astype(jnp.int32)
    return g, t, starts, ends, live[None].astype(jnp.int32)


def _gmm_kernel(ig_ref, it_ref, st_ref, en_ref, live_ref, lhs_ref, *rest,
                tm: int, gated: bool):
    if gated:
        rhs_ref, up_ref, out_ref = rest
    else:
        rhs_ref, out_ref = rest
    i = pl.program_id(1)

    @pl.when(i < live_ref[0])
    def _item():
        g = ig_ref[i]
        x = lhs_ref[...]
        acc = jnp.dot(x, rhs_ref[...], preferred_element_type=jnp.float32)
        if gated:
            acc = jax.nn.silu(acc) * jnp.dot(
                x, up_ref[...], preferred_element_type=jnp.float32)
        row = it_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= st_ref[g]) & (row < en_ref[g])
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


def _pallas_gmm(lhs, rhs, group_sizes, rhs_up, interpret: bool):
    m, k = lhs.shape
    G, _, n = rhs.shape
    tm = row_tile(m, lhs.dtype.itemsize)
    mp = _round_up(m, tm)
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    tiles_m = mp // tm
    # the widest 128-multiple of columns whose [k, tn] block fits the budget
    tn = n
    while tn % 256 == 0 and k * tn * rhs.dtype.itemsize > _BLOCK_BYTES:
        tn //= 2
    items = _work_items(group_sizes, tm, tiles_m)
    gated = rhs_up is not None

    def lhs_map(j, i, ig, it, st, en, live):
        return (it[i], 0)

    def rhs_map(j, i, ig, it, st, en, live):
        return (ig[i], 0, j)

    def out_map(j, i, ig, it, st, en, live):
        return (it[i], j)

    w_spec = pl.BlockSpec((None, k, tn), rhs_map)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, tiles_m + G - 1),
            in_specs=[pl.BlockSpec((tm, k), lhs_map), w_spec]
            + ([w_spec] if gated else []),
            out_specs=pl.BlockSpec((tm, tn), out_map)),
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_experts",
    )(*items, lhs, rhs, *((rhs_up,) if gated else ()))
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, rhs_up=None, *,
                   kernel: bool = False,
                   interpret: Optional[bool] = None):
    """``[m, n]`` in ``lhs``'s type: each group's rows times its matrix,
    float32 accumulation; with ``rhs_up`` the gated form ``silu(x W) * (x
    W_up)``, the activation in float32. See the module docstring."""
    if kernel:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _pallas_gmm(lhs, rhs, group_sizes, rhs_up, interpret)
    sizes = group_sizes.astype(jnp.int32)
    dot = lambda w: jax.lax.ragged_dot(
        lhs, w.astype(lhs.dtype), sizes,
        preferred_element_type=jnp.float32)
    out = dot(rhs)
    if rhs_up is not None:
        out = jax.nn.silu(out) * dot(rhs_up)
    return out.astype(lhs.dtype)
