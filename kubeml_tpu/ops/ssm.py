"""The Mamba-2 state-space recurrence (Dao & Gu, 2024), three ways that
compute one function.

Per head ``h`` (``P`` channels, state width ``N``; head ``h`` reads the ``B``
and ``C`` of group ``h // (H / G)``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t      # float32
    y_t = S_t C_t

A state is held ``[..., H, N, P]``: the state's width on the sublanes, a
head's channels (128 at the published sizes) on the lanes, so that ``x`` and
``y`` are lane-dense rows.

``dt`` is past its softplus and ``A`` is negative; the ``D x`` skip, the gate
and the norm belong to the caller (models/mamba2.py). A position with
``dt == 0`` and ``x == 0`` leaves ``S`` exactly as it was (the decay is
``exp(0) == 1``, the update 0), which is how callers mask padding and rows
that are not live.

* :func:`ssd_sequential` — the recurrence as written, a ``lax.scan`` over
  positions: the oracle of the tests.
* :func:`ssd_scan` — the chunked form (state-space duality): inside a chunk
  of ``chunk`` positions everything is a masked matrix product, and only the
  chunk-end states go through a scan. Plain ``jnp``; prefill and the
  non-decode forward use it, from zeros or from a carried state.
* :func:`ssm_update` — one position for every row of the engine's slab, as
  ONE Pallas kernel that reads and writes the rows' states in place
  (``input_output_aliases``): a decode step touches each state once each
  way, and no second copy of the slab's states ever exists. Mosaic names the
  custom call after the kernel, so a device trace shows ``ssm_update``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST


def ssd_sequential(x, dt, A, B, C, init_state=None):
    """x [b, L, H, P], dt [b, L, H], A [H], B and C [b, L, G, N], all
    float32 -> (y [b, L, H, P], final state [b, H, N, P])."""
    b, L, H, P = x.shape
    G, N = B.shape[2:]
    rep = H // G
    S0 = (jnp.zeros((b, H, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(S, t):
        xt, dtt, Bt, Ct = t
        Bh = jnp.repeat(Bt, rep, axis=1)   # [b, H, N]
        Ch = jnp.repeat(Ct, rep, axis=1)
        S = (S * jnp.exp(dtt * A)[..., None, None]
             + Bh[..., None] * (dtt[..., None] * xt)[:, :, None, :])
        return S, jnp.einsum("bhnp,bhn->bhp", S, Ch, precision=_HI)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    S, y = jax.lax.scan(step, S0, seq)
    return jnp.moveaxis(y, 0, 1), S


def ssd_scan(x, dt, A, B, C, *, chunk: int, init_state=None):
    """The same function as :func:`ssd_sequential`, in chunks of ``chunk``
    positions (the last chunk is padded with ``dt = x = 0``, which moves
    nothing). Products run at precision ``highest``: they are a few percent
    of a layer's operations and the state is summed over hundreds of
    positions."""
    b, L, H, P = x.shape
    G, N = B.shape[2:]
    J = H // G
    Q = min(int(chunk), L)
    nc = -(-L // Q)
    pad = nc * Q - L

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((b, nc, Q) + a.shape[2:])

    x, dt, B, C = (chunks(a.astype(jnp.float32)) for a in (x, dt, B, C))
    a = dt * A                                     # [b, nc, Q, H], <= 0
    cum = jnp.cumsum(a, axis=2)                    # inclusive
    # inside a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # [b,nc,t,s,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", C, B, precision=_HI)
    w = (decay * dt[:, :, None, :, :]).reshape(b, nc, Q, Q, G, J) \
        * cb[..., None]
    xg = x.reshape(b, nc, Q, G, J, P)
    y = jnp.einsum("bctsgj,bcsgjp->bctgjp", w, xg, precision=_HI)
    # what a chunk adds to the state at its own end, and how far it decays
    # what was there before
    to_end = jnp.exp(cum[:, :, -1:, :] - cum) * dt           # [b,nc,Q,H]
    adds = jnp.einsum("bcsgjp,bcsgn->bcgjnp",
                      xg * to_end.reshape(b, nc, Q, G, J)[..., None], B,
                      precision=_HI)                          # [b,nc,G,J,N,P]
    keeps = jnp.exp(cum[:, :, -1, :]).reshape(b, nc, G, J)
    S0 = (jnp.zeros((b, G, J, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32).reshape(b, G, J, N, P))

    def step(S, c):
        add, keep = c
        return S * keep[..., None, None] + add, S

    S, before = jax.lax.scan(
        step, S0, (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(keeps, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)            # state entering chunk c
    y = y + jnp.einsum("bcgjnp,bctgn->bctgjp", before, C, precision=_HI) \
        * jnp.exp(cum).reshape(b, nc, Q, G, J)[..., None]
    y = y.reshape(b, nc * Q, H, P)[:, :L]
    return y, S.reshape(b, H, N, P)


def ssm_update_reference(state, x, dt, A, B, C):
    """One position in plain ``jnp``: state [S, H, N, P], x [S, H, P],
    dt [S, H], A [H], B and C [S, G, N] -> (y [S, H, P], new state)."""
    rep = state.shape[1] // B.shape[1]
    Bh = jnp.repeat(B, rep, axis=1)
    Ch = jnp.repeat(C, rep, axis=1)
    new = (state * jnp.exp(dt * A)[..., None, None]
           + Bh[..., None] * (dt[..., None] * x)[:, :, None, :])
    return jnp.einsum("bhnp,bhn->bhp", new, Ch, precision=_HI), new


def _update_kernel(x_ref, keep_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *,
                   heads):
    """One (row, block of heads of one group) program. ``x`` (already times
    ``dt``) and the head's decay ``exp(dt A)`` are rows along the lanes,
    ``B`` a column along the sublanes, so the update is two broadcasts and a
    multiply-add per element; ``y`` is ``C`` (a row, repeated to a sublane
    tile) times the new state on the MXU."""
    Bcol = b_ref[0, 0]                                    # [N, 1]
    C8 = jnp.broadcast_to(c_ref[0, 0], (8, Bcol.shape[0]))
    for h in range(heads):
        new = (s_ref[0, h] * keep_ref[0, 0, h:h + 1, :]
               + Bcol * x_ref[0, 0, h:h + 1, :])          # [N, P]
        o_ref[0, h] = new
        y_ref[0, 0, h:h + 1, :] = jax.lax.dot_general(
            C8, new, (((1,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)[0:1]


# bytes of state one program holds each way (it is double-buffered in and
# out): 8 heads of [256, 128] float32 at the published sizes
_BLOCK_BYTES = 1 << 20


def ssm_update(state, x, dt, A, B, C, *, interpret: Optional[bool] = None):
    """The decode step of every row, in place: ``state`` [S, H, N, P]
    float32 is aliased to the new state. Returns ``(y [S, H, P], state)``.
    Every row is read and written; a row that is not live passes ``dt = 0``
    and ``x = 0`` and gets its state back unchanged."""
    S, H, N, P = state.shape
    G = B.shape[1]
    J = H // G
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb = 1
    while hb * 2 <= J and J % (hb * 2) == 0 \
            and hb * 2 * P * N * 4 <= _BLOCK_BYTES:
        hb *= 2
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    # per block of heads, so that a block's trailing dims are the array's
    blocks = lambda a: a.reshape(S, H // hb, hb, P)
    keep = jnp.broadcast_to(jnp.exp(dt * A)[..., None], x.shape)
    per_group = J // hb
    heads = lambda s, j: (s, j, 0, 0)
    group = lambda s, j: (s, j // per_group, 0, 0)
    y, new = pl.pallas_call(
        functools.partial(_update_kernel, heads=hb),
        grid=(S, H // hb),
        in_specs=[pl.BlockSpec((1, 1, hb, P), heads),
                  pl.BlockSpec((1, 1, hb, P), heads),
                  pl.BlockSpec((1, 1, N, 1), group),
                  pl.BlockSpec((1, 1, 1, N), group),
                  pl.BlockSpec((1, hb, N, P), heads)],
        out_specs=[pl.BlockSpec((1, 1, hb, P), heads),
                   pl.BlockSpec((1, hb, N, P), heads)],
        out_shape=[jax.ShapeDtypeStruct((S, H // hb, hb, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={4: 1},
        interpret=interpret,
        name="ssm_update",
    )(blocks(dt[..., None] * x), blocks(keep),
      B.astype(jnp.float32)[..., None], C.astype(jnp.float32)[:, :, None, :],
      state)
    return y.reshape(S, H, P), new
