"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464), three
ways that compute one function.

Per head (keys ``d_k`` wide, values ``d_v`` wide; ``q`` and ``k`` arrive
l2-normed, ``q`` scaled, ``g <= 0`` the log of the step's decay, ``beta`` in
``[0, 2]``), the state ``S`` ``[d_k, d_v]`` float32::

    S_t = Diag(exp(g_t)) S_{t-1}
    S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T        # the delta rule
    o_t = S_t^T q_t

**The gate has one of two shapes.** ``g`` ``[.., H]`` is one decay a head
(Gated DeltaNet: every row of ``S`` forgets alike); ``g`` ``[.., H, d_k]`` is
one a KEY CHANNEL (Kimi Delta Attention, arXiv:2510.26692: row ``i`` of ``S``
is scaled by its own ``exp(g_t[i])``). With a head's ``d_k`` decays equal
the second is the first. Every entry below takes either; inside, a head's
scalar is a gate one channel wide.

Not a diagonal decay alone (ops/ssm.py): each step takes back what the state
already answers for ``k_t`` before it writes ``v_t`` there, a rank-one
correction. The convolutions, the norms and the gate belong to the caller
(models/gated_deltanet.py). A position with ``g == 0`` and ``beta == 0``
leaves ``S`` exactly as it was, which is how callers mask padding and rows
that are not live.

* :func:`gdn_sequential` — the recurrence as written, a ``lax.scan`` over
  positions: the oracle of the tests.
* :func:`gdn_chunked` — chunks of ``chunk`` positions (Yang et al.,
  arXiv:2406.06484 section 3, with the decay of arXiv:2412.06464): inside a
  chunk the corrections of all positions are one unit-lower-triangular
  solve and everything else a masked matrix product; only the chunk-end
  states go through a scan. Plain ``jnp``; prefill and the non-decode forward
  use it, from zeros or from a carried state.
* :func:`gdn_update` — one position for every row of the engine's slab, as
  ONE Pallas kernel that reads and writes the rows' states in place
  (``input_output_aliases``): a decode step touches each state once each
  way. Mosaic names the custom call after the kernel, so a device trace
  shows ``gdn_update`` where the gate is a head's scalar and ``kda_update``
  where it is per key channel: two names of one entry, so that a trace says
  which rule a program ran.

A state is STORED ``[rows, H / p, d_k, p * d_v]`` (:func:`pack_state`):
keys on the sublanes, values on the lanes, ``p`` heads side by side where one
head's values are not whole 128-lane rows (the published 192: one and a half,
which alone would be stored in 256; two make 384, three whole rows).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST
_LANES = 128
# the unit-lower-triangular solve inverts diagonal blocks of this many
# positions by repeated squaring and substitutes block by block
_SOLVE_BLOCK = 16


def heads_packed(num_heads: int, value_dim: int) -> int:
    """Heads whose values share a stored row: 2 where one head's are not
    whole lane rows and the heads pair off, else 1."""
    return 2 if value_dim % _LANES and num_heads % 2 == 0 else 1


def pack_state(S):
    """``[..., H, d_k, d_v]`` -> the stored ``[..., H / p, d_k, p * d_v]``."""
    *lead, H, dk, dv = S.shape
    p = heads_packed(H, dv)
    S = S.reshape(*lead, H // p, p, dk, dv)
    return jnp.moveaxis(S, -3, -2).reshape(*lead, H // p, dk, p * dv)


def unpack_state(S, num_heads: int):
    """The inverse of :func:`pack_state`."""
    *lead, Hp, dk, W = S.shape
    p = num_heads // Hp
    S = S.reshape(*lead, Hp, dk, p, W // p)
    return jnp.moveaxis(S, -2, -3).reshape(*lead, num_heads, dk, W // p)


def _channels(g, q):
    """The gate as ``[..., H, 1 or d_k]``: a head's scalar is one channel
    wide."""
    return g[..., None] if g.ndim == q.ndim - 1 else g


def gdn_sequential(q, k, v, g, beta, init_state=None):
    """q, k [b, L, H, d_k], v [b, L, H, d_v], beta [b, L, H], g [b, L, H]
    or [b, L, H, d_k], all float32 -> (o [b, L, H, d_v], final state [b, H,
    d_k, d_v])."""
    b, L, H, dk = q.shape
    dv = v.shape[-1]
    g = _channels(g, q)
    S0 = (jnp.zeros((b, H, dk, dv), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[..., None]
        r = vt - jnp.einsum("bhkv,bhk->bhv", S, kt, precision=_HI)
        S = S + kt[..., :, None] * (bt[..., None] * r)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=_HI)

    seq = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
                for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, seq)
    return jnp.moveaxis(o, 0, 1), S


def _solve_unit_lower(M, rhs):
    """``X`` with ``(I + M) X = rhs`` for ``M`` ``[..., C, C]`` strictly
    lower triangular. A diagonal block ``I + N`` of :data:`_SOLVE_BLOCK`
    positions is inverted exactly by its finite Neumann series, ``(I - N)(I
    + N^2)(I + N^4)(I + N^8)`` (``N`` is nilpotent), and the blocks are
    substituted in order: products only, no loop over positions. One series
    over the whole chunk would pass through powers of ``M`` thousands of
    times larger than the inverse they sum to."""
    C = M.shape[-1]
    nb, blk = C // _SOLVE_BLOCK, _SOLVE_BLOCK
    mm = functools.partial(jnp.matmul, precision=_HI)
    eye = jnp.eye(blk, dtype=M.dtype)
    out = []
    for i in range(nb):
        rows = slice(i * blk, (i + 1) * blk)
        r = rhs[..., rows, :]
        for j, Xj in enumerate(out):
            r = r - mm(M[..., rows, j * blk:(j + 1) * blk], Xj)
        P = -M[..., rows, rows]
        inv = eye + P
        for _ in range(blk.bit_length() - 2):
            P = mm(P, P)
            inv = inv + mm(inv, P)
        out.append(mm(inv, r))
    return jnp.concatenate(out, axis=-2)


def _pair_terms(rows, k, gam):
    """The in-chunk pair terms under a gate PER KEY CHANNEL. For each ``x``
    of ``rows`` (the keys, the queries), ``P[.., t, s] = sum_d x[.., t, d]
    k[.., s, d] exp(gam[.., t, d] - gam[.., s, d])`` for ``t >= s`` and 0
    above the diagonal: what every pair of positions of one chunk owes the
    decays between them. ``x`` and ``k`` ``[.., C, d_k]``, ``gam`` ``[.., C,
    d_k]`` the running sum of the gate inside the chunk.

    A head's scalar gate factors out of the sum over ``d`` (one masked
    ``exp(gam_t - gam_s)`` times ``x k^T``: :func:`gdn_chunked` does that in
    place). A gate per key channel does not, and ``x * exp(gam)`` against
    ``k * exp(-gam)`` overflows float32
    where a channel forgets fast (``e^-1.4`` a position over 64). Secondary
    chunking (Yang et al., arXiv:2312.06635 section 4) in sub-blocks of
    :data:`_SOLVE_BLOCK` positions: a diagonal sub-block's pairs exactly,
    from the ``[16, 16, d_k]`` differences; sub-block ``i`` against the
    positions before it as ONE product of ``rows_t * exp(gam_t - Gamma_i)``
    and ``k_s * exp(Gamma_i - gam_s)``, ``Gamma_i`` the running sum at the
    sub-block's first position: both exponents are of sums of ``g`` over
    positions that follow one another, so nothing overflows."""
    C = k.shape[-2]
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    blk = _SOLVE_BLOCK
    nb = C // blk
    sub = lambda a: a.reshape(a.shape[:-2] + (nb, blk) + a.shape[-1:])
    rows = jnp.stack(rows, axis=-3)       # [.., n, C, d_k]: one exp for all
    rb, kb, gb = sub(rows), sub(k), sub(gam)       # [.., (n,) nb, blk, d_k]
    # inside a sub-block: exp of each pair's own difference, [blk, blk, d_k]
    # a sub-block, weighed and summed over the channels (no product form)
    own = jnp.exp(jnp.where(
        (t >= s)[:blk, :blk, None],
        gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    diag = (rb[..., :, None, :]
            * (kb[..., None, :, :] * own)[..., None, :, :, :, :]).sum(-1)
    # sub-block i against every position before its first, through Gamma_i
    first = gb[..., :1, :]                          # [.., nb, 1, d_k]
    before = (s < blk * jnp.arange(nb)[:, None])[..., None]    # [nb, C, 1]
    k_to = k[..., None, :, :] * jnp.exp(jnp.where(
        before, first - gam[..., None, :, :], -jnp.inf))   # [.., nb, C, d_k]
    off = jnp.einsum("...nitd,...isd->...nits",
                     rb * jnp.exp(gb - first)[..., None, :, :, :], k_to,
                     precision=_HI)                 # [.., n, nb, blk, C]
    eye = jnp.eye(nb, dtype=diag.dtype)[:, None, :, None]   # [nb, 1, nb, 1]
    placed = (diag[..., :, :, None, :] * eye).reshape(
        diag.shape[:-3] + (nb, blk, C))
    pairs = (off + placed).reshape(rows.shape[:-1] + (C,))
    return tuple(pairs[..., n, :, :] for n in range(rows.shape[-3]))


def gdn_chunked(q, k, v, g, beta, *, chunk: int = 64, init_state=None):
    """The same function as :func:`gdn_sequential`, in chunks of ``chunk``
    positions (the last chunk is padded with ``g = beta = 0``, which moves
    nothing). With ``gamma`` the running sum of ``g`` inside a chunk (a
    scalar or ``d_k`` values a position), ``S`` the state entering it and
    ``(X . Y)[t, s] = sum_d X[t, d] Y[s, d] exp(gamma_t[d] - gamma_s[d])``
    (:func:`_pair_terms`)::

        A  = (I + strict_lower(diag(beta) (K . K)))^-1 diag(beta)
        W  = A (K * exp(gamma));   U = A V;   V' = U - W S
        O  = (Q * exp(gamma)) S + tril(Q . K) V'
        S <- Diag(exp(gamma_end)) S + (K * exp(gamma_end - gamma))^T V'

    Every exponent is of a sum of ``g`` over positions that follow one
    another, so nothing overflows. Products run at precision ``highest``:
    they are a percent of a layer's operations and the state is summed over
    hundreds of positions."""
    b, L, H, dk = q.shape
    dv = v.shape[-1]
    blk = _SOLVE_BLOCK
    if chunk % blk:
        raise ValueError(f"chunk {chunk} is not a multiple of {blk}")
    C = min(int(chunk), -(-L // blk) * blk)
    nc = -(-L // C)
    pad = nc * C - L

    def chunks(a):   # [b, L, H, ...] -> [nc, b, H, C, ...]
        a = jnp.pad(a.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((b, nc, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    mm = functools.partial(jnp.matmul, precision=_HI)
    if g.ndim == beta.ndim:
        # one decay a head: it factors out of the sums over the channels
        gam = jnp.cumsum(g, axis=-1)                       # [nc, b, H, C]
        decay = jnp.exp(jnp.where(t >= s, gam[..., :, None]
                                  - gam[..., None, :], -jnp.inf))  # [.., t, s]
        kT = jnp.swapaxes(k, -1, -2)
        M = jnp.where(t > s, beta[..., :, None] * mm(k, kT) * decay, 0.0)
        qk = mm(q, kT) * decay
        eg = jnp.exp(gam)[..., None]
        to_end = jnp.exp(gam[..., -1:] - gam)[..., None]
        keep = jnp.exp(gam[..., -1])[..., None, None]      # [nc, b, H, 1, 1]
    else:
        gam = jnp.cumsum(g, axis=-2)                       # [nc, b, H, C, dk]
        kk, qk = _pair_terms((k, q), k, gam)
        M = jnp.where(t > s, beta[..., :, None] * kk, 0.0)
        eg = jnp.exp(gam)
        to_end = jnp.exp(gam[..., -1:, :] - gam)
        keep = jnp.exp(gam[..., -1, :])[..., None]         # [nc, b, H, dk, 1]
    WU = _solve_unit_lower(M, beta[..., None] * jnp.concatenate(
        [k * eg, v], axis=-1))
    W, U = WU[..., :dk], WU[..., dk:]
    qe = q * eg
    k_end = jnp.swapaxes(k * to_end, -1, -2)
    S0 = (jnp.zeros((b, H, dk, dv), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(S, c):
        W_c, U_c, qe_c, qk_c, k_end_c, keep_c = c
        Vp = U_c - mm(W_c, S)
        return keep_c * S + mm(k_end_c, Vp), mm(qe_c, S) + mm(qk_c, Vp)

    S, o = jax.lax.scan(step, S0, (W, U, qe, qk, k_end, keep))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)          # [b, nc, C, H, dv]
    return o.reshape(b, nc * C, H, dv)[:, :L], S


def gdn_update_reference(state, q, k, v, g, beta):
    """One position in plain ``jnp``: state [R, H, d_k, d_v] (not packed),
    q and k [R, H, d_k], v [R, H, d_v], beta [R, H], g [R, H] or [R, H,
    d_k] -> (o [R, H, d_v], new state)."""
    o, S = gdn_sequential(q[:, None], k[:, None], v[:, None], g[:, None],
                          beta[:, None], init_state=state)
    return o[:, 0], S


def _update_kernel(vec_ref, s_ref, o_ref, out_ref, *, blocks, packed,
                   key_dim, value_dim, channels):
    """One (row, block of stored heads) program. A stored head is ``packed``
    heads side by side on the lanes; ``vec`` holds, as rows along the lanes,
    everything a state meets, in one lane-dense block (as columns ``[d_k, 2
    p]`` the same numbers took 32 times their bytes in HBM, four lanes of
    128 to a row): first the rows that lie along a head's VALUES (a head's
    scalars repeated over its lanes), then each head's key-wide rows in a
    row's first ``key_dim`` lanes, which are turned onto the sublanes here,
    one transpose of the tile. With ``u = S^T k`` and ``w = S^T q`` taken
    in the one pass over ``S``, a head's scalar gate (rows ``v``, ``e^g``,
    ``beta``, ``k . q``, then ``k`` and ``q``)::

        r = v - e^g u;   S' = e^g S + k (beta r)^T;   o = e^g w + (k.q) beta r

    and a gate per key channel (``channels``; rows ``v``, ``beta``, ``k .
    q``, then ``k``, ``q``, ``e^g k``, ``e^g q`` and ``e^g``), where the
    decay scales the state's ROWS and so rides the sublanes, with ``u = S^T
    (e^g k)`` and ``w = S^T (e^g q)``::

        r = v - u;   S' = Diag(e^g) S + k (beta r)^T;   o = w + (k.q) beta r
    """
    W = s_ref.shape[-1]
    head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // value_dim
    wide = min(W, -(-key_dim // _LANES) * _LANES)
    along = 3 if channels else 4        # rows that lie along the values
    for j in range(blocks):
        S = s_ref[0, j]                                   # [d_k, W]
        cols = vec_ref[0, j, :, 0:wide].T[0:key_dim]      # [d_k, rows]

        def across(n):
            # the n-th key-wide column of each lane's own head, [d_k, W]
            first = along + n * packed
            m = jnp.broadcast_to(cols[:, first:first + 1], S.shape)
            for i in range(1, packed):
                m = jnp.where(head == i, cols[:, first + i:first + i + 1], m)
            return m

        K = across(0)
        if channels:
            v, beta, kq = (vec_ref[0, j, i:i + 1, :] for i in range(3))
            u = jnp.sum(S * across(2), axis=0, keepdims=True)  # [1, W]
            w = jnp.sum(S * across(3), axis=0, keepdims=True)
            br = beta * (v - u)
            out_ref[0, j] = across(4) * S + K * br
            o_ref[0, j] = w + kq * br
        else:
            v, eg, beta, kq = (vec_ref[0, j, i:i + 1, :] for i in range(4))
            u = jnp.sum(S * K, axis=0, keepdims=True)
            w = jnp.sum(S * across(1), axis=0, keepdims=True)
            br = beta * (v - eg * u)
            out_ref[0, j] = eg * S + K * br
            o_ref[0, j] = eg * w + kq * br


# bytes of state one program holds each way (it is double-buffered in and
# out): 5 stored heads of [96, 384] float32 at Olmo-Hybrid's sizes, 16 of
# [128, 128] at Kimi-Linear's
_BLOCK_BYTES = 1 << 20


def gdn_update(state, q, k, v, g, beta, *, interpret: Optional[bool] = None):
    """The decode step of every row, in place: ``state`` ``[R, H / p, d_k,
    p * d_v]`` float32 (:func:`pack_state`) is aliased to the new state.
    q and k [R, H, d_k], v [R, H, d_v], beta [R, H], g [R, H] (the call is
    named ``gdn_update``) or [R, H, d_k] (``kda_update``). Returns ``(o [R,
    H, d_v], state)``. Every row is read and written; a row that is not live
    passes ``g = 0`` and ``beta = 0`` and gets its state back unchanged."""
    R, Hp, dk, W = state.shape
    H, dv = v.shape[1:]
    p = H // Hp
    channels = g.ndim == 3
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb = max(d for d in range(1, Hp + 1)
             if Hp % d == 0 and (d == 1 or d * dk * W * 4 <= _BLOCK_BYTES))
    if dk > W:
        raise ValueError(f"keys of {dk} do not fit a stored row of {W} "
                         f"values")
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = (f32(a) for a in (q, k, v, g, beta))
    eg = jnp.exp(g)
    # [R, Hp, rows, W]: the rows along the values (a head's scalar over its
    # d_v lanes), then the packed heads' key-wide rows in a row's first lanes
    over = lambda a: jnp.broadcast_to(a[..., None], (R, H, dv))
    lanes = lambda a: jnp.swapaxes(a.reshape(R, Hp, p, -1, dv), 2, 3).reshape(
        R, Hp, -1, W)
    kq = over(jnp.sum(k * q, axis=-1))
    if channels:
        along, keyed = [v, over(beta), kq], [k, q, eg * k, eg * q, eg]
    else:
        along, keyed = [v, over(eg), over(beta), kq], [k, q]
    keys = jnp.concatenate([a.reshape(R, Hp, p, dk) for a in keyed], axis=2)
    rows = -(-(len(along) + len(keyed) * p) // 8) * 8
    vec = jnp.concatenate([
        lanes(jnp.stack(along, axis=2)),
        jnp.pad(keys, ((0, 0), (0, 0),
                       (0, rows - len(along) - len(keyed) * p),
                       (0, W - dk)))], axis=2)
    at = lambda r, j: (r, j, 0, 0)
    o, new = pl.pallas_call(
        functools.partial(_update_kernel, blocks=hb, packed=p, key_dim=dk,
                          value_dim=dv, channels=channels),
        grid=(R, Hp // hb),
        in_specs=[pl.BlockSpec((1, hb, rows, W), at),
                  pl.BlockSpec((1, hb, dk, W), at)],
        out_specs=[pl.BlockSpec((1, hb, 1, W), at),
                   pl.BlockSpec((1, hb, dk, W), at)],
        out_shape=[jax.ShapeDtypeStruct((R, Hp, 1, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={1: 1},
        interpret=interpret,
        name="kda_update" if channels else "gdn_update",
    )(vec, state)
    return o.reshape(R, Hp, p, dv).reshape(R, H, dv), new
