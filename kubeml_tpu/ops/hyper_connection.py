"""Manifold-constrained hyper-connections (arXiv:2512.24880): a residual path
of ``n`` streams that every sub-layer reads through a pre-map, writes through
a post-map and mixes through a doubly-stochastic residual map, all three made
from the token's own streams.

For one token, ``X`` in R^{n x E} (held flat, ``[..., n * E]``: stream ``i`` is
columns ``i E .. (i + 1) E``), a sub-layer ``F`` with its own ``phi``
``[n E, 2 n + n n]``, ``alpha`` (three scalars) and ``bias`` ``[2 n + n n]``:

    xs          = vec(X) * rsqrt(mean(vec(X)^2) + norm_eps)       # no gain
    [p | q | r] = xs @ phi
    pre         = sigmoid(alpha[0] p + bias[:n])                  # (0, 1)
    post        = 2 sigmoid(alpha[1] q + bias[n:2n])              # (0, 2)
    Z           = clip(alpha[2] mat(r) + mat(bias[2n:]), -clamp, clamp)
    M           = exp(Z - max(Z));  sinkhorn_iters times:
                      M /= rowsum(M) + eps;  M /= colsum(M) + eps
    u           = sum_i pre[i] X[i]                               # hc_pre
    X'[i]       = sum_j M[i, j] X[j] + post[i] F(norm(u))         # hc_post

The maps are made in float32 whatever type the streams are held in.
:func:`hc_pre` and :func:`hc_post` are the two halves around ``F``; each is
one function for the whole-sequence forward, an admission and a decode step.
On a TPU, in a decode apply, they are Pallas kernels named ``hc_pre`` and
``hc_post`` (a trace shows ``%hc_pre.<n>``, ``%hc_post.<n>``): ``hc_pre``
reads a tile of positions' streams once for the sum of squares, the
projection and the weighted sum; ``hc_post`` reads the streams and the
branch's output once and writes the streams once. The Sinkhorn loop runs with
the positions on the lanes (``n n`` rows of ``[1, tile]``), so its 40
normalisations of a 4 x 4 matrix cost a few vector operations a tile, not a
padded register a token; the small transposes between that layout and the
token-major one are products with an identity on the MXU at precision
``highest`` (exact: every sum has one term). Elsewhere (the CPU, training,
a position count with no tile) the same equations run as ``jax.numpy``.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_PRE_TILE = 128    # positions a hc_pre program reads (3.7 MB of bfloat16 at
_POST_TILE = 64    # 4 x 3584); hc_post holds a tile in and a tile out
_VMEM = 64 * 2 ** 20


@dataclass(frozen=True)
class HCConfig:
    """The residual path's constants, under the names of the published
    ``config`` (``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_max`` = -``mhc_h_res_clamp_min``)."""

    mult: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: float = 30.0
    norm_eps: float = 1e-6

    @property
    def maps(self) -> int:
        """Columns of ``phi``: pre, post and the residual map."""
        return 2 * self.mult + self.mult * self.mult


def sinkhorn(m, iters: int, eps: float):
    """``m`` [..., n, n] positive -> ``iters`` times rows, then columns,
    divided by their sums + ``eps``."""
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def maps_of(proj, alpha, bias, cfg: HCConfig):
    """The normed streams' projection ``proj`` [..., 2n + n n] (float32) ->
    (pre [..., n], post [..., n], M [..., n, n])."""
    n = cfg.mult
    a, b = alpha.astype(jnp.float32), bias.astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    z = jnp.clip(a[2] * proj[..., 2 * n:] + b[2 * n:], -cfg.clamp, cfg.clamp)
    m = jnp.exp(z - z.max(axis=-1, keepdims=True))
    m = sinkhorn(m.reshape(m.shape[:-1] + (n, n)), cfg.sinkhorn_iters,
                 cfg.eps)
    return pre, post, m


def _tile(positions: int, cap: int) -> int:
    """The kernels' tile: the largest power of two under ``cap`` that
    divides ``positions``; under 8 there is none."""
    t = math.gcd(positions, cap)
    return t if t >= 8 else 0


def _use_kernel(kernel: Optional[bool], positions: int, width: int) -> bool:
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    return bool(kernel) and width % 128 == 0 and _tile(
        positions, _POST_TILE) > 0


def _transposed(a, eye):
    """``a`` [r, c] float32 -> [c, r] as ``eye @ a^T`` on the MXU (``eye``
    the c x c identity): exact at precision ``highest``."""
    return jax.lax.dot_general(eye, a, (((1,), (1,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            ).astype(jnp.float32)


def _pre_kernel(x_ref, wt_ref, ab_ref, u_ref, maps_ref, zt_ref, mt_ref, *,
                n: int, width: int, iters: int, eps: float, clamp: float,
                norm_eps: float):
    tile, c = x_ref.shape[0], maps_ref.shape[1]
    gc = wt_ref.shape[0]       # c, or 3 c: phi as three bfloat16 terms
    exact = wt_ref.dtype == jnp.bfloat16
    ss = jnp.zeros((tile, 1), jnp.float32)
    proj = jnp.zeros((tile, gc), jnp.float32)
    for i in range(n):
        xr = x_ref[:, i * width:(i + 1) * width]
        xi = xr.astype(jnp.float32)
        ss += (xi * xi).sum(axis=1, keepdims=True)
        proj += jax.lax.dot_general(
            xr if exact else xi, wt_ref[:, i * width:(i + 1) * width],
            (((1,), (1,)), ((), ())), precision=None if exact else _HI,
            preferred_element_type=jnp.float32)
    rs = jax.lax.rsqrt(ss / (n * width) + norm_eps)
    # alpha and the bias a column, then the positions onto the lanes
    zt_ref[...] = _transposed(proj * rs * ab_ref[0:1, :] + ab_ref[1:2, :],
                              _eye(gc))
    z = lambda k: functools.reduce(
        jnp.add, [zt_ref[g + k:g + k + 1, :] for g in range(0, gc, c)])
    for k in range(2 * n):
        mt_ref[k:k + 1, :] = (1.0 if k < n else 2.0) / (1.0 + jnp.exp(-z(k)))
    zs = [jnp.clip(z(k), -clamp, clamp) for k in range(2 * n, c)]
    top = functools.reduce(jnp.maximum, zs)
    m = [[jnp.exp(zs[i * n + j] - top) for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for i in range(n):
            inv = 1.0 / (functools.reduce(jnp.add, m[i]) + eps)
            m[i] = [v * inv for v in m[i]]
        for j in range(n):
            inv = 1.0 / (functools.reduce(
                jnp.add, [m[i][j] for i in range(n)]) + eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv
    for i in range(n):
        for j in range(n):
            k = 2 * n + i * n + j
            mt_ref[k:k + 1, :] = m[i][j]
    maps = _transposed(mt_ref[...], _eye(tile))
    maps_ref[...] = maps
    u = jnp.zeros((tile, width), jnp.float32)
    for i in range(n):
        u += maps[:, i:i + 1] * x_ref[:, i * width:(i + 1) * width].astype(
            jnp.float32)
    u_ref[...] = u.astype(u_ref.dtype)


def _post_kernel(x_ref, y_ref, co_ref, o_ref, *, n: int, width: int,
                 chunk: int):
    co = co_ref[...]             # [tile, n + n n]: post, then M by rows
    for c0 in range(0, width, chunk):
        xs = [x_ref[:, j * width + c0:j * width + c0 + chunk].astype(
            jnp.float32) for j in range(n)]
        y = y_ref[:, c0:c0 + chunk].astype(jnp.float32)
        for i in range(n):
            acc = co[:, i:i + 1] * y
            for j in range(n):
                k = n + i * n + j
                acc += co[:, k:k + 1] * xs[j]
            o_ref[:, i * width + c0:i * width + c0 + chunk] = acc.astype(
                o_ref.dtype)


def _params(interpret: bool):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM)}


def _three_terms(w):
    """float32 ``w`` [c, k] as three bfloat16 terms [3 c, k] that add up
    to it (8 + 8 + 8 bits): a bfloat16 stream times them, accumulated in
    float32, is the float32 product in one pass of the MXU."""
    hi = w.astype(jnp.bfloat16)
    r = w - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _pre_call(x2, params, cfg: HCConfig, interpret: bool):
    """x2 [T, n E] -> (u [T, E], maps [T, 2n + n n] float32)."""
    T, n, c = x2.shape[0], cfg.mult, cfg.maps
    width = x2.shape[1] // n
    tile = _tile(T, _PRE_TILE)
    wt = params["phi"].astype(jnp.float32).T                    # [c, n E]
    a = params["alpha"].astype(jnp.float32)
    a = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                         jnp.full((n * n,), a[2])])
    b = params["bias"].astype(jnp.float32)
    if x2.dtype == jnp.bfloat16:
        wt = _three_terms(wt)
        a, b = jnp.tile(a, 3), jnp.concatenate([b, jnp.zeros((2 * c,))])
    gc = wt.shape[0]
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, width=width,
                          iters=cfg.sinkhorn_iters, eps=cfg.eps,
                          clamp=cfg.clamp, norm_eps=cfg.norm_eps),
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((tile, n * width), lambda t: (t, 0)),
                  pl.BlockSpec((gc, n * width), lambda t: (0, 0)),
                  pl.BlockSpec((2, gc), lambda t: (0, 0))],
        out_specs=[pl.BlockSpec((tile, width), lambda t: (t, 0)),
                   pl.BlockSpec((tile, c), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, width), x2.dtype),
                   jax.ShapeDtypeStruct((T, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((gc, tile), jnp.float32),
                        pltpu.VMEM((c, tile), jnp.float32)],
        interpret=interpret, name="hc_pre", **_params(interpret),
    )(x2, wt, jnp.stack([a, b]))


def _post_call(x2, y2, coef, n: int, interpret: bool):
    """x2 [T, n E], y2 [T, E], coef [T, n + n n] -> the new streams."""
    T, width = y2.shape
    tile = _tile(T, _POST_TILE)
    chunk = next(c for c in (512, 256, 128) if width % c == 0)
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, width=width, chunk=chunk),
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((tile, n * width), lambda t: (t, 0)),
                  pl.BlockSpec((tile, width), lambda t: (t, 0)),
                  pl.BlockSpec((tile, n + n * n), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((tile, n * width), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret, name="hc_post", **_params(interpret),
    )(x2, y2, coef)


def hc_pre(x, params, cfg: HCConfig, *, kernel: Optional[bool] = None):
    """The streams ``x`` [..., n E] and a sub-layer's ``params`` (``phi``,
    ``alpha``, ``bias``) -> (the branch's input ``u`` [..., E] in ``x``'s
    type, ``post`` [..., n] and ``M`` [..., n, n] in float32).
    ``kernel``: None = the Pallas kernel on a TPU, the equations in
    ``jax.numpy`` elsewhere."""
    n = cfg.mult
    lead, width = x.shape[:-1], x.shape[-1] // n
    T = math.prod(lead)
    if _use_kernel(kernel, T, width):
        u, maps = _pre_call(x.reshape(T, n * width), params, cfg,
                            jax.default_backend() != "tpu")
        return (u.reshape(lead + (width,)),
                maps[:, n:2 * n].reshape(lead + (n,)),
                maps[:, 2 * n:].reshape(lead + (n, n)))
    xf = x.astype(jnp.float32)
    rs = jax.lax.rsqrt((xf * xf).mean(axis=-1, keepdims=True) + cfg.norm_eps)
    proj = jnp.dot(xf, params["phi"].astype(jnp.float32), precision=_HI) * rs
    pre, post, m = maps_of(proj, params["alpha"], params["bias"], cfg)
    u = jnp.einsum("...n,...ne->...e", pre, xf.reshape(lead + (n, width)))
    return u.astype(x.dtype), post, m


def hc_post(x, y, post, m, *, kernel: Optional[bool] = None):
    """The streams ``x`` [..., n E], the branch's output ``y`` [..., E] and
    the maps of :func:`hc_pre` -> the streams after the sub-layer."""
    n = post.shape[-1]
    lead, width = x.shape[:-1], y.shape[-1]
    T = math.prod(lead)
    if _use_kernel(kernel, T, width):
        coef = jnp.concatenate([post.reshape(T, n), m.reshape(T, n * n)],
                               axis=1)
        out = _post_call(x.reshape(T, n * width), y.reshape(T, width), coef,
                         n, jax.default_backend() != "tpu")
        return out.reshape(x.shape)
    xf = x.astype(jnp.float32).reshape(lead + (n, width))
    out = (jnp.einsum("...ij,...je->...ie", m, xf)
           + post[..., None] * y.astype(jnp.float32)[..., None, :])
    return out.reshape(x.shape).astype(x.dtype)
