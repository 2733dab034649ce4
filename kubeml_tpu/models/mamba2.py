"""The Mamba-2 mixer as a block's second branch (Falcon-H1: mixer and
attention run in parallel on the same normed input).

    z, xBC, dt = split((u W_in) * mup)            # d_ssm | d_ssm + 2 G N | H
    xBC   = silu(causal_depthwise_conv1d(xBC, width d_conv) + conv_bias)
    x,B,C = split(xBC)                            # x: H x P;  B, C: G x N
    dt    = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t   = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;   y_t = S_t C_t + D x_t
    out   = RMSNorm_grouped(y * silu(z)) W_out

Three entries compute that one function (ops/ssm.py has the recurrence):

* the whole sequence from zeros, in chunks of ``chunk_size`` (the non-decode
  forward);
* ``decode=True`` over ``L`` positions, from zeros where a row's
  ``positions`` is 0 and from the row's carried state otherwise (prefill,
  and the later chunks of a chunked prefill). Positions at or past a row's
  ``seq_lens`` leave the state untouched (``dt`` and ``x`` are zeroed there)
  and the convolution's tail is gathered at the row's true length, so the
  bucket a prompt is padded to cannot be seen in its state;
* ``decode=True``, one position, no ``rows``: the engine's decode step, the
  state advanced in place by the ``ssm_update`` kernel. A row whose
  ``seq_lens`` is 0 (not live) keeps its state and its tail.

The recurrent state lives in the ``cache`` collection beside the attention's
pages: ``ssm_state`` ``[rows, H, N, P]`` float32 (it is multiplied by a decay
near 1 for hundreds of steps) and ``conv_tail`` ``[rows, d_conv - 1, d_ssm +
2 G N]``, float32 as the convolution's input is (it is 1% of the state). ``rows`` is the engine's slab (``state_rows``,
cloned in like ``kv_pages``) or, without it, the batch; the ``rows``
argument names each batch row's place in it (an admit program's rows are
not the slab's).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssm import ssd_scan, ssm_update
from .layers import QuantizableDense


@dataclass(frozen=True)
class SSMConfig:
    """The mixer's sizes, under the names of the published ``config``."""

    d_ssm: int              # mamba_d_ssm = heads * head_dim
    num_heads: int          # mamba_n_heads
    head_dim: int           # mamba_d_head
    n_groups: int           # mamba_n_groups
    d_state: int            # mamba_d_state
    d_conv: int = 4         # mamba_d_conv
    chunk_size: int = 128   # mamba_chunk_size
    conv_bias: bool = True  # mamba_conv_bias
    proj_bias: bool = False  # mamba_proj_bias
    norm_eps: float = 1e-5  # rms_norm_eps
    # ssm_multipliers over the z, x, B, C and dt columns of in_proj's output
    mup: Tuple[float, float, float, float, float] = (1.0,) * 5

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.num_heads

    @property
    def state_row_bytes(self) -> int:
        """Bytes one program row's state takes in one layer: the float32
        state and the convolution's tail."""
        return 4 * (self.num_heads * self.d_state * self.head_dim
                    + (self.d_conv - 1) * self.conv_dim)


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


class Mamba2Mixer(nn.Module):
    cfg: SSMConfig
    dtype: Any = jnp.float32
    state_rows: int = 0

    @nn.compact
    def __call__(self, u, decode: bool = False, positions=None,
                 seq_lens=None, rows=None):
        c = self.cfg
        B_, L, _ = u.shape
        H, P, G, N, K = (c.num_heads, c.head_dim, c.n_groups, c.d_state,
                         c.d_conv)
        proj = QuantizableDense(
            c.in_dim, name="in_proj", use_bias=c.proj_bias, dtype=self.dtype,
            kernel_init=_part((None, "tp"))(nn.initializers.lecun_normal()))(u)
        # everything after the projection is float32: the muP vector (a
        # constant rounded to bfloat16 would be off the same way in every
        # element), the convolution, the recurrence, the gate and the norm
        proj = proj.astype(jnp.float32)
        if any(m != 1.0 for m in c.mup):
            widths = (c.d_ssm, c.d_ssm, G * N, G * N, H)
            proj = proj * jnp.concatenate(
                [jnp.full((w,), m, jnp.float32)
                 for w, m in zip(widths, c.mup)])
        z, xBC, dt = jnp.split(proj, [c.d_ssm, c.d_ssm + c.conv_dim], axis=-1)
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, c.conv_dim))
        conv_b = (self.param("conv_bias", nn.initializers.zeros,
                             (c.conv_dim,)) if c.conv_bias else None)
        A = -jnp.exp(self.param(
            "A_log", lambda k, s: jnp.log(jnp.arange(1, s[0] + 1,
                                                     dtype=jnp.float32)),
            (H,)).astype(jnp.float32))
        D = self.param("D", nn.initializers.ones, (H,)).astype(jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros,
                             (H,)).astype(jnp.float32)

        def conv(window):
            """window [B, K - 1 + n, C] -> silu(conv + bias) [B, n, C]:
            tap j weighs the input K - 1 - j positions back."""
            n = window.shape[1] - (K - 1)
            out = sum(window[:, j:j + n].astype(jnp.float32)
                      * conv_w[j].astype(jnp.float32)
                      for j in range(K))
            if conv_b is not None:
                out = out + conv_b.astype(jnp.float32)
            return jax.nn.silu(out)

        def split_xbc(a):
            x, Bm, Cm = jnp.split(a, [c.d_ssm, c.d_ssm + G * N], axis=-1)
            lead = a.shape[:-1]
            return (x.reshape(lead + (H, P)), Bm.reshape(lead + (G, N)),
                    Cm.reshape(lead + (G, N)))

        dt = jax.nn.softplus(dt + dt_bias)                     # [B, L, H]
        if not decode:
            tail = jnp.zeros((B_, K - 1, c.conv_dim), xBC.dtype)
            x, Bm, Cm = split_xbc(conv(jnp.concatenate([tail, xBC], axis=1)))
            y, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=c.chunk_size)
        else:
            R = self.state_rows or B_
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (R, H, N, P), jnp.float32)
            tails = self.variable("cache", "conv_tail", jnp.zeros,
                                  (R, K - 1, c.conv_dim), xBC.dtype)
            if L == 1 and rows is None:
                if R != B_:
                    raise ValueError(
                        f"a decode step advances every row of the state "
                        f"({R}), got {B_}")
                live = (jnp.ones((B_,), bool) if seq_lens is None
                        else seq_lens > 0)
                window = jnp.concatenate([tails.value, xBC], axis=1)
                x, Bm, Cm = split_xbc(conv(window)[:, 0])
                lf = live.astype(jnp.float32)
                x = x * lf[:, None, None]
                y, state.value = ssm_update(state.value, x, dt[:, 0] * lf[:, None],
                                            A, Bm, Cm)
                tails.value = jnp.where(live[:, None, None], window[:, 1:],
                                        tails.value)
                y, x = y[:, None], x[:, None]
            else:
                at = jnp.arange(B_) if rows is None else rows
                S0, tail = state.value[at], tails.value[at]
                if positions is not None:
                    fresh = (positions == 0)
                    S0 = jnp.where(fresh[:, None, None, None], 0.0, S0)
                    tail = jnp.where(fresh[:, None, None], 0, tail)
                sl = (jnp.full((B_,), L, jnp.int32) if seq_lens is None
                      else seq_lens.astype(jnp.int32))
                window = jnp.concatenate([tail, xBC], axis=1)
                x, Bm, Cm = split_xbc(conv(window))
                valid = (jnp.arange(L)[None, :] < sl[:, None]).astype(
                    jnp.float32)
                x = x * valid[:, :, None, None]
                y, S1 = ssd_scan(x, dt * valid[:, :, None], A, Bm, Cm,
                                 chunk=c.chunk_size, init_state=S0)
                # the last K - 1 inputs before the row's true length:
                # window[i] is the input at position i - (K - 1)
                last = jnp.take_along_axis(
                    window, (sl[:, None] + jnp.arange(K - 1))[:, :, None],
                    axis=1)
                state.value = state.value.at[at].set(S1)
                tails.value = tails.value.at[at].set(last)
        y = y + D[:, None] * x
        y = y.reshape(B_, L, c.d_ssm) * jax.nn.silu(z)
        # mamba_rms_norm with norm_before_gate false: the gated product is
        # normed, each group of d_ssm / n_groups channels by itself
        scale = self.param("norm_scale", nn.initializers.ones, (c.d_ssm,))
        yg = y.reshape(B_, L, G, c.d_ssm // G)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + c.norm_eps)
        y = yg.reshape(B_, L, c.d_ssm) * scale.astype(jnp.float32)
        return QuantizableDense(
            u.shape[-1], name="out_proj", use_bias=c.proj_bias,
            dtype=self.dtype,
            kernel_init=_part(("tp", None))(nn.initializers.lecun_normal()))(
                y.astype(self.dtype))
