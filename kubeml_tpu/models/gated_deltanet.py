"""The Gated DeltaNet mixer as a layer's token mixer IN PLACE OF attention
(Olmo-Hybrid: three such layers to every full-attention layer; the keys are
those of the ``fla`` layer of that name, arXiv:2412.06464). ``H`` heads, keys
``d_k`` wide, values ``d_v`` wide:

    q|k|v = silu(causal_depthwise_conv1d(x W_q | x W_k | x W_v, width d_conv))   # no bias
    q_h, k_h = l2norm(q_h) / sqrt(d_k), l2norm(k_h)                               # per head
    beta  = 2 sigmoid(x W_b);   g = -exp(A_log) softplus(x W_a + dt_bias)         # [H]
    S_t   = exp(g_t) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t
    out   = (RMSNorm_head(o) * silu(x W_g)) W_o

**Kimi Delta Attention** (Kimi-Linear, arXiv:2510.26692; the ``fla`` layer
``KimiDeltaAttention``; :class:`KDAConfig`) is the same mixer with another
gate: the decay is one a KEY CHANNEL, made through a low-rank pair with a
bias a channel; ``beta`` lies in (0, 1); the output gate is a low-rank pair
under a sigmoid:

    beta  = sigmoid(x W_b)                                                        # [H]
    g     = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)                       # [H, d_k]
    S_t   = Diag(exp(g_t)) S_{t-1};  the delta rule and o_t as above
    out   = (RMSNorm_head(o) * sigmoid((x W_ga) W_gb)) W_o

The convolutions, the heads' norms, the three entries and the cache leaves
are the one module's; ``cfg.gate_width`` (1 or ``d_k``) says which gate runs.

Three entries compute that one function, as models/mamba2.py's do
(ops/gated_delta.py has the recurrence):

* the whole sequence from zeros, in chunks (the non-decode forward);
* ``decode=True`` over ``L`` positions, from zeros where a row's
  ``positions`` is 0 and from the row's carried state otherwise (prefill).
  Positions at or past a row's ``seq_lens`` leave the state untouched
  (``g`` and ``beta`` are zeroed there) and the convolutions' tail is
  gathered at the row's true length, so the bucket a prompt is padded to
  cannot be seen in its state;
* ``decode=True``, one position, no ``rows``: the engine's decode step, the
  state advanced in place by the ``gdn_update`` kernel (named
  ``kda_update`` in a trace where the gate is per channel). A row whose
  ``seq_lens`` is 0 (not live) keeps its state and its tail.

A layer of this kind keeps NO paged cache. Its state lives in the ``cache``
collection per program row: ``gdn_state`` ``[rows, H / p, d_k, p d_v]``
float32 (ops/gated_delta.py pack_state: it is multiplied by a decay near 1
for hundreds of steps) and ``conv_tail`` ``[d_conv - 1, rows, 2 H d_k + H
d_v]``, the three convolutions' inputs side by side, float32 as the
convolution's input is, the taps leading so that the rows lie on the
sublanes (three taps there would be stored in eight). ``rows`` is the
engine's slab (``state_rows``) or, without it, the batch; the ``rows``
argument names each batch row's place in it (an admit program's rows are not
the slab's).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.gated_delta import (gdn_chunked, gdn_update, heads_packed,
                               pack_state, unpack_state)
from .layers import QuantizableDense


@dataclass(frozen=True)
class GDNConfig:
    """The mixer's sizes, under the names of the published ``config``."""

    num_heads: int            # linear_num_key_heads == linear_num_value_heads
    key_dim: int              # linear_key_head_dim
    value_dim: int            # linear_value_head_dim
    d_conv: int = 4           # linear_conv_kernel_dim
    neg_eigval: bool = True   # linear_allow_neg_eigval: beta in (0, 2)
    norm_eps: float = 1e-6    # rms_norm_eps

    # what a row's gate carries a step: one decay a head
    gate_width: ClassVar[int] = 1

    @property
    def conv_dim(self) -> int:
        return self.num_heads * (2 * self.key_dim + self.value_dim)

    @property
    def state_row_bytes(self) -> int:
        """Bytes one program row's state takes in one layer: the float32
        state's live values and the convolutions' tail."""
        return 4 * (self.num_heads * self.key_dim * self.value_dim
                    + (self.d_conv - 1) * self.conv_dim)


@dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention's sizes, under the names of the published
    ``linear_attn_config``: keys and values are both ``head_dim`` wide, and
    so is the low rank of the two gate pairs (the ``fla`` layer's
    ``f_proj`` and ``g_proj``). What the mixer reads of a
    :class:`GDNConfig` it reads of this under the same names."""

    num_heads: int                    # num_heads
    head_dim: int                     # head_dim: d_k = d_v = the gates' rank
    short_conv_kernel_size: int = 4   # short_conv_kernel_size
    norm_eps: float = 1e-5            # rms_norm_eps
    neg_eigval: ClassVar[bool] = False   # beta in (0, 1)

    key_dim = property(lambda self: self.head_dim)
    value_dim = property(lambda self: self.head_dim)
    d_conv = property(lambda self: self.short_conv_kernel_size)
    # what a row's gate carries a step: one decay a key channel
    gate_width = property(lambda self: self.head_dim)
    conv_dim = GDNConfig.conv_dim
    state_row_bytes = GDNConfig.state_row_bytes


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


class GatedDeltaNet(nn.Module):
    cfg: Union[GDNConfig, KDAConfig]
    dtype: Any = jnp.float32
    state_rows: int = 0

    @nn.compact
    def __call__(self, u, decode: bool = False, positions=None,
                 seq_lens=None, rows=None):
        c = self.cfg
        B_, L, E = u.shape
        H, dk, dv, K = c.num_heads, c.key_dim, c.value_dim, c.d_conv
        proj = lambda width, name: QuantizableDense(
            width, name=name, use_bias=False, dtype=self.dtype,
            kernel_init=_part((None, "tp"))(nn.initializers.lecun_normal()))(
                u).astype(jnp.float32)
        # everything after the projections is float32: the convolutions,
        # the recurrence, the gate and the norm
        qkv = jnp.concatenate([proj(H * dk, "q_proj"), proj(H * dk, "k_proj"),
                               proj(H * dv, "v_proj")], axis=-1)
        if c.gate_width == 1:
            a, b = proj(H, "a_proj"), proj(H, "b_proj")
            gate = jax.nn.silu(proj(H * dv, "g_proj"))
        else:
            # a decay a key channel and the output gate, each through a
            # low-rank pair (the compute type in between, float32 out)
            pair = lambda name, width: QuantizableDense(
                width, name=f"{name}_b_proj", use_bias=False,
                dtype=self.dtype, kernel_init=_part((None, "tp"))(
                    nn.initializers.lecun_normal()))(QuantizableDense(
                        c.head_dim, name=f"{name}_a_proj", use_bias=False,
                        dtype=self.dtype,
                        kernel_init=nn.initializers.lecun_normal())(
                            u)).astype(jnp.float32)
            a, b = pair("f", H * dk).reshape(B_, L, H, dk), proj(H, "b_proj")
            gate = jax.nn.sigmoid(pair("g", H * dv))
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, c.conv_dim)).astype(jnp.float32)
        A = jnp.exp(self.param(
            "A_log", lambda k, s: jnp.log(jax.random.uniform(
                k, s, jnp.float32, 1e-3, 16.0)), (H,)).astype(jnp.float32))
        # one bias a head, or one a key channel of each head
        dt_bias = self.param("dt_bias", nn.initializers.zeros,
                             a.shape[2:]).astype(jnp.float32)
        # [B, L, H] or [B, L, H, d_k]
        g = -A.reshape(A.shape + (1,) * (a.ndim - 3)) * jax.nn.softplus(
            a + dt_bias)
        beta = (2.0 if c.neg_eigval else 1.0) * jax.nn.sigmoid(b)
        # a position that moves nothing: ``g`` and ``beta`` times 0
        masked = lambda t, m: t * m.reshape(m.shape + (1,) * (t.ndim - m.ndim))

        def conv(window, n=None):
            """silu(conv) of ``window``: [B, K - 1 + n, C] -> [B, n, C], or
            the taps leading (``n`` None) [K, R, C] -> [R, C]. Tap j weighs
            the input K - 1 - j positions back."""
            tap = ((lambda j: window[j]) if n is None
                   else (lambda j: window[:, j:j + n]))
            return jax.nn.silu(sum(tap(j) * conv_w[j] for j in range(K)))

        def heads(x):
            q, k, v = jnp.split(x, [H * dk, 2 * H * dk], axis=-1)
            lead = x.shape[:-1]
            return (_l2norm(q.reshape(lead + (H, dk))) * dk ** -0.5,
                    _l2norm(k.reshape(lead + (H, dk))),
                    v.reshape(lead + (H, dv)))

        if not decode:
            tail = jnp.zeros((B_, K - 1, c.conv_dim), jnp.float32)
            q, k, v = heads(conv(jnp.concatenate([tail, qkv], axis=1), L))
            o, _ = gdn_chunked(q, k, v, g, beta)
        else:
            R = self.state_rows or B_
            p = heads_packed(H, dv)
            state = self.variable("cache", "gdn_state", jnp.zeros,
                                  (R, H // p, dk, p * dv), jnp.float32)
            tails = self.variable("cache", "conv_tail", jnp.zeros,
                                  (K - 1, R, c.conv_dim), jnp.float32)
            if L == 1 and rows is None:
                if R != B_:
                    raise ValueError(
                        f"a decode step advances every row of the state "
                        f"({R}), got {B_}")
                live = (jnp.ones((B_,), bool) if seq_lens is None
                        else seq_lens > 0)
                window = jnp.concatenate(
                    [tails.value, jnp.moveaxis(qkv, 1, 0)], axis=0)  # [K, R, C]
                q, k, v = heads(conv(window))
                lf = live.astype(jnp.float32)
                o, state.value = gdn_update(state.value, q, k, v,
                                            masked(g[:, 0], lf),
                                            masked(beta[:, 0], lf))
                tails.value = jnp.where(live[None, :, None], window[1:],
                                        tails.value)
                o = o[:, None]
            else:
                at = jnp.arange(B_) if rows is None else rows
                S0 = unpack_state(state.value[at], H)
                tail = jnp.moveaxis(tails.value[:, at], 0, 1)
                if positions is not None:
                    fresh = (positions == 0)
                    S0 = jnp.where(fresh[:, None, None, None], 0.0, S0)
                    tail = jnp.where(fresh[:, None, None], 0.0, tail)
                sl = (jnp.full((B_,), L, jnp.int32) if seq_lens is None
                      else seq_lens.astype(jnp.int32))
                window = jnp.concatenate([tail, qkv], axis=1)
                q, k, v = heads(conv(window, L))
                valid = (jnp.arange(L)[None, :] < sl[:, None]).astype(
                    jnp.float32)
                o, S1 = gdn_chunked(q, k, v, masked(g, valid),
                                    masked(beta, valid), init_state=S0)
                # the last K - 1 inputs before the row's true length:
                # window[i] is the input at position i - (K - 1)
                last = jnp.take_along_axis(
                    window, (sl[:, None] + jnp.arange(K - 1))[:, :, None],
                    axis=1)
                state.value = state.value.at[at].set(pack_state(S1))
                tails.value = tails.value.at[:, at].set(
                    jnp.moveaxis(last, 1, 0))
        # each head's output normed over its d_v values, one weight of d_v
        scale = self.param("norm_scale", nn.initializers.ones, (dv,))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + c.norm_eps) * scale.astype(jnp.float32)
        y = o.reshape(B_, L, H * dv) * gate
        return QuantizableDense(
            E, name="o_proj", use_bias=False, dtype=self.dtype,
            kernel_init=_part(("tp", None))(nn.initializers.lecun_normal()))(
                y.astype(self.dtype))
