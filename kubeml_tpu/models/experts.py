"""Routed experts with a shared one as a block's feed-forward kind
(``mlp="experts"``): the DeepSeek-V3 form, as GLM-4.7-Flash's ``config``
spells it. Dropless, and the work is by assignment.

    s   = sigmoid(float32(f) W_r)                    # one score an expert
    T   = the top_k largest of s + b                 # b: selection only
    g_i = scale * s_i / (sum_{j in T} s_j + 1e-20)   # weights from s alone
    out = sum_{i in T} g_i E_i(f) + E_shared(f),   E(x) = (silu(x Wg) * (x Wu)) Wd

(The published ``norm_topk_prob`` true and ``n_shared_experts`` 1 are what
is built: no configuration here has another value.) The router runs in
float32 whatever the compute type. For ``N`` tokens the ``N x top_k`` assignments
are sorted by expert and go through three grouped products over the
experts' stacked weights ``[experts, E, width]`` (gate, up) and ``[experts,
width, E]`` (down) — ops/grouped_matmul.py: XLA's ``ragged_dot`` in the
whole-sequence forward and off the chip, the ``moe_experts`` Pallas kernel
in a decode apply (admission and step) on a TPU — then back to token order, weighted by the gates in float32 and summed.
No capacity, no dropped token, no auxiliary loss: an expert that no token
chose is no work and its weights are not read, and every token's every
choice is computed however skewed the routing. Tokens that are not real
(a dead row of a decode step, the padding of a prefill bucket) are given
to no expert. This is the serving path's expert layer; ``parallel/moe.py``
is the GShard-style training layer over an ``ep`` mesh axis.

In a decode apply the layer leaves ``experts_touched`` in the ``cache``
collection: how many experts this call's real tokens chose, the number a
step's weight traffic follows (the engine sums it over layers into its
``moe_experts_touched`` counter).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul
from .layers import QuantizableDense


@dataclass(frozen=True)
class ExpertsConfig:
    """The expert layer's sizes, under the names of the published
    ``config``."""

    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    routed_scaling_factor: float = 1.0


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


def route(scores, bias, top_k: int, scaling: float):
    """scores [N, G] float32 (after the sigmoid) -> (experts [N, k] int32,
    gates [N, k] float32): selection by ``scores + bias``, weights from
    ``scores``, normalised over the chosen."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), gates * scaling


class ExpertMLP(nn.Module):
    cfg: ExpertsConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, real, decode: bool = False):
        """x [B, L, E]; ``real`` [B, L] bool, the tokens that are given to
        experts (the others get the shared expert's output alone)."""
        c = self.cfg
        B, L, E = x.shape
        G, k, width = (c.n_routed_experts, c.num_experts_per_tok,
                       c.moe_intermediate_size)
        N = B * L
        xf = x.reshape(N, E)
        w_r = self.param("router", _part((None, None))(
            nn.initializers.lecun_normal()), (E, G))
        b_r = self.param("router_bias", nn.initializers.zeros, (G,))
        stack = lambda name, shape, names: jnp.asarray(self.param(
            name, _part(names)(nn.initializers.lecun_normal(
                in_axis=1, out_axis=2, batch_axis=0)), shape), self.dtype)
        w_gate = stack("w_gate", (G, E, width), (None, None, "tp"))
        w_up = stack("w_up", (G, E, width), (None, None, "tp"))
        w_down = stack("w_down", (G, width, E), (None, "tp", None))

        scores = jax.nn.sigmoid(jnp.dot(
            xf.astype(jnp.float32), jnp.asarray(w_r, jnp.float32),
            precision="highest"))
        chosen, gates = route(scores, jnp.asarray(b_r, jnp.float32), k,
                              c.routed_scaling_factor)
        # (kept for whoever asks for ``intermediates``: a test, a probe of
        # how often a lower precision flips a choice; nothing otherwise)
        self.sow("intermediates", "chosen", chosen)
        # assignments sorted by expert; a token that is not real sorts past
        # the last group and belongs to none
        live = real.reshape(N)
        flat = jnp.where(live[:, None], chosen, G).reshape(N * k)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((G + 1,), jnp.int32).at[flat].add(1)[:G]
        kernel = decode and jax.default_backend() == "tpu"
        xs = xf[order // k]
        a = grouped_matmul(xs, w_gate, sizes, w_up, kernel=kernel)
        ys = grouped_matmul(a, w_down, sizes, kernel=kernel)
        # back to token order; rows of no group hold nothing meant
        y = jnp.zeros_like(ys).at[order].set(ys).reshape(N, k, E)
        y = jnp.where(live[:, None, None], y, 0).astype(jnp.float32)
        out = jnp.einsum("nke,nk->ne", y, gates).astype(x.dtype)
        if decode:
            touched = self.variable("cache", "experts_touched",
                                    lambda: jnp.zeros((), jnp.int32))
            touched.value = (sizes > 0).sum().astype(jnp.int32)
        wide = lambda name: QuantizableDense(
            width, name=name, use_bias=False, dtype=self.dtype,
            kernel_init=_part((None, "tp"))(nn.initializers.lecun_normal()))
        sh = nn.silu(wide("shared_gate")(x)) * wide("shared_up")(x)
        return out.reshape(B, L, E) + QuantizableDense(
            E, name="shared_out", use_bias=False, dtype=self.dtype,
            kernel_init=_part(("tp", None))(
                nn.initializers.lecun_normal()))(sh)
