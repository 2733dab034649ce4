"""Routed experts with a shared one as a block's feed-forward kind
(``mlp="experts"``): the DeepSeek-V3 form, as GLM-4.7-Flash's ``config``
spells it. Dropless, and the work is by assignment.

    s   = sigmoid(float32(f) W_r)                    # one score an expert
    T   = the top_k largest of s + b                 # b: selection only
    g_i = scale * s_i / (sum_{j in T} s_j + 1e-20)   # weights from s alone
    out = sum_{i in T} g_i E_i(f) + E_shared(f),   E(x) = (silu(x Wg) * (x Wu)) Wd

That is the default; :class:`ExpertsConfig` holds what another published
``config`` changes, under its own names (LongCat-Flash: ``scoring_func``
softmax, ``norm_topk_prob`` false, ``n_shared_experts`` 0, ``zero_expert_num``
identity experts, and a chip's share of the experts; below). The router runs
in float32 whatever the compute type. For ``N`` tokens the ``N x top_k`` assignments
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

**Zero-compute experts** (``zero_expert_num`` Z > 0): the router has ``G + Z``
outputs, and a choice ``i >= G`` is an identity expert: it adds ``g_i`` times
the token itself, no weights, no product.

**A chip's share** (``held = (first, count)``): under expert parallelism a
layer's ``G`` experts lie on several chips and this one holds ``count`` of
them from ``first`` on: the stacked weights are ``[count, ...]``. The router
keeps all its outputs and its ``top_k``; the layer computes the part of the
sum its own experts give (and the identity part, which every chip computes
for its own tokens), and an assignment to an expert that lies elsewhere adds
nothing here: it sorts past the last group like a token that is not real.
The parts of all shares, the identity part counted once, add up to the
whole layer (tests/test_longcat_flash.py). There is no exchange in this
layer and nothing that stands in for one.

In a decode apply the layer leaves ``experts_touched`` in the ``cache``
collection: how many of the experts held here this call's real tokens chose,
the number a step's weight traffic follows (the engine sums it over layers
into its ``moe_experts_touched`` counter), and beside it ``assignments_held``
and ``assignments_zero``: how many of their choices entered the grouped
product and how many were identity experts (all and none where the layer
holds every expert its router scores).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

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
    scoring_func: str = "sigmoid"     # or "softmax", over all outputs
    norm_topk_prob: bool = True       # gates divided by the chosen's sum
    n_shared_experts: int = 1         # 0: no shared expert
    zero_expert_num: int = 0          # identity experts after the routed
    # (first, count) of the routed experts whose weights are here; None: all
    held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown scoring_func {self.scoring_func!r} "
                             f"(valid: 'sigmoid', 'softmax')")
        if self.n_shared_experts not in (0, 1):
            raise ValueError("n_shared_experts is 0 or 1")
        first, count = self.held_range
        if not (0 <= first and 0 < count
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held {self.held!r} is no range of "
                             f"{self.n_routed_experts} experts")

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.n_routed_experts)


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


def route(scores, bias, top_k: int, scaling: float, normalise: bool = True):
    """scores [N, G] float32 (after the sigmoid or softmax) -> (experts
    [N, k] int32, gates [N, k] float32): selection by ``scores + bias``,
    weights from ``scores``, with ``normalise`` divided by the chosen's
    sum."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalise:
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
        first, held = c.held_range
        N = B * L
        xf = x.reshape(N, E)
        w_r = self.param("router", _part((None, None))(
            nn.initializers.lecun_normal()), (E, G + c.zero_expert_num))
        b_r = self.param("router_bias", nn.initializers.zeros,
                         (G + c.zero_expert_num,))
        stack = lambda name, shape, names: jnp.asarray(self.param(
            name, _part(names)(nn.initializers.lecun_normal(
                in_axis=1, out_axis=2, batch_axis=0)), shape), self.dtype)
        w_gate = stack("w_gate", (held, E, width), (None, None, "tp"))
        w_up = stack("w_up", (held, E, width), (None, None, "tp"))
        w_down = stack("w_down", (held, width, E), (None, "tp", None))

        score = (jax.nn.sigmoid if c.scoring_func == "sigmoid"
                 else jax.nn.softmax)
        scores = score(jnp.dot(
            xf.astype(jnp.float32), jnp.asarray(w_r, jnp.float32),
            precision="highest"))
        chosen, gates = route(scores, jnp.asarray(b_r, jnp.float32), k,
                              c.routed_scaling_factor, c.norm_topk_prob)
        # (kept for whoever asks for ``intermediates``: a test, a probe of
        # how often a lower precision flips a choice; nothing otherwise)
        self.sow("intermediates", "chosen", chosen)
        # assignments sorted by expert; a token that is not real sorts past
        # the last group and belongs to none, as does a choice of an expert
        # that is not held here (another chip's, or an identity expert)
        live = real.reshape(N)
        mine = live[:, None] & (chosen >= first) & (chosen < first + held)
        flat = jnp.where(mine, chosen - first, held).reshape(N * k)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
        kernel = decode and jax.default_backend() == "tpu"
        xs = xf[order // k]
        a = grouped_matmul(xs, w_gate, sizes, w_up, kernel=kernel)
        ys = grouped_matmul(a, w_down, sizes, kernel=kernel)
        # back to token order; rows of no group hold nothing meant
        y = jnp.zeros_like(ys).at[order].set(ys).reshape(N, k, E)
        y = jnp.where(mine[:, :, None], y, 0).astype(jnp.float32)
        out = jnp.einsum("nke,nk->ne", y, gates)
        zero = live[:, None] & (chosen >= G)
        if c.zero_expert_num:
            # the identity experts: the token itself, times their gates
            out = out + (jnp.where(zero, gates, 0.0).sum(-1, keepdims=True)
                         * xf.astype(jnp.float32))
        out = out.astype(x.dtype)
        if decode:
            counts = {"experts_touched": (sizes > 0).sum(),
                      "assignments_held": mine.sum(),
                      "assignments_zero": zero.sum()}
            for name, n in counts.items():
                self.variable("cache", name, lambda: jnp.zeros((), jnp.int32)
                              ).value = n.astype(jnp.int32)
        if not c.n_shared_experts:
            return out.reshape(B, L, E)
        wide = lambda name: QuantizableDense(
            width, name=name, use_bias=False, dtype=self.dtype,
            kernel_init=_part((None, "tp"))(nn.initializers.lecun_normal()))
        sh = nn.silu(wide("shared_gate")(x)) * wide("shared_up")(x)
        return out.reshape(B, L, E) + QuantizableDense(
            E, name="shared_out", use_bias=False, dtype=self.dtype,
            kernel_init=_part(("tp", None))(
                nn.initializers.lecun_normal()))(sh)
