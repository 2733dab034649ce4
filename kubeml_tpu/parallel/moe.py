"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

No counterpart in the reference (SURVEY §2.4: expert parallelism — NO); this is
the TPU-idiomatic extension. Design follows the Switch/GShard dense-dispatch
recipe: tokens are routed top-k with a capacity limit, dispatch/combine are
einsums against one-hot masks, and expert weights carry a leading ``[E, ...]``
axis annotated ``ep`` via ``nn.with_partitioning`` — sharding propagation turns
the dispatch einsum into the all-to-all over ICI (the scaling-book recipe: pick
the mesh, annotate, let XLA insert the collectives).

The router's load-balancing auxiliary loss (mean over experts of
fraction-routed x mean-gate, scaled by E, the Switch formulation) is sown into
the ``"aux_loss"`` collection; :class:`kubeml_tpu.parallel.trainer.SPMDTrainer`
collects it during the loss computation.

This is the TRAINING-side expert layer (``CausalTransformer(moe_every=...)``):
a capacity, dropped overflow and dense one-hot dispatch suit 8 experts on a
mesh and have no paged decode path (``supports_paged_decode`` refuses the
model). The expert layer that SERVES is ``models/experts.py``
(``mlp="experts"``): dropless, sorted assignments through grouped products,
sigmoid scores with a selection bias and a shared expert.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


class MoEMlp(nn.Module):
    """Drop-in replacement for a transformer MLP block: routed expert FFNs.

    Token dispatch: top-``top_k`` gating over ``num_experts`` with per-expert
    capacity ``ceil(tokens/num_experts * capacity_factor)``; overflow tokens
    fall through the residual (standard Switch behavior).
    Expert weights: ``[E, D, H]`` / ``[E, H, D]`` sharded (ep, -, tp).
    """

    num_experts: int = 8
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    router_noise: float = 1e-2

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False,
                 decode: bool = False) -> jnp.ndarray:
        B, L, D = x.shape
        E = self.num_experts
        S = B * L
        H = D * self.mlp_ratio
        cap = max(1, int((S / E) * self.capacity_factor))

        tokens = x.reshape(S, D)

        # --- router (always f32: tiny, and gate ordering must be stable) ---
        router_w = self.param(
            "router", _part((None, None))(nn.initializers.lecun_normal()), (D, E)
        )
        logits = jnp.einsum("sd,de->se", tokens.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        if train and self.router_noise > 0:
            rng = self.make_rng("dropout")
            logits = logits + self.router_noise * jax.random.normal(rng, logits.shape)
        gates = jax.nn.softmax(logits, axis=-1)  # [S, E]

        # expert weights (shared by both routing paths below)
        w_in = self.param(
            "w_in", _part(("ep", None, "tp"))(nn.initializers.lecun_normal()), (E, D, H)
        )
        w_out = self.param(
            "w_out", _part(("ep", "tp", None))(nn.initializers.lecun_normal()), (E, H, D)
        )

        if decode:
            # Serving path: UNCAPPED top-k routing (standard no-token-dropping
            # inference). Capacity competition makes a token's output depend
            # on how many OTHER tokens already claimed its expert's slots —
            # not causally consistent, so KV-cache incremental decode could
            # never reproduce a capped full forward. Without the cap each
            # token routes independently: decode steps route exactly like a
            # full forward. Cost: every expert runs on every token (gates
            # zero the non-chosen ones) — E/top_k x the dense-MLP FLOPs, the
            # price of causal consistency; on decode STEPS the token count
            # is the slot count, and PREFILL scans over experts so peak
            # memory stays [S, H] per expert instead of an [E, S, H] slab.
            kth = jax.lax.top_k(gates, self.top_k)[0][:, -1:]
            keep = (gates >= kth).astype(jnp.float32) * gates
            keep = keep / jnp.maximum(keep.sum(-1, keepdims=True), 1e-9)
            keep = keep.astype(tokens.dtype)

            def one_expert(acc, ws):
                w_i, w_o, k_e = ws  # [D, H], [H, D], [S]
                h = jax.nn.gelu(tokens @ w_i.astype(tokens.dtype))
                return acc + k_e[:, None] * (h @ w_o.astype(tokens.dtype)), None

            out, _ = jax.lax.scan(
                one_expert, jnp.zeros_like(tokens), (w_in, w_out, keep.T))
            return out.reshape(B, L, D)

        # --- top-k dispatch with capacity (GShard-style) ---
        # Queue positions must be offset by the tokens already enqueued for the
        # expert in earlier top-k iterations, otherwise a first-choice and a
        # second-choice of the same expert collide in one capacity slot.
        combine = jnp.zeros((S, E, cap), jnp.float32)
        used = jnp.zeros((S, E), jnp.float32)  # experts already taken per token
        enqueued = jnp.zeros((E,), jnp.float32)  # tokens assigned per expert so far
        for _ in range(self.top_k):
            g = gates * (1.0 - used)
            choice = jnp.argmax(g, axis=-1)  # [S]
            onehot = jax.nn.one_hot(choice, E, dtype=jnp.float32)  # [S, E]
            # position within the chosen expert's queue: this iteration's rank
            # plus everything earlier iterations already enqueued
            pos = (jnp.cumsum(onehot, axis=0) - 1.0 + enqueued[None, :]) * onehot
            in_cap = (pos < cap).astype(jnp.float32) * onehot
            slot = jax.nn.one_hot(
                (pos * onehot).sum(-1).astype(jnp.int32), cap, dtype=jnp.float32
            )
            gate_val = (gates * onehot).sum(-1, keepdims=True)  # [S, 1]
            combine = combine + (in_cap * gate_val)[:, :, None] * slot[:, None, :]
            used = used + onehot
            enqueued = enqueued + onehot.sum(axis=0)

        # renormalize kept gates so each token's routed mass sums to 1
        denom = jnp.maximum(combine.sum(axis=(1, 2), keepdims=True), 1e-9)
        combine = combine / denom
        dispatch = (combine > 0.0).astype(tokens.dtype)  # [S, E, cap]

        # --- aux load-balancing loss (Switch eq. 4); sown only at apply time,
        # never captured into the initial variables ---
        if not self.is_initializing():
            frac_routed = dispatch.sum(axis=(0, 2)) / jnp.maximum(dispatch.sum(), 1.0)
            mean_gate = gates.mean(axis=0)
            aux = E * jnp.sum(frac_routed.astype(jnp.float32) * mean_gate)
            self.sow("aux_loss", "moe", self.aux_loss_weight * aux,
                     reduce_fn=lambda _, b: b)
            # capacity-overflow telemetry: fraction of attempted top-k
            # assignments dropped by the capacity limit (those tokens fall
            # through the residual). Sown into its own collection so the
            # trainer can surface it on /metrics without touching the loss.
            asked = jnp.float32(S * self.top_k)
            kept = dispatch.astype(jnp.float32).sum()
            self.sow("moe_stats", "overflow",
                     1.0 - kept / jnp.maximum(asked, 1.0),
                     reduce_fn=lambda _, b: b)

        # --- expert FFNs ([E, cap, D] per-expert batches, ep-sharded) ---
        expert_in = jnp.einsum("sec,sd->ecd", dispatch, tokens)  # a2a via sharding
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w_in.astype(tokens.dtype)))
        expert_out = jnp.einsum("ech,ehd->ecd", h, w_out.astype(tokens.dtype))
        out = jnp.einsum("sec,ecd->sd", combine.astype(tokens.dtype), expert_out)
        return out.reshape(B, L, D)


class MoEBlock(nn.Module):
    """Transformer block with the MLP replaced by routed experts."""

    num_heads: int
    num_experts: int = 8
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    dropout: float = 0.0
    mesh: Optional[object] = None  # jax.sharding.Mesh; for sp attention
    sp_impl: str = "ring"
    dtype: object = jnp.float32  # computation dtype (router stays f32)
    rope: bool = False  # rotary q/k (ops.rotary), forwarded by the parent
    rope_theta: float = 10000.0
    # KV-cache capacity for autoregressive decode (set by the parent from
    # max_len); the expert MLP is position-free, so serving an MoE model is
    # just the attention cache path plus routing the stepped tokens
    cache_len: int = 0

    @nn.compact
    def __call__(self, x, valid, train: bool = False, decode: bool = False,
                 positions=None):
        from ..models.gpt import CausalSelfAttention

        y = nn.LayerNorm(name="ln1", dtype=jnp.float32)(x).astype(self.dtype)
        y = CausalSelfAttention(self.num_heads, mesh=self.mesh,
                                sp_impl=self.sp_impl, dtype=self.dtype,
                                rope=self.rope, rope_theta=self.rope_theta,
                                cache_len=self.cache_len,
                                name="attn")(y, valid, decode=decode,
                                             positions=positions)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y
        y = nn.LayerNorm(name="ln2", dtype=jnp.float32)(x).astype(self.dtype)
        y = MoEMlp(
            num_experts=self.num_experts,
            mlp_ratio=self.mlp_ratio,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            name="moe",
        )(y, train=train, decode=decode)
        return x + y


def MoETransformer(**kwargs):
    """Decoder-only LM with MoE MLPs interleaved every ``moe_every`` blocks —
    a configuration of :class:`kubeml_tpu.models.gpt.CausalTransformer` (one
    embed/head/block-loop implementation serves dense and MoE)."""
    from ..models.gpt import CausalTransformer

    kwargs.setdefault("moe_every", 2)
    return CausalTransformer(**kwargs)


def MoETiny(vocab_size: int = 1000, max_len: int = 64, num_experts: int = 4, mesh=None):
    """Test-sized MoE config."""
    return MoETransformer(vocab_size=vocab_size, max_len=max_len, embed_dim=64,
                          depth=2, num_heads=4, num_experts=num_experts,
                          moe_every=2, mesh=mesh)
