"""Pipeline parallelism over the ``pp`` mesh axis (GPipe schedule, SPMD style).

No counterpart in the reference (SURVEY §2.4: pipeline parallelism — NO); this
is the TPU-idiomatic extension for models deeper than one device's HBM. The
transformer stack is split into ``pp`` stages of identical structure; stage
parameters are stacked on a leading ``[S, ...]`` axis sharded over ``pp``, and
one ``shard_map`` runs the GPipe schedule: each device executes its resident
stage every tick, activations hop stage-to-stage over ICI via
``jax.lax.ppermute``, and microbatches stream through to fill the pipe
(bubble fraction (S-1)/(M+S-1)). The whole schedule is a ``lax.scan``, so it
is a single differentiable XLA program — backprop replays the ring in reverse
with no hand-written backward pass.

Composes with data parallelism: the batch axis is sharded over ``dp`` in the
same shard_map. (Within-stage tensor parallelism would require manual
collectives inside the stage body — XLA's automatic sharding does not reach
inside shard_map — so stages here run dp x pp; use SPMDTrainer's tp/sp mesh
for within-layer sharding instead.)
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Any, Callable, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("kubeml.pipeline")


def gpipe(
    stage_apply: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x_mb: jnp.ndarray,
    axis_name: str = "pp",
):
    """The GPipe schedule. MUST run inside shard_map over ``axis_name``.

    ``stage_params``: the local stage's parameter pytree (leading stage axis
    already stripped to this device's slice of size 1).
    ``x_mb``: [M, mb, ...] microbatches, replicated over the pp axis.
    Returns [M, mb, ...] outputs, identical on every pp rank.
    """
    S = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]
    T = M + S - 1  # total ticks: fill + drain
    perm = [(i, i + 1) for i in range(S - 1)]  # stage i -> i+1; rank 0 gets zeros

    params_local = jax.tree.map(lambda p: p[0], stage_params)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t (clipped during drain); others take the
        # activation handed to them last tick
        x_t = jax.lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        block_in = jnp.where(idx == 0, x_t, state)
        out = stage_apply(params_local, block_in)
        # the last stage completes microbatch m = t-(S-1) at tick t
        m = t - (S - 1)
        mc = jnp.clip(m, 0, M - 1)
        write = (idx == S - 1) & (m >= 0)
        prev = jax.lax.dynamic_index_in_dim(outputs, mc, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, out, prev), mc, 0
        )
        state = jax.lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    state0 = jnp.zeros_like(x_mb[0])
    outputs0 = jnp.zeros_like(x_mb)
    # constants are device-invariant; the carry becomes device-varying
    state0, outputs0 = (
        jax.lax.pcast(v, (axis_name,), to="varying") for v in (state0, outputs0)
    )
    (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0), jnp.arange(T))
    # only the last stage holds real outputs; zero the rest and sum-broadcast
    outputs = jnp.where(idx == S - 1, outputs, 0.0)
    return jax.lax.psum(outputs, axis_name)


class PipelinedLM:
    """Decoder-only LM with its block stack pipelined over ``pp``.

    Embedding + position (front) and final norm + head (back) are replicated
    (they are a small fraction of parameters); the ``depth``-layer block stack
    runs as ``pp`` stages of ``depth/pp`` layers each via :func:`gpipe`.
    """

    def __init__(
        self,
        mesh: Mesh,
        vocab_size: int = 1000,
        max_len: int = 128,
        embed_dim: int = 64,
        depth: int = 4,
        num_heads: int = 4,
        mlp_ratio: int = 4,
        microbatches: int = 4,
        pad_id: int = 0,
    ):
        from ..ops.attention import dot_product_attention

        self.mesh = mesh
        self.stages = int(mesh.shape.get("pp", 1))
        if depth % self.stages != 0:
            raise ValueError(f"depth {depth} must divide into pp={self.stages} stages")
        self.layers_per_stage = depth // self.stages
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.microbatches = microbatches
        self.pad_id = pad_id

        class StageBlock(nn.Module):
            """Pre-LN transformer block with UNannotated params: partitioning
            metadata would try to apply with_sharding_constraint inside the
            manual (shard_map) region; stage placement is the pp sharding of
            the stacked leading axis instead."""

            n_heads: int
            ratio: int

            @nn.compact
            def __call__(self, x):
                B, L, E = x.shape
                H = self.n_heads
                D = E // H
                y = nn.LayerNorm(name="ln1")(x)
                q = nn.Dense(E, use_bias=False, name="query")(y).reshape(B, L, H, D)
                k = nn.Dense(E, use_bias=False, name="key")(y).reshape(B, L, H, D)
                v = nn.Dense(E, use_bias=False, name="value")(y).reshape(B, L, H, D)
                a = dot_product_attention(q, k, v, causal=True)
                x = x + nn.Dense(E, use_bias=False, name="proj")(a.reshape(B, L, E))
                y = nn.LayerNorm(name="ln2")(x)
                y = nn.Dense(E * self.ratio, name="mlp_in")(y)
                y = nn.gelu(y)
                return x + nn.Dense(E, name="mlp_out")(y)

        class Stage(nn.Module):
            """One pipeline stage: layers_per_stage blocks (no sp/tp inside).
            Pad positions are zeroed in the embedding up front; attention over
            pads is neutralized by causality + the loss mask, keeping the
            stage signature activation-only."""

            n_layers: int
            n_heads: int
            ratio: int

            @nn.compact
            def __call__(self, x):
                for i in range(self.n_layers):
                    x = StageBlock(self.n_heads, self.ratio, name=f"layer_{i}")(x)
                return x

        self.stage_module = Stage(self.layers_per_stage, num_heads, mlp_ratio)

        class Outer(nn.Module):
            """Embedding + head (replicated params)."""

            vocab: int
            maxlen: int
            dim: int

            @nn.compact
            def __call__(self, ids):
                x = nn.Embed(self.vocab, self.dim, name="token_embed")(ids)
                pos = self.param("pos_embed", nn.initializers.normal(0.02),
                                 (1, self.maxlen, self.dim))
                return x + pos[:, : ids.shape[1]]

        class Head(nn.Module):
            vocab: int

            @nn.compact
            def __call__(self, x):
                x = nn.LayerNorm(name="ln_f")(x)
                return nn.Dense(self.vocab, name="lm_head", use_bias=False)(x)

        self.embed_module = Outer(vocab_size, max_len, embed_dim)
        self.head_module = Head(vocab_size)

    # --- params ---

    def init(self, rng: jax.Array, sample_ids: np.ndarray) -> Dict[str, Any]:
        ids = jnp.asarray(sample_ids, jnp.int32)
        r_embed, r_stage, r_head = jax.random.split(rng, 3)
        embed = self.embed_module.init(r_embed, ids)
        x = self.embed_module.apply(embed, ids)
        mb = max(1, ids.shape[0] // self.microbatches)
        stage_keys = jax.random.split(r_stage, self.stages)
        stacked = jax.vmap(lambda k: self.stage_module.init(k, x[:mb]))(stage_keys)
        head = self.head_module.init(r_head, x)
        return {"embed": embed, "stages": stacked, "head": head}

    # --- forward ---

    def apply(self, variables: Dict[str, Any], token_ids: jnp.ndarray) -> jnp.ndarray:
        ids = jnp.asarray(token_ids, jnp.int32)
        B, L = ids.shape
        M = self.microbatches
        if B % M != 0:
            raise ValueError(f"batch {B} must divide into {M} microbatches")
        mb = B // M
        x = self.embed_module.apply(variables["embed"], ids)
        x = x * (ids != self.pad_id)[..., None]  # zero pad embeddings
        x_mb = x.reshape(M, mb, L, self.embed_dim)

        pipe = jax.shard_map(
            partial(gpipe, lambda p, a: self.stage_module.apply(p, a), axis_name="pp"),
            mesh=self.mesh,
            in_specs=(jax.tree.map(lambda _: P("pp"), variables["stages"]),
                      P(None, "dp")),
            out_specs=P(None, "dp"),
            check_vma=False,
        )
        y_mb = pipe(variables["stages"], x_mb)
        y = y_mb.reshape(B, L, self.embed_dim)
        return self.head_module.apply(variables["head"], y)

    def reference_apply(self, variables: Dict[str, Any], token_ids: jnp.ndarray) -> jnp.ndarray:
        """Sequential (non-pipelined) forward for correctness checks."""
        ids = jnp.asarray(token_ids, jnp.int32)
        x = self.embed_module.apply(variables["embed"], ids)
        x = x * (ids != self.pad_id)[..., None]
        for s in range(self.stages):
            params_s = jax.tree.map(lambda p: p[s], variables["stages"])
            x = self.stage_module.apply(params_s, x)
        return self.head_module.apply(variables["head"], x)


class PipelineTrainer:
    """Minimal training loop around :class:`PipelinedLM` (adamw + lm_loss).

    Variables are placed explicitly (stage stack over ``pp``, embed/head
    replicated); optimizer state and step outputs inherit their shardings via
    XLA propagation from the placed inputs (mu/nu follow the params they
    mirror), so no hand-built optimizer sharding tree is needed."""

    def __init__(self, model: PipelinedLM, optimizer=None, lr: float = 3e-4):
        from .trainer import lm_loss

        self.model = model
        self.tx = optimizer or optax.adamw(lr)
        self.loss_fn = lm_loss
        self.variables = None
        self.opt_state = None
        self._step = None

    def init(self, rng: jax.Array, sample_ids: np.ndarray) -> None:
        model = self.model
        variables = model.init(rng, sample_ids)
        rep = NamedSharding(model.mesh, P())
        stage = NamedSharding(model.mesh, P("pp"))
        shardings = {
            "embed": jax.tree.map(lambda _: rep, variables["embed"]),
            "stages": jax.tree.map(lambda _: stage, variables["stages"]),
            "head": jax.tree.map(lambda _: rep, variables["head"]),
        }
        self.variables = jax.device_put(variables, shardings)
        with jax.set_mesh(model.mesh):
            self.opt_state = jax.jit(self.tx.init)(self.variables)

    def train_step(self, batch_ids: np.ndarray) -> jnp.ndarray:
        if self.variables is None:
            raise RuntimeError("call init() first")
        if self._step is None:
            model, tx, loss_fn = self.model, self.tx, self.loss_fn

            def step(variables, opt_state, ids):
                def compute(vs):
                    logits = model.apply(vs, ids)
                    return loss_fn(logits.astype(jnp.float32), ids)

                loss, grads = jax.value_and_grad(compute)(variables)
                updates, opt_next = tx.update(grads, opt_state, variables)
                return optax.apply_updates(variables, updates), opt_next, loss

            self._step = jax.jit(step, donate_argnums=(0, 1))
            log.info("compiling pipeline step: mesh=%s", dict(model.mesh.shape))
        batch_sharding = NamedSharding(self.model.mesh, P("dp"))
        ids = jax.device_put(jnp.asarray(batch_ids, jnp.int32), batch_sharding)
        with jax.set_mesh(self.model.mesh):
            self.variables, self.opt_state, loss = self._step(
                self.variables, self.opt_state, ids
            )
        return loss
