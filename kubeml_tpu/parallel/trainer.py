"""SPMDTrainer — synchronous multi-axis-parallel training over one mesh.

The second engine next to K-AVG (kubeml_tpu.engine.kavg): where K-AVG
reproduces the reference's local-SGD semantics for elastic data parallelism,
SPMDTrainer is the standard TPU recipe for models too big or too
long-context for pure DP — batch sharded over ``dp``, sequence over ``sp``
(ring attention inside the model), weights over ``tp`` (megatron matmuls,
psum inserted by XLA). One jitted step: forward, loss, grads, optimizer
update; gradients are automatically reduced over ``dp`` because params are
replicated on that axis (XLA derives the psum from the shardings — the
scaling-book recipe, no hand-written collectives here).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("kubeml.spmd")


def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray, pad_id: int = 0) -> jnp.ndarray:
    """Next-token cross-entropy over valid (non-pad) positions."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    mask = (targets != pad_id).astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_lm_loss(hidden: jnp.ndarray, lm_kernel: jnp.ndarray,
                    tokens: jnp.ndarray, pad_id: int = 0, chunk: int = 2048,
                    with_acc: bool = False):
    """``lm_loss`` without ever materializing the [B, L, vocab] logits.

    At long context the logits tensor is the HBM wall once flash attention
    removes the L^2 scores (measured on v5e: L=64k x 32k vocab = 8.4 GB f32,
    and XLA keeps fwd+bwd copies). This computes the same masked mean CE from
    the model's final hidden states [B, L, E] and the lm_head kernel [E, V]:
    a ``lax.scan`` over sequence chunks, each chunk's [B, C, V] logits live
    only inside one ``jax.checkpoint`` region, so peak HBM is O(B*C*V) and
    the backward recomputes per chunk instead of storing.

    ``with_acc=True`` also returns next-token top-1 accuracy (eval path).
    Exact parity with the unchunked loss is tested
    (tests/test_generation.py::test_chunked_lm_loss_matches_unchunked)."""
    targets = tokens[:, 1:]
    h = hidden[:, :-1]
    B, n, E = h.shape
    if n == 0:  # length-1 sequences have no next-token targets (lm_loss
        zero = jnp.float32(0.0)  # returns 0 there too, via the mask floor)
        return (zero, zero) if with_acc else zero
    chunk = min(chunk, n)
    pad = (-n) % chunk
    # padded positions get pad_id targets -> zero mask -> no contribution
    h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=pad_id)
    n_chunks = (n + pad) // chunk
    h = h.reshape(B, n_chunks, chunk, E).swapaxes(0, 1)  # [N, B, C, E]
    t = t.reshape(B, n_chunks, chunk).swapaxes(0, 1)     # [N, B, C]

    @jax.checkpoint
    def one(h_c, t_c):
        logits = jnp.einsum("bce,ev->bcv", h_c, lm_kernel).astype(jnp.float32)
        mask = (t_c != pad_id).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, t_c)
        hit = (jnp.argmax(logits, axis=-1) == t_c).astype(jnp.float32)
        return (ce * mask).sum(), (hit * mask).sum(), mask.sum()

    def body(carry, xs):
        s, a, c = carry
        ds, da, dc = one(*xs)
        return (s + ds, a + da, c + dc), None

    (s, a, c), _ = jax.lax.scan(body, (0.0, 0.0, 0.0), (h, t))
    loss = s / jnp.maximum(c, 1.0)
    if with_acc:
        return loss, a / jnp.maximum(c, 1.0)
    return loss


class SPMDTrainer:
    """Owns sharded params/opt-state and one compiled train step for a module.

    ``module`` must accept ``(token_ids, train=...)`` (or ``(x, train=...)``);
    param PartitionSpecs come from the module's own ``nn.with_partitioning``
    annotations via ``nn.get_partition_spec``.
    """

    def __init__(
        self,
        module: nn.Module,
        mesh: Mesh,
        optimizer: Optional[optax.GradientTransformation] = None,
        loss_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] = lm_loss,
        precision: str = "bf16",
        batch_spec: P = P("dp", "sp"),
        donate: bool = True,
        input_transform: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
        logits_chunk: Optional[int] = None,
    ):
        self.module = module
        self.mesh = mesh
        self.tx = optimizer or optax.adamw(3e-4)
        self.loss_fn = loss_fn
        self.precision = precision
        self.batch_spec = batch_spec
        self.donate = donate
        # stream the lm_head + cross-entropy over sequence chunks of this size
        # instead of materializing [B, L, vocab] logits (chunked_lm_loss) —
        # the long-context HBM lever after flash attention; needs a module
        # that honors return_hidden (CausalTransformer) and the default
        # lm_loss (a custom loss_fn sees logits, which this path never forms)
        self.logits_chunk = logits_chunk
        if logits_chunk is not None and loss_fn is not lm_loss:
            raise ValueError("logits_chunk streams the default lm_loss; "
                             "custom loss_fn needs the full logits")
        # device-side input pipeline hook traced into the step (the KubeModel
        # preprocess contract, runtime/model.py — e.g. uint8 dequantization)
        self.input_transform = input_transform
        self._step_fn = None
        self.params = None
        self.opt_state = None
        # expert-capacity overflow rate of the last step (device scalar;
        # -1 sentinel when the model has no MoE layers)
        self.last_moe_overflow = None

    # --- init ---

    def init(self, rng: jax.Array, sample_batch: np.ndarray) -> None:
        sample = jnp.asarray(sample_batch)
        if self.input_transform is not None:
            sample = self.input_transform(sample)
        abstract = jax.eval_shape(lambda r: self.module.init(r, sample, train=False), rng)
        specs = nn.get_partition_spec(abstract)
        param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )

        def _init(r):
            variables = self.module.init(r, sample, train=False)
            return variables

        with jax.set_mesh(self.mesh):
            variables = jax.jit(_init, out_shardings=param_shardings)(rng)
        self.params = variables
        self._param_shardings = param_shardings

        opt_abstract = jax.eval_shape(lambda p: self.tx.init(p["params"]), abstract)
        opt_specs = nn.get_partition_spec(opt_abstract)
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        with jax.set_mesh(self.mesh):
            self.opt_state = jax.jit(
                lambda p: self.tx.init(p["params"]), out_shardings=opt_shardings
            )(self.params)
        self._opt_shardings = opt_shardings

    # --- the step ---

    def _build_step(self):
        module = self.module
        tx = self.tx
        loss_fn = self.loss_fn
        base_cast = (
            (lambda x: x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x)
            if self.precision == "bf16"
            else (lambda x: x)
        )
        transform = self.input_transform
        cast = (lambda x: transform(base_cast(x))) if transform is not None else base_cast

        logits_chunk = self.logits_chunk

        def step(variables, opt_state, batch, rng):
            def compute_loss(params):
                vs = {**variables, "params": params}
                # mutable collections: aux_loss collects router load-balancing
                # penalties sown by MoE layers (kubeml_tpu.parallel.moe);
                # moe_stats carries their capacity-overflow telemetry; both
                # empty for dense models
                if logits_chunk is not None:
                    hidden, sown = module.apply(
                        vs, cast(batch), train=True, rngs={"dropout": rng},
                        mutable=["aux_loss", "moe_stats"], return_hidden=True,
                    )
                    kernel = nn.meta.unbox(params)["lm_head"]["kernel"]
                    loss = chunked_lm_loss(hidden, kernel.astype(hidden.dtype),
                                           batch, chunk=logits_chunk)
                else:
                    logits, sown = module.apply(
                        vs, cast(batch), train=True, rngs={"dropout": rng},
                        mutable=["aux_loss", "moe_stats"],
                    )
                    loss = loss_fn(logits.astype(jnp.float32), batch)
                for leaf in jax.tree.leaves(sown.get("aux_loss", {})):
                    loss = loss + jnp.sum(leaf)
                stats = jax.tree.leaves(sown.get("moe_stats", {}))
                overflow = (sum(jnp.mean(s) for s in stats) / len(stats)
                            if stats else jnp.float32(-1.0))  # -1 = no MoE
                return loss, overflow

            (loss, overflow), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(variables["params"])
            updates, opt_next = tx.update(grads, opt_state, variables["params"])
            params = optax.apply_updates(variables["params"], updates)
            return {**variables, "params": params}, opt_next, loss, overflow

        batch_sharding = NamedSharding(self.mesh, self.batch_spec)
        replicated = NamedSharding(self.mesh, P())
        return jax.jit(
            step,
            in_shardings=(self._param_shardings, self._opt_shardings, batch_sharding, replicated),
            out_shardings=(self._param_shardings, self._opt_shardings, replicated, replicated),
            donate_argnums=(0, 1) if self.donate else (),
        )

    def train_step(self, batch: np.ndarray, rng: jax.Array) -> float:
        """One optimizer step on a global batch; returns the (device) loss.
        MoE models additionally leave their expert-capacity overflow rate in
        ``last_moe_overflow`` (a device scalar; -1 sentinel for dense)."""
        if self.params is None:
            raise RuntimeError("call init() before train_step()")
        if self._step_fn is None:
            self._step_fn = self._build_step()
            log.info("compiling SPMD step: mesh=%s batch=%s",
                     dict(self.mesh.shape), np.shape(batch))
        with jax.set_mesh(self.mesh):
            self.params, self.opt_state, loss, self.last_moe_overflow = self._step_fn(
                self.params, self.opt_state, jnp.asarray(batch), rng
            )
        return loss

    # --- eval ---

    def eval_metrics(self, batch: np.ndarray, pad_id: int = 0) -> Tuple[float, float]:
        """(eval loss, next-token top-1 accuracy) over non-pad positions — the
        SPMD engine's accuracy-style validation (K-AVG parity: the reference
        validates accuracy every epoch, ml/pkg/train/job.go:339-362)."""
        x = jnp.asarray(batch)
        if self.input_transform is not None:
            x = self.input_transform(x)
        with jax.set_mesh(self.mesh):
            tokens = jnp.asarray(batch)
            if self.logits_chunk is not None:
                hidden = self.module.apply(self.params, x, train=False,
                                           return_hidden=True)
                kernel = nn.meta.unbox(self.params["params"])["lm_head"]["kernel"]
                l, a = chunked_lm_loss(hidden, kernel.astype(hidden.dtype),
                                       tokens, pad_id=pad_id,
                                       chunk=self.logits_chunk, with_acc=True)
                return float(l), float(a)
            logits = self.module.apply(self.params, x, train=False)
            logits = jnp.asarray(logits, jnp.float32)
            loss = float(self.loss_fn(logits, tokens))
            targets = tokens[:, 1:]
            mask = (targets != pad_id).astype(jnp.float32)
            correct = (jnp.argmax(logits[:, :-1], axis=-1) == targets).astype(jnp.float32)
            acc = float((correct * mask).sum() / jnp.maximum(mask.sum(), 1.0))
        return loss, acc

    def eval_loss(self, batch: np.ndarray) -> float:
        return self.eval_metrics(batch)[0]
