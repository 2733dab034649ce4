"""KubeModel — the user-facing model API.

The reference's ``KubeModel`` is an imperative torch ABC: users override
``init/train/validate/infer`` and the platform drives them per task
(reference: python/kubeml/kubeml/network.py:29-52, 463-476). The JAX re-design
keeps the same "write your model, never touch devices or distribution" promise but
with a *functional* contract the engine can ``jit``/``shard_map``:

* ``build()`` returns a Flax module (required);
* ``per_sample_loss``/``per_sample_correct`` act on logits and return per-sample
  vectors — the engine applies validity masks and reductions, which is how padded
  lockstep batches and partial-worker failures stay out of user code;
* ``configure_optimizers()`` returns an optax transformation (reference
  network.py:463-467), re-initialized at every sync round exactly like the
  reference resets optimizer state each iteration (network.py:121-128);
* mutable collections (e.g. BatchNorm ``batch_stats``) live alongside params in
  one ``variables`` pytree and are averaged at sync like the reference averages
  the full state_dict including BN counters (ml/pkg/model/parallelSGD.go:26-54).

User code never imports jax.sharding, never sees the mesh, and never calls a
collective — distribution is entirely the platform's job.
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..data.dataset import KubeDataset


class KubeModel(ABC):
    """Subclass, implement :meth:`build`, optionally override the hooks::

        class KubeLeNet(KubeModel):
            def __init__(self):
                super().__init__(MnistDataset())

            def build(self):
                return LeNet(num_classes=10)

            def configure_optimizers(self):
                return optax.sgd(self.lr, momentum=0.9)
    """

    # Set True in a subclass whose configure_optimizers reads self.epoch (e.g.
    # epoch-based lr decay, reference function_resnet34.py:52-63): the engine
    # then feeds the current epoch to the schedule. Schedules written with jnp
    # ops compile ONCE (lr/epoch are runtime scalars in the program); Python
    # control flow on self.epoch (int(), if-chains) falls back to one compile
    # per (lr, epoch). Left False (default), the schedule never sees the epoch.
    epoch_in_schedule: bool = False

    def __init__(self, dataset: KubeDataset):
        self._dataset = dataset
        self._module = None
        # set by the SPMD engine before build() so mesh-aware modules can read
        # it (e.g. CausalTransformer(mesh=self.mesh)); None under K-AVG
        self.mesh = None
        # per-invocation parameters, set by the runtime before any task runs
        # (the reference reads them from request args each call, network.py:91-97)
        self.lr: float = 0.01
        self.batch_size: int = 64
        self.epoch: int = 0
        self.k: int = -1
        self.task: str = ""

    # --- wiring ---

    @property
    def dataset(self) -> KubeDataset:
        return self._dataset

    @property
    def module(self):
        if self._module is None:
            self._module = self.build()
        return self._module

    def rebind_mesh(self, mesh) -> None:
        """Point the model at a new mesh and drop the cached module so the
        next ``module`` access re-runs ``build()`` against it. The SPMD
        engine calls this on elastic re-mesh — a module that captured the old
        mesh (sp shard_map closures, pipeline sharding constraints) would
        otherwise issue collectives sized for devices it no longer has."""
        self.mesh = mesh
        self._module = None

    def _set_params(self, *, lr: float, batch_size: int, epoch: int, k: int, task: str) -> None:
        self.lr = lr
        self.batch_size = batch_size
        self.epoch = epoch
        self.k = k
        self.task = task

    # --- required user surface ---

    @abstractmethod
    def build(self):
        """Return the Flax module for this model."""

    # --- overridable hooks (all jax-pure: traced under jit) ---

    def init(self, rng: jax.Array, sample_x: jnp.ndarray) -> Dict[str, Any]:
        """Initialize the full variables pytree ({'params': ..., maybe
        'batch_stats': ...}) from one sample batch."""
        return self.module.init(rng, sample_x, train=False)

    def forward(
        self,
        variables: Dict[str, Any],
        x: jnp.ndarray,
        train: bool,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """Run the module; returns (logits, updated mutable state). Mutable
        collections (everything except 'params') are updated only when training."""
        mutable = [k for k in variables if k != "params"]
        rngs = {"dropout": rng} if (train and rng is not None) else None
        if train and mutable:
            logits, new_state = self.module.apply(
                variables, x, train=True, mutable=mutable, rngs=rngs
            )
            return logits, dict(new_state)
        logits = self.module.apply(variables, x, train=train, rngs=rngs)
        return logits, {}

    def preprocess(self, x: jnp.ndarray) -> jnp.ndarray:
        """Device-side input preprocessing, traced into the jitted step (default
        identity). Override to run normalization on device so the host can
        stage quantized inputs — e.g. stage uint8 images and scale here::

            def preprocess(self, x):
                return x.astype(jnp.bfloat16) / 127.5 - 1.0

        which cuts host->HBM bytes 4x vs f32 (2x vs bf16) — the standard TPU
        input-pipeline pattern. Host-side (numpy) augmentation belongs in
        ``KubeDataset.transform``; this hook is for the final cast/scale."""
        return x

    def per_sample_loss(self, logits: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Per-sample losses [B]; default integer-label softmax cross-entropy."""
        return optax.softmax_cross_entropy_with_integer_labels(logits, y)

    def per_sample_correct(self, logits: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Per-sample 0/1 correctness [B] for accuracy; default argmax match."""
        return (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)

    def configure_optimizers(self) -> optax.GradientTransformation:
        """Optimizer; default plain SGD at the job's lr (reference default is the
        user's choice; examples use SGD with momentum)."""
        return optax.sgd(self.lr)

    def infer(self, variables: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
        """Prediction for raw inference payloads; default class ids."""
        logits, _ = self.forward(variables, x, train=False)
        return jnp.argmax(logits, axis=-1)

    def serving_remap(self):
        """None (default), or a restore-time leaf remap from this model's
        TRAINING checkpoint layout to its serving layout (the ``remap``
        contract of ``storage.sharded_checkpoint``: ``stored_path -> None |
        [(target_path, index_prefix)]``).

        Override when ``build()`` returns a different module shape under a
        training mesh than for serving — the canonical case is a function
        whose build() trains ``PipelinedCausalLM`` (stage-STACKED params)
        when ``self.mesh`` has pp > 1 but serves the flat
        ``CausalTransformer``; return
        ``models.gpt_pipeline.flat_serving_remap(stages, layers_per_stage)``
        there. The platform applies it when loading finished checkpoints for
        /infer and /generate."""
        return None


def make_synthetic_model(module, dataset_name: str = "synthetic",
                         uint8_inputs: bool = False):
    """Wrap a Flax module in a KubeModel over a placeholder dataset (the
    caller feeds data directly, so the dataset is never attached).

    ``uint8_inputs=True`` installs the device-side dequantize preprocess
    (uint8 [0,255] -> bf16 [-1,1]) so the host stages quantized images — 4x
    fewer host->HBM bytes than f32."""

    class _SyntheticDataset(KubeDataset):
        def __init__(self):
            super().__init__(dataset_name)

    class _SyntheticModel(KubeModel):
        def __init__(self):
            super().__init__(_SyntheticDataset())

        def build(self):
            return module

        def configure_optimizers(self):
            return optax.sgd(self.lr, momentum=0.9)

        if uint8_inputs:
            def preprocess(self, x):
                return x.astype(jnp.bfloat16) / 127.5 - 1.0

    return _SyntheticModel()
