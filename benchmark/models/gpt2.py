"""Builder for the GPT-2 family (``"builder": "gpt2"`` in a configuration's
file): the function a user would deploy for it, its weights made from the
seed, and their places in the program's parameter tree.

The weights are the benchmark's, not the program's: one jitted call on the
device makes them, in the reference's own layout (every per-layer array
stacked along a leading layer axis), in float32, the type they are served
in. The program gets them as a finished job's final checkpoint; the
reference gets the same call's result again after the program is gone."""

from __future__ import annotations

import functools

FUNCTION_NAME = "bench-gpt2"


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer at the
    configuration's sizes (GPT-2's biases and LayerNorm epsilon)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    assert cfg["n_inner"] == 4 * cfg["n_embd"], "CausalTransformer: mlp_ratio"
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.runtime.model import KubeModel


class Tokens(KubeDataset):
    def __init__(self):
        super().__init__("bench-tokens")


class Model(KubeModel):
    def __init__(self):
        super().__init__(Tokens())

    def build(self):
        return CausalTransformer(
            vocab_size={cfg["vocab_size"]}, max_len={cfg["n_positions"]},
            embed_dim={cfg["n_embd"]}, depth={cfg["n_layer"]},
            num_heads={cfg["n_head"]}, mlp_ratio=4, dtype={dtype},
            attn_bias=True, ln_eps={cfg["layer_norm_epsilon"]!r})

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind). A leading n_layer axis marks a per-layer
    array. kind: 'embed' normal(0.02), 'kernel' normal(1/sqrt(fan_in)),
    'bias' normal(0.02), 'scale' 1 + normal(0.1)."""
    n, e, i, v, p = (cfg["n_layer"], cfg["n_embd"], cfg["n_inner"],
                     cfg["vocab_size"], cfg["n_positions"])
    return {
        "wte": ((v, e), "embed"), "wpe": ((p, e), "embed"),
        "ln1_g": ((n, e), "scale"), "ln1_b": ((n, e), "bias"),
        "wq": ((n, e, e), "kernel"), "bq": ((n, e), "bias"),
        "wk": ((n, e, e), "kernel"), "bk": ((n, e), "bias"),
        "wv": ((n, e, e), "kernel"), "bv": ((n, e), "bias"),
        "wo": ((n, e, e), "kernel"), "bo": ((n, e), "bias"),
        "ln2_g": ((n, e), "scale"), "ln2_b": ((n, e), "bias"),
        "w_in": ((n, e, i), "kernel"), "b_in": ((n, i), "bias"),
        "w_out": ((n, i, e), "kernel"), "b_out": ((n, e), "bias"),
        "lnf_g": ((e,), "scale"), "lnf_b": ((e,), "bias"),
        "lm_head": ((e, v), "kernel"),
    }


@functools.lru_cache(maxsize=None)
def _init_fn(shape_items: tuple):
    import jax
    import jax.numpy as jnp

    def init(key):
        out = {}
        for j, (name, (shape, kind)) in enumerate(shape_items):
            z = jax.random.normal(jax.random.fold_in(key, j), shape,
                                  jnp.float32)
            if kind == "kernel":
                z = z * (shape[-2] ** -0.5)
            elif kind == "scale":
                z = 1.0 + 0.1 * z
            else:
                z = 0.02 * z
            out[name] = z
        return out

    return jax.jit(init)


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, in one jitted call."""
    import jax

    key = jax.random.PRNGKey(int(seed) % (2 ** 63))
    return _init_fn(tuple(sorted(shapes(cfg).items())))(key)


_BLOCK = {  # reference name -> path under params/block_<i>/
    "ln1_g": "ln1/scale", "ln1_b": "ln1/bias",
    "wq": "attn/query/kernel", "bq": "attn/query/bias",
    "wk": "attn/key/kernel", "bk": "attn/key/bias",
    "wv": "attn/value/kernel", "bv": "attn/value/bias",
    "wo": "attn/proj/kernel", "bo": "attn/proj/bias",
    "ln2_g": "ln2/scale", "ln2_b": "ln2/bias",
    "w_in": "mlp_in/kernel", "b_in": "mlp_in/bias",
    "w_out": "mlp_out/kernel", "b_out": "mlp_out/bias",
}


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, numpy array), leaf by leaf,
    fetching one stacked array from the device at a time."""
    import numpy as np

    yield "params/token_embed/embedding", np.asarray(weights["wte"])
    yield "params/pos_embed", np.asarray(weights["wpe"])[None]
    yield "params/ln_f/scale", np.asarray(weights["lnf_g"])
    yield "params/ln_f/bias", np.asarray(weights["lnf_b"])
    yield "params/lm_head/kernel", np.asarray(weights["lm_head"])
    for name, path in _BLOCK.items():
        stacked = np.asarray(weights[name])
        for i in range(cfg["n_layer"]):
            yield f"params/block_{i}/{path}", stacked[i]
