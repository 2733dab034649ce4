"""Builder for the ``xing4_0`` family (``"builder": "xing4"`` in a
configuration's file; Xing4.0-29B-A4B): the function a user would deploy for
it, its weights made from the seed, and their places in the program's
parameter tree.

The block is GLM-4.7-Flash's (``models/glm_moe_lite.py``: latent attention, a
dense SwiGLU layer, then routed experts with a shared one), so the arrays,
their drawing and their paths are that builder's; what this family adds is
the residual path (two sub-layers a layer, each with ``phi`` [n E, 2n + n n],
``alpha`` [3] and ``bias`` [2n + n n]: ``hc1_*``, ``hc2_*``) and YaRN's
``rope_scaling``. ``assumed.init`` in the configuration's file says how the
residual maps are drawn and why."""

from __future__ import annotations

import importlib.util

from .. import spec

FUNCTION_NAME = "bench-xing4"

# a program from before PR 37 has no residual path of several streams: say
# so and exit at once, before any weights are made (a SpecError exits
# non-zero, no result)
if importlib.util.find_spec("kubeml_tpu.ops.hyper_connection") is None:
    raise spec.SpecError(
        "this program has no hyper-connections "
        "(kubeml_tpu/ops/hyper_connection.py): it cannot run a xing4_0 "
        "configuration")

from . import glm_moe_lite as glm  # noqa: E402

_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow", "mscale", "mscale_all_dim")


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published stack (RMSNorm, latent attention with YaRN rotary, dense SwiGLU
    layers, then routed experts with a shared one, on ``hc_mult``
    hyper-connected streams)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("topk_method", "noaux_tc"), ("scoring_func", "sigmoid"),
                      ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0),
                      ("norm_topk_prob", True), ("n_shared_experts", 1)):
        assert cfg[key] == want, f"xing4 builder: {key} = {cfg[key]!r}"
    yarn = cfg["rope_scaling"]
    assert yarn["type"] == "yarn", f"xing4 builder: rope_scaling {yarn!r}"
    assert cfg["mhc_h_res_clamp_min"] == -cfg["mhc_h_res_clamp_max"]
    scaling = ", ".join(f"{k}={yarn[k]!r}" for k in _YARN_KEYS)
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.experts import ExpertsConfig
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.models.mla import MLAConfig
from kubeml_tpu.ops.rotary import YarnScaling
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
            embed_dim={cfg["hidden_size"]}, depth={cfg["num_hidden_layers"]},
            num_heads={cfg["num_attention_heads"]}, dtype={dtype},
            norm="rmsnorm", ln_eps={cfg["rms_norm_eps"]!r},
            pos="rope", rope_theta={float(cfg["rope_theta"])!r},
            mla=MLAConfig(
                q_lora_rank={cfg["q_lora_rank"]},
                kv_lora_rank={cfg["kv_lora_rank"]},
                qk_nope_head_dim={cfg["qk_nope_head_dim"]},
                qk_rope_head_dim={cfg["qk_rope_head_dim"]},
                v_head_dim={cfg["v_head_dim"]},
                norm_eps={cfg["rms_norm_eps"]!r},
                rope_scaling=YarnScaling({scaling})),
            mlp="experts", mlp_dim={cfg["intermediate_size"]},
            dense_layers={cfg["first_k_dense_replace"]},
            experts=ExpertsConfig(
                n_routed_experts={cfg["n_routed_experts"]},
                num_experts_per_tok={cfg["num_experts_per_tok"]},
                moe_intermediate_size={cfg["moe_intermediate_size"]},
                routed_scaling_factor={float(cfg["routed_scaling_factor"])!r}),
            hc_mult={cfg["hc_mult"]},
            hc_sinkhorn_iters={cfg["hc_sinkhorn_iters"]},
            hc_eps={float(cfg["hc_eps"])!r},
            hc_clamp={float(cfg["mhc_h_res_clamp_max"])!r})

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind): the ``glm_moe_lite`` builder's arrays and
    kinds, and the two sub-layers' residual maps a layer: ``phi`` a
    'kernel' (normal(0, 1 / sqrt(n E)): the normed streams' projection is
    normal(0, 1) a column), ``alpha`` a 'scale' (1 + normal(0, 0.1)),
    ``bias`` a 'bias' (normal(0, 0.5))."""
    layers, e, n = (cfg["num_hidden_layers"], cfg["hidden_size"],
                    cfg["hc_mult"])
    c = 2 * n + n * n
    out = glm.shapes(cfg)
    for k in (1, 2):
        out[f"hc{k}_phi"] = ((layers, n * e, c), "kernel")
        out[f"hc{k}_alpha"] = ((layers, 3), "scale")
        out[f"hc{k}_bias"] = ((layers, c), "bias")
    return out


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names; and
    what the reference needs beside them (``rope_theta``, ``routed_scale``,
    ``yarn``, ``hc_eps``, ``hc_clamp``, and ``topk_slots`` /
    ``sinkhorn_slots`` whose lengths are ``num_experts_per_tok`` /
    ``hc_sinkhorn_iters``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 37])
    f = lambda x: jnp.asarray(x, jnp.float32)
    yarn = cfg["rope_scaling"]
    out = {"rope_theta": f(cfg["rope_theta"]),
           "routed_scale": f(cfg["routed_scaling_factor"]),
           "topk_slots": jnp.zeros((cfg["num_experts_per_tok"],),
                                   jnp.float32),
           "yarn": f([yarn[k] for k in ("factor", "beta_fast", "beta_slow",
                                        "mscale", "mscale_all_dim",
                                        "original_max_position_embeddings")]),
           "hc_eps": f(cfg["hc_eps"]),
           "hc_clamp": f(cfg["mhc_h_res_clamp_max"]),
           "sinkhorn_slots": jnp.zeros((cfg["hc_sinkhorn_iters"],),
                                       jnp.float32)}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        if kind == "bias":
            out[name] = jnp.asarray(
                0.5 * host.standard_normal(shape, np.float32),
                cfg["param_dtype"])
        else:
            out[name] = glm._draw(kind, shape, jax.random.fold_in(key, j),
                                  host, cfg["param_dtype"])
    return out


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, numpy array), leaf by leaf:
    the ``glm_moe_lite`` builder's, and each layer's residual maps."""
    import numpy as np

    yield from glm.program_leaves(cfg, weights)
    for i in range(cfg["num_hidden_layers"]):
        for k in (1, 2):
            for part in ("phi", "alpha", "bias"):
                yield (f"params/block_{i}/hc{k}_{part}",
                       np.asarray(weights[f"hc{k}_{part}"][i]))
