"""Builder for the ``glm4_moe_lite`` family (``"builder": "glm_moe_lite"`` in
a configuration's file; GLM-4.7-Flash): the function a user would deploy for
it, its weights made from the seed, and their places in the program's
parameter tree.

As ``models/falcon_h1.py``: the weights are the benchmark's, made on the
device from the seed (one program a distinct size, the chip's own bit
generator), in the reference's layout (per-layer arrays stacked along a
leading layer axis: the attention's over all layers, the dense layers'
``d_*`` and the expert layers' ``e_*`` / ``s_*`` / router over their own),
rounded once to ``param_dtype``. ``assumed.init`` in the configuration's
file says how they are scaled."""

from __future__ import annotations

import importlib.util

from .. import spec

FUNCTION_NAME = "bench-glm-moe-lite"

# a program from before PR 32 has no latent attention: say so and exit at
# once, before any weights are made (a SpecError exits non-zero, no result)
if importlib.util.find_spec("kubeml_tpu.models.mla") is None:
    raise spec.SpecError(
        "this program has no latent attention (kubeml_tpu/models/mla.py): "
        "it cannot run a glm4_moe_lite configuration")


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published stack (RMSNorm, latent attention with rotary positions, one
    dense SwiGLU layer, then routed experts with a shared one)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("rope_scaling", None), ("tie_word_embeddings", False),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("num_nextn_predict_layers", 0),
                      ("norm_topk_prob", True), ("n_shared_experts", 1)):
        assert cfg[key] == want, f"glm_moe_lite builder: {key} = {cfg[key]!r}"
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.experts import ExpertsConfig
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.models.mla import MLAConfig
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
                norm_eps={cfg["rms_norm_eps"]!r}),
            mlp="experts", mlp_dim={cfg["intermediate_size"]},
            dense_layers={cfg["first_k_dense_replace"]},
            experts=ExpertsConfig(
                n_routed_experts={cfg["n_routed_experts"]},
                num_experts_per_tok={cfg["num_experts_per_tok"]},
                moe_intermediate_size={cfg["moe_intermediate_size"]},
                routed_scaling_factor={float(cfg["routed_scaling_factor"])!r}))

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind). kind: 'embed' normal(0, 1); 'kernel' normal(0,
    1 / sqrt(fan_in)) with fan_in the axis before the last; 'scale' 1 +
    normal(0, 0.1); 'select' normal(0, 0.01) (the selection bias: the
    configuration's ``assumed.init`` says why so small)."""
    c = cfg
    n, e, v, h = (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"],
                  c["num_attention_heads"])
    nd = c["first_k_dense_replace"]
    ne = n - nd
    rq, dc = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    i, g, w = (c["intermediate_size"], c["n_routed_experts"],
               c["moe_intermediate_size"])
    return {
        "wte": ((v, e), "embed"), "lnf_g": ((e,), "scale"),
        "lm_head": ((e, v), "kernel"),
        "ln1_g": ((n, e), "scale"), "w_dq": ((n, e, rq), "kernel"),
        "q_norm_g": ((n, rq), "scale"),
        "w_uq": ((n, rq, h * (dn + dr)), "kernel"),
        "w_dkv": ((n, e, dc + dr), "kernel"),
        "kv_norm_g": ((n, dc), "scale"),
        "w_ukv": ((n, dc, h * (dn + dv)), "kernel"),
        "wo": ((n, h * dv, e), "kernel"), "ln2_g": ((n, e), "scale"),
        "d_gate": ((nd, e, i), "kernel"), "d_up": ((nd, e, i), "kernel"),
        "d_down": ((nd, i, e), "kernel"),
        "w_r": ((ne, e, g), "kernel"), "b_r": ((ne, g), "select"),
        "e_gate": ((ne, g, e, w), "kernel"), "e_up": ((ne, g, e, w), "kernel"),
        "e_down": ((ne, g, w, e), "kernel"),
        "s_gate": ((ne, e, w), "kernel"), "s_up": ((ne, e, w), "kernel"),
        "s_down": ((ne, w, e), "kernel"),
    }


def _draw(kind: str, shape: tuple, key, host, dtype: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    # one drawing program per distinct size, the chip's own bit generator,
    # small arrays on the host: the Falcon-H1 builder's (it says why)
    from .falcon_h1 import _ON_DEVICE, _normal_fn

    size = int(np.prod(shape))
    mean, std = {"embed": (0.0, 1.0), "scale": (1.0, 0.1),
                 "select": (0.0, 0.01),
                 "kernel": (0.0, shape[-2] ** -0.5 if len(shape) > 1
                            else 1.0)}[kind]
    if size < _ON_DEVICE:
        z = mean + std * host.standard_normal(shape, np.float32)
        return jnp.asarray(z, dtype)
    # an expert stack is drawn a layer at a time: its float32 normals are
    # 2.4 GB a layer before they are rounded
    if len(shape) == 4:
        return jnp.stack([
            _normal_fn(size // shape[0], dtype)(
                jax.random.fold_in(key, j), mean, std
            ).reshape(shape[1:]) for j in range(shape[0])])
    return _normal_fn(size, dtype)(key, mean, std).reshape(shape)


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names; and
    what the reference needs beside them (``rope_theta``, ``routed_scale``,
    ``topk_slots`` whose length is ``num_experts_per_tok``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 32])
    out = {"rope_theta": jnp.asarray(cfg["rope_theta"], jnp.float32),
           "routed_scale": jnp.asarray(cfg["routed_scaling_factor"],
                                       jnp.float32),
           "topk_slots": jnp.zeros((cfg["num_experts_per_tok"],),
                                   jnp.float32)}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        out[name] = _draw(kind, shape, jax.random.fold_in(key, j), host,
                          cfg["param_dtype"])
    return out


_ATTN = {  # reference name -> path under params/block_<i>/
    "ln1_g": "ln1/scale", "ln2_g": "ln2/scale",
    "w_dq": "attn/q_down/kernel", "q_norm_g": "attn/q_norm/scale",
    "w_uq": "attn/q_up/kernel", "w_dkv": "attn/kv_down/kernel",
    "kv_norm_g": "attn/kv_norm/scale", "wo": "attn/proj/kernel",
}
_DENSE = {"d_gate": "mlp_gate/kernel", "d_up": "mlp_up/kernel",
          "d_down": "mlp_out/kernel"}
_EXPERTS = {"w_r": "experts/router", "b_r": "experts/router_bias",
            "e_gate": "experts/w_gate", "e_up": "experts/w_up",
            "e_down": "experts/w_down",
            "s_gate": "experts/shared_gate/kernel",
            "s_up": "experts/shared_up/kernel",
            "s_down": "experts/shared_out/kernel"}


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, numpy array), leaf by leaf,
    one layer's array fetched from the device at a time, in the type the
    weights are held in (``param_dtype``): a bfloat16 leaf goes into the
    checkpoint as its two bytes a value, which the program's store reads
    back as bfloat16 (storage/sharded_checkpoint.py), so weights that are
    served in bfloat16 are not written, read and cast as float32 first."""
    import numpy as np

    host = np.asarray
    yield "params/token_embed/embedding", host(weights["wte"])
    yield "params/ln_f/scale", host(weights["lnf_g"])
    yield "params/lm_head/kernel", host(weights["lm_head"])
    h, dc = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nd = cfg["first_k_dense_replace"]
    for i in range(cfg["num_hidden_layers"]):
        for name, path in _ATTN.items():
            yield f"params/block_{i}/{path}", host(weights[name][i])
        # the program holds W_ukv by head: [dc, H, dn + dv]
        yield (f"params/block_{i}/attn/kv_up",
               host(weights["w_ukv"][i]).reshape(dc, h, -1))
        table, j = (_DENSE, i) if i < nd else (_EXPERTS, i - nd)
        for name, path in table.items():
            yield f"params/block_{i}/{path}", host(weights[name][j])
