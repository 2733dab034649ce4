"""Builder for the ``longcat_flash`` family (``"builder": "longcat_flash"`` in
a configuration's file; the language model of LongCat-Flash-Omni): the
function a user would deploy for it, its weights made from the seed, and
their places in the program's parameter tree.

As ``models/glm_moe_lite.py``: the weights are the benchmark's, made on the
device from the seed, in the reference's layout (the attention's and the
dense SwiGLUs' arrays stacked over the ``2 x num_layers`` sub-layers, block
b's at rows 2b and 2b + 1; the router's and the held experts' over the
blocks), rounded once to ``param_dtype``. ``assumed.init`` in the
configuration's file says how they are scaled.

The configuration holds one chip's share of each layer's experts:
``n_routed_experts`` is the count HELD (``reduced``), ``published
.n_routed_experts`` what the router scores, ``experts_held_from`` the first
held expert's router output."""

from __future__ import annotations

import dataclasses
import importlib.util

from .. import spec

FUNCTION_NAME = "bench-longcat-flash"


def _program_has_the_family() -> bool:
    if importlib.util.find_spec("kubeml_tpu.models.mla") is None:
        return False
    from kubeml_tpu.models import experts, gpt

    return (hasattr(gpt, "ShortcutBlock") and "zero_expert_num" in {
        f.name for f in dataclasses.fields(experts.ExpertsConfig)})


# a program from before PR 41 has no shortcut block, no share of a layer's
# experts and no identity experts: say so and exit at once, before any
# weights are made (a SpecError exits non-zero, no result)
if not _program_has_the_family():
    raise spec.SpecError(
        "this program has no shortcut-connected expert layer over a share "
        "of the experts (kubeml_tpu/models/gpt.py ShortcutBlock, "
        "models/experts.py ExpertsConfig.zero_expert_num): it cannot run a "
        "longcat_flash configuration")

from . import glm_moe_lite as glm  # noqa: E402


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published stack (RMSNorm, double layers of two latent attentions with
    their scale factors, two dense SwiGLUs and a shortcut-connected expert
    layer: softmax router, identity experts, this chip's share)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    for key, want in (("attention_bias", False), ("attention_method", "MLA"),
                      ("zero_expert_type", "identity"),
                      ("mla_scale_q_lora", True),
                      ("mla_scale_kv_lora", True)):
        assert cfg[key] == want, f"longcat_flash builder: {key} = {cfg[key]!r}"
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
            embed_dim={cfg["hidden_size"]}, depth={cfg["num_layers"]},
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
                mla_scale_q_lora=True, mla_scale_kv_lora=True),
            mlp="shortcut", mlp_dim={cfg["ffn_hidden_size"]},
            experts=ExpertsConfig(
                n_routed_experts={cfg["published"]["n_routed_experts"]},
                num_experts_per_tok={cfg["moe_topk"]},
                moe_intermediate_size={cfg["expert_ffn_hidden_size"]},
                routed_scaling_factor={float(cfg["routed_scaling_factor"])!r},
                scoring_func="softmax", norm_topk_prob=False,
                n_shared_experts=0,
                zero_expert_num={cfg["zero_expert_num"]},
                held=({cfg["experts_held_from"]}, {cfg["n_routed_experts"]})))

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


# kind -> (mean, std); 'kernel', 'up' and 'router' by the shape (_spread)
_KINDS = {"embed": (0.0, 1.0), "scale": (1.0, 0.1), "select": (0.0, 1e-4)}
# the router's logits have this spread over a token's 768 outputs: a peaked
# softmax, as a trained router's is (assumed.init says what it gives)
ROUTER_LOGIT_STD = 3.0


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind). kind: 'embed' normal(0, 1); 'kernel' normal(0,
    1 / sqrt(fan_in)) with fan_in the axis before the last; 'up' (the two
    latent up-projections) normal(0, 1 / sqrt(hidden_size)): the fan-in the
    published scale factors on their inputs restore; 'router' normal(0,
    ROUTER_LOGIT_STD / sqrt(fan_in)): logits that spread by 3, a peaked
    softmax; 'scale' 1 + normal(0, 0.1); 'select' normal(0, 1e-4) (the
    selection bias). The configuration's ``assumed.init`` says why each."""
    c = cfg
    n, e, v, h = (c["num_layers"], c["hidden_size"], c["vocab_size"],
                  c["num_attention_heads"])
    s = 2 * n
    rq, dc = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    i, w = c["ffn_hidden_size"], c["expert_ffn_hidden_size"]
    held = c["n_routed_experts"]
    outputs = c["published"]["n_routed_experts"] + c["zero_expert_num"]
    return {
        "wte": ((v, e), "embed"), "lnf_g": ((e,), "scale"),
        "lm_head": ((e, v), "kernel"),
        "ln1_g": ((s, e), "scale"), "w_dq": ((s, e, rq), "kernel"),
        "q_norm_g": ((s, rq), "scale"),
        "w_uq": ((s, rq, h * (dn + dr)), "up"),
        "w_dkv": ((s, e, dc + dr), "kernel"),
        "kv_norm_g": ((s, dc), "scale"),
        "w_ukv": ((s, dc, h * (dn + dv)), "up"),
        "wo": ((s, h * dv, e), "kernel"), "ln2_g": ((s, e), "scale"),
        "d_gate": ((s, e, i), "kernel"), "d_up": ((s, e, i), "kernel"),
        "d_down": ((s, i, e), "kernel"),
        "w_r": ((n, e, outputs), "router"), "b_r": ((n, outputs), "select"),
        "e_gate": ((n, held, e, w), "kernel"),
        "e_up": ((n, held, e, w), "kernel"),
        "e_down": ((n, held, w, e), "kernel"),
    }


def _spread(cfg: dict, kind: str, shape: tuple) -> tuple:
    if kind == "kernel":
        return 0.0, shape[-2] ** -0.5
    if kind == "up":
        return 0.0, cfg["hidden_size"] ** -0.5
    if kind == "router":
        return 0.0, ROUTER_LOGIT_STD * shape[-2] ** -0.5
    return _KINDS[kind]


def _draw(shape: tuple, mean: float, std: float, key, host, dtype: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    # one drawing program per distinct size, the chip's own bit generator,
    # small arrays on the host: the Falcon-H1 builder's (it says why)
    from .falcon_h1 import _ON_DEVICE, _normal_fn

    size = int(np.prod(shape))
    if size < _ON_DEVICE:
        return jnp.asarray(
            mean + std * host.standard_normal(shape, np.float32), dtype)
    # a stack is drawn a leading row at a time: the float32 normals of the
    # dense SwiGLUs' are 2.4 GB whole
    if len(shape) > 2:
        return jnp.stack([
            _normal_fn(size // shape[0], dtype)(
                jax.random.fold_in(key, j), mean, std
            ).reshape(shape[1:]) for j in range(shape[0])])
    return _normal_fn(size, dtype)(key, mean, std).reshape(shape)


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names; and
    what the reference needs beside them (``rope_theta``, ``routed_scale``,
    and ``topk_slots`` / ``zero_slots`` / ``first_slots`` whose lengths are
    ``moe_topk`` / ``zero_expert_num`` / ``experts_held_from``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 41])
    slots = lambda n: jnp.zeros((n,), jnp.float32)
    out = {"rope_theta": jnp.asarray(cfg["rope_theta"], jnp.float32),
           "routed_scale": jnp.asarray(cfg["routed_scaling_factor"],
                                       jnp.float32),
           "topk_slots": slots(cfg["moe_topk"]),
           "zero_slots": slots(cfg["zero_expert_num"]),
           "first_slots": slots(cfg["experts_held_from"])}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        out[name] = _draw(shape, *_spread(cfg, kind, shape),
                          jax.random.fold_in(key, j), host,
                          cfg["param_dtype"])
    return out


_SUB = {  # reference name -> path under params/block_<b>/sub_<j>/
    **glm._ATTN, **glm._DENSE}
_EXPERTS = {"w_r": "router", "b_r": "router_bias", "e_gate": "w_gate",
            "e_up": "w_up", "e_down": "w_down"}


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, numpy array), leaf by leaf,
    one sub-layer's array fetched from the device at a time, in the type
    the weights are held in (``param_dtype``)."""
    import numpy as np

    host = np.asarray
    yield "params/token_embed/embedding", host(weights["wte"])
    yield "params/ln_f/scale", host(weights["lnf_g"])
    yield "params/lm_head/kernel", host(weights["lm_head"])
    h, dc = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    for b in range(cfg["num_layers"]):
        for j in (0, 1):
            base, i = f"params/block_{b}/sub_{j}", 2 * b + j
            for name, path in _SUB.items():
                yield f"{base}/{path}", host(weights[name][i])
            # the program holds W_ukv by head: [dc, H, dn + dv]
            yield (f"{base}/attn/kv_up",
                   host(weights["w_ukv"][i]).reshape(dc, h, -1))
        for name, path in _EXPERTS.items():
            yield (f"params/block_{b}/sub_0/experts/{path}",
                   host(weights[name][b]))
