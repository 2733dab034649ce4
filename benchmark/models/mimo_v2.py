"""Builder for the ``mimo_v2`` family (``"builder": "mimo_v2"`` in a
configuration's file; MiMo-V2-Flash): the function a user would deploy for
it, its weights made from the seed, and their places in the program's
parameter tree.

As ``models/longcat_flash.py``: the weights are the benchmark's, made on the
device from the seed, in the reference's layout (what every layer has stacked
over all layers; ``f_*`` over the full-attention layers, ``s_*`` over the
window layers, ``d_*`` over the dense layers, the router's and the held
experts' over the expert layers), rounded once to ``param_dtype``.
``assumed.init`` in the configuration's file says how they are scaled.

The stack is cut to its first ``num_hidden_layers`` layers:
``hybrid_layer_pattern`` and ``moe_layer_freq`` stay whole in the file (48
entries, as published) and the first ``num_hidden_layers`` of each are read.
The configuration holds one chip's share of each layer's experts:
``n_routed_experts`` is the count HELD (``reduced``), ``published
.n_routed_experts`` what the router scores, ``experts_held_from`` the first
held expert's router output."""

from __future__ import annotations

import dataclasses

from .. import spec

FUNCTION_NAME = "bench-mimo-v2"


def _program_has_the_family() -> bool:
    from kubeml_tpu.models import gpt

    return hasattr(gpt, "AttnKind") and "attn_kinds" in {
        f.name for f in dataclasses.fields(gpt.CausalTransformer)}


# a program from before PR 44 has one attention kind a stack and one kind of
# lease: say so and exit at once, before any weights are made (a SpecError
# exits non-zero, no result)
if not _program_has_the_family():
    raise spec.SpecError(
        "this program has no attention kind that differs by layer "
        "(kubeml_tpu/models/gpt.py AttnKind, CausalTransformer.attn_kinds): "
        "it cannot run a mimo_v2 configuration")


def layers(cfg: dict) -> tuple:
    """(window?, experts?) of each layer as run: the first
    ``num_hidden_layers`` entries of the two published patterns."""
    n = cfg["num_hidden_layers"]
    return tuple(zip((bool(k) for k in cfg["hybrid_layer_pattern"][:n]),
                     (bool(k) for k in cfg["moe_layer_freq"][:n])))


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published stack (RMSNorm, full and window attention layers by
    ``hybrid_layer_pattern`` at their own K/V head counts and rotary bases
    over K heads of 192 and V heads of 128, a sink in the window layers,
    one dense SwiGLU layer, then routed experts without a shared one: this
    chip's share)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("n_shared_experts", None), ("scoring_func", "sigmoid"),
                      ("routed_scaling_factor", None),
                      ("add_swa_attention_sink_bias", True),
                      ("add_full_attention_sink_bias", False),
                      ("swa_head_dim", cfg["head_dim"]),
                      ("swa_v_head_dim", cfg["v_head_dim"]),
                      ("swa_num_attention_heads",
                       cfg["num_attention_heads"])):
        assert cfg[key] == want, f"mimo_v2 builder: {key} = {cfg[key]!r}"
    kinds = layers(cfg)
    dense = sum(not moe for _, moe in kinds)
    assert all(moe == (i >= dense) for i, (_, moe) in enumerate(kinds)), (
        "mimo_v2 builder: the dense layers lead the stack")
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.experts import ExpertsConfig
from kubeml_tpu.models.gpt import AttnKind, CausalTransformer
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
            norm="rmsnorm", ln_eps={cfg["layernorm_epsilon"]!r}, pos="rope",
            head_dim={cfg["head_dim"]}, v_head_dim={cfg["v_head_dim"]},
            partial_rotary_factor={cfg["partial_rotary_factor"]!r},
            value_scale={cfg["attention_value_scale"]!r},
            attn_kinds=(
                AttnKind(num_kv_heads={cfg["num_key_value_heads"]},
                         rope_theta={float(cfg["rope_theta"])!r}),
                AttnKind(num_kv_heads={cfg["swa_num_key_value_heads"]},
                         rope_theta={float(cfg["swa_rope_theta"])!r},
                         window={cfg["sliding_window"]}, sink=True)),
            attn_pattern={tuple(int(swa) for swa, _ in kinds)!r},
            mlp="experts", mlp_dim={cfg["intermediate_size"]},
            dense_layers={dense},
            experts=ExpertsConfig(
                n_routed_experts={cfg["published"]["n_routed_experts"]},
                num_experts_per_tok={cfg["num_experts_per_tok"]},
                moe_intermediate_size={cfg["moe_intermediate_size"]},
                routed_scaling_factor=1.0, scoring_func="sigmoid",
                norm_topk_prob=True, n_shared_experts=0,
                held=({cfg["experts_held_from"]}, {cfg["n_routed_experts"]})))

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


# the router's logits have this spread over a token's 256 outputs, and its
# selection bias this one (GLM's); a window layer's sink is drawn about the
# logarithm of the window with this spread (assumed.init says what each
# gives)
ROUTER_LOGIT_STD = 1.0
SELECT_STD = 0.01
SINK_STD = 0.5


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind). kind: 'embed' normal(0, 1); 'kernel' normal(0,
    1 / sqrt(fan_in)) with fan_in the axis before the last; 'router'
    normal(0, ROUTER_LOGIT_STD / sqrt(fan_in)); 'scale' 1 + normal(0, 0.1);
    'select' normal(0, SELECT_STD) (the selection bias); 'sink'
    normal(log(sliding_window), SINK_STD). The configuration's
    ``assumed.init`` says why each."""
    c = cfg
    kinds = layers(c)
    n, e, v, h = (len(kinds), c["hidden_size"], c["vocab_size"],
                  c["num_attention_heads"])
    nw = sum(swa for swa, _ in kinds)
    ne = sum(moe for _, moe in kinds)
    nf, nd = n - nw, n - ne
    dk, dv = c["head_dim"], c["v_head_dim"]
    kf, kw = c["num_key_value_heads"], c["swa_num_key_value_heads"]
    i, w = c["intermediate_size"], c["moe_intermediate_size"]
    held, outputs = c["n_routed_experts"], c["published"]["n_routed_experts"]
    return {
        "wte": ((v, e), "embed"), "lnf_g": ((e,), "scale"),
        "lm_head": ((e, v), "kernel"),
        "ln1_g": ((n, e), "scale"), "ln2_g": ((n, e), "scale"),
        "w_q": ((n, e, h * dk), "kernel"), "wo": ((n, h * dv, e), "kernel"),
        "f_wk": ((nf, e, kf * dk), "kernel"),
        "f_wv": ((nf, e, kf * dv), "kernel"),
        "s_wk": ((nw, e, kw * dk), "kernel"),
        "s_wv": ((nw, e, kw * dv), "kernel"),
        "s_sink": ((nw, h), "sink"),
        "d_gate": ((nd, e, i), "kernel"), "d_up": ((nd, e, i), "kernel"),
        "d_down": ((nd, i, e), "kernel"),
        "w_r": ((ne, e, outputs), "router"), "b_r": ((ne, outputs), "select"),
        "e_gate": ((ne, held, e, w), "kernel"),
        "e_up": ((ne, held, e, w), "kernel"),
        "e_down": ((ne, held, w, e), "kernel"),
    }


def _spread(cfg: dict, kind: str, shape: tuple) -> tuple:
    import math

    if kind == "kernel":
        return 0.0, shape[-2] ** -0.5
    if kind == "router":
        return 0.0, ROUTER_LOGIT_STD * shape[-2] ** -0.5
    if kind == "sink":
        return math.log(cfg["sliding_window"]), SINK_STD
    return {"embed": (0.0, 1.0), "scale": (1.0, 0.1),
            "select": (0.0, SELECT_STD)}[kind]


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names; and
    what the reference needs beside them: the three scalars, the static
    sizes as lengths (``window_slots``, ``rotary_slots``, ``topk_slots``,
    ``first_slots``) and the two patterns as lists of an array a layer whose
    length is 1 where the layer is a window layer / an expert layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # one drawing program per distinct size, stacks a leading row at a time
    from .longcat_flash import _draw

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 44])
    slots = lambda n: jnp.zeros((int(n),), jnp.float32)
    f = lambda x: jnp.asarray(x, jnp.float32)
    dk = cfg["head_dim"]
    out = {"rope_theta": f(cfg["rope_theta"]),
           "swa_rope_theta": f(cfg["swa_rope_theta"]),
           "value_scale": f(cfg["attention_value_scale"]),
           "window_slots": slots(cfg["sliding_window"]),
           "rotary_slots": slots(
               int(dk * cfg["partial_rotary_factor"]) // 2 * 2),
           "topk_slots": slots(cfg["num_experts_per_tok"]),
           "first_slots": slots(cfg["experts_held_from"]),
           "swa_layers": [slots(swa) for swa, _ in layers(cfg)],
           "moe_layers": [slots(moe) for _, moe in layers(cfg)]}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        out[name] = _draw(shape, *_spread(cfg, kind, shape),
                          jax.random.fold_in(key, j), host,
                          cfg["param_dtype"])
    return out


_ALL = {"ln1_g": "ln1/scale", "ln2_g": "ln2/scale",
        "w_q": "attn/query/kernel", "wo": "attn/proj/kernel"}
_FULL = {"f_wk": "attn/key/kernel", "f_wv": "attn/value/kernel"}
_WINDOW = {"s_wk": "attn/key/kernel", "s_wv": "attn/value/kernel",
           "s_sink": "attn/sink"}
_DENSE = {"d_gate": "mlp_gate/kernel", "d_up": "mlp_up/kernel",
          "d_down": "mlp_out/kernel"}
_EXPERTS = {"w_r": "experts/router", "b_r": "experts/router_bias",
            "e_gate": "experts/w_gate", "e_up": "experts/w_up",
            "e_down": "experts/w_down"}


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, numpy array), leaf by leaf,
    one layer's array fetched from the device at a time, in the type the
    weights are held in (``param_dtype``)."""
    import numpy as np

    host = np.asarray
    yield "params/token_embed/embedding", host(weights["wte"])
    yield "params/ln_f/scale", host(weights["lnf_g"])
    yield "params/lm_head/kernel", host(weights["lm_head"])
    at = {id(t): 0 for t in (_FULL, _WINDOW, _DENSE, _EXPERTS)}
    for i, (swa, moe) in enumerate(layers(cfg)):
        for name, path in _ALL.items():
            yield f"params/block_{i}/{path}", host(weights[name][i])
        for table in (_WINDOW if swa else _FULL, _EXPERTS if moe else _DENSE):
            j = at[id(table)]
            at[id(table)] = j + 1
            for name, path in table.items():
                yield f"params/block_{i}/{path}", host(weights[name][j])
