"""Builder for the ``olmo_hybrid`` family (``"builder": "olmo_hybrid"`` in a
configuration's file; Olmo-Hybrid-7B): the function a user would deploy for
it, its weights made from the seed, and their places in the program's
parameter tree.

As ``models/mimo_v2.py``: the weights are the benchmark's, made on the device
from the seed, in the reference's layout (what every layer has stacked over
all layers; ``l_*`` over the linear-attention layers, ``f_*`` over the
full-attention layers), rounded once to ``param_dtype``. ``assumed.init`` in
the configuration's file says how they are scaled.

The stack is the file's ``layer_types`` (cut to ``num_hidden_layers``
entries there, ``published.layer_types`` says what of). The program holds
the three convolutions' kernels of a linear layer side by side (q | k | v
channels, one array) where the reference holds three: the same
mathematics."""

from __future__ import annotations

import importlib.util

from .. import spec

FUNCTION_NAME = "bench-olmo-hybrid"

# a program from before PR 48 has no layer whose token mixer is not
# attention: say so and exit at once, before any weights are made (a
# SpecError exits non-zero, no result)
if importlib.util.find_spec("kubeml_tpu.models.gated_deltanet") is None:
    raise spec.SpecError(
        "this program has no gated-delta-rule mixer "
        "(kubeml_tpu/models/gated_deltanet.py, AttnKind.linear): it cannot "
        "run an olmo_hybrid configuration")


def layers(cfg: dict) -> tuple:
    """True for each layer as run that is a linear-attention layer."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert len(kinds) == cfg["num_hidden_layers"] and all(
        k in ("linear_attention", "full_attention") for k in kinds), kinds
    return tuple(k == "linear_attention" for k in kinds)


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published stack (RMSNorm on each branch's output, no positional term,
    full-attention layers with normed queries and keys and Gated DeltaNet
    layers by ``layer_types``, SwiGLU)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("rope_parameters", {"rope_theta": None}),
                      ("num_key_value_heads", cfg["num_attention_heads"]),
                      ("linear_num_key_heads",
                       cfg["linear_num_value_heads"])):
        assert cfg[key] == want, f"olmo_hybrid builder: {key} = {cfg[key]!r}"
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gated_deltanet import GDNConfig
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
            head_dim={cfg["head_dim"]}, norm="rmsnorm",
            ln_eps={cfg["rms_norm_eps"]!r}, norm_at="output", qk_norm=True,
            pos="none", mlp="swiglu", mlp_dim={cfg["intermediate_size"]},
            attn_kinds=(
                AttnKind(num_kv_heads={cfg["num_key_value_heads"]}),
                AttnKind(linear=True)),
            attn_pattern={tuple(int(lin) for lin in layers(cfg))!r},
            gdn=GDNConfig(
                num_heads={cfg["linear_num_value_heads"]},
                key_dim={cfg["linear_key_head_dim"]},
                value_dim={cfg["linear_value_head_dim"]},
                d_conv={cfg["linear_conv_kernel_dim"]},
                neg_eigval={bool(cfg["linear_allow_neg_eigval"])!r},
                norm_eps={cfg["rms_norm_eps"]!r}))

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind). kind: 'embed' normal(0, 1); 'kernel' normal(0,
    1 / sqrt(fan_in)) with fan_in the axis before the last (a convolution's
    four taps); 'scale' 1 + normal(0, 0.1); 'a_log' the log of uniform(0,
    16); 'dt_bias' the inverse softplus of a step log-uniform in (0.001,
    0.1). The configuration's ``assumed.init`` says why each."""
    c = cfg
    kinds = layers(c)
    n, e, v, i = (len(kinds), c["hidden_size"], c["vocab_size"],
                  c["intermediate_size"])
    nl = sum(kinds)
    nf = n - nl
    h, d = c["num_attention_heads"], c["head_dim"]
    hl, dk, dv, k = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                     c["linear_value_head_dim"], c["linear_conv_kernel_dim"])
    return {
        "wte": ((v, e), "embed"), "lnf_g": ((e,), "scale"),
        "lm_head": ((e, v), "kernel"),
        "ln1_g": ((n, e), "scale"), "ln2_g": ((n, e), "scale"),
        "w_gate": ((n, e, i), "kernel"), "w_up": ((n, e, i), "kernel"),
        "w_down": ((n, i, e), "kernel"),
        "f_wq": ((nf, e, h * d), "kernel"), "f_wk": ((nf, e, h * d), "kernel"),
        "f_wv": ((nf, e, h * d), "kernel"), "f_wo": ((nf, h * d, e), "kernel"),
        "f_qn_g": ((nf, h * d), "scale"), "f_kn_g": ((nf, h * d), "scale"),
        "l_wq": ((nl, e, hl * dk), "kernel"),
        "l_wk": ((nl, e, hl * dk), "kernel"),
        "l_wv": ((nl, e, hl * dv), "kernel"),
        "l_wg": ((nl, e, hl * dv), "kernel"),
        "l_wo": ((nl, hl * dv, e), "kernel"),
        "l_wa": ((nl, e, hl), "kernel"), "l_wb": ((nl, e, hl), "kernel"),
        "l_conv_q": ((nl, k, hl * dk), "kernel"),
        "l_conv_k": ((nl, k, hl * dk), "kernel"),
        "l_conv_v": ((nl, k, hl * dv), "kernel"),
        "l_A_log": ((nl, hl), "a_log"), "l_dt_bias": ((nl, hl), "dt_bias"),
        "l_on_g": ((nl, dv), "scale"),
    }


def _draw(shape: tuple, kind: str, key, host, dtype: str):
    import jax.numpy as jnp
    import numpy as np

    # one drawing program per distinct size, stacks a leading row at a time
    from .longcat_flash import _draw as normal

    if kind == "a_log":
        return jnp.asarray(np.log(16.0 * np.maximum(
            host.random(shape, np.float32), 1e-6)), dtype)
    if kind == "dt_bias":
        step = np.exp(np.log(1e-3) + host.random(shape, np.float32)
                      * (np.log(1e-1) - np.log(1e-3)))
        return jnp.asarray(step + np.log(-np.expm1(-step)), dtype)
    mean, std = {"embed": (0.0, 1.0), "scale": (1.0, 0.1),
                 "kernel": (0.0, shape[-2] ** -0.5 if len(shape) > 1
                            else 1.0)}[kind]
    return normal(shape, mean, std, key, host, dtype)


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names; and
    what the reference needs beside them: ``beta_scale`` and the pattern as
    a list of an array a layer whose length is 1 where the layer is a
    linear-attention layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 48])
    out = {"beta_scale": jnp.asarray(
               2.0 if cfg["linear_allow_neg_eigval"] else 1.0, jnp.float32),
           "linear_layers": [jnp.zeros((int(lin),), jnp.float32)
                             for lin in layers(cfg)]}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        out[name] = _draw(shape, kind, jax.random.fold_in(key, j), host,
                          cfg["param_dtype"])
    return out


_ALL = {"ln1_g": "ln1/scale", "ln2_g": "ln2/scale",
        "w_gate": "mlp_gate/kernel", "w_up": "mlp_up/kernel",
        "w_down": "mlp_out/kernel"}
_FULL = {"f_wq": "attn/query/kernel", "f_wk": "attn/key/kernel",
         "f_wv": "attn/value/kernel", "f_wo": "attn/proj/kernel",
         "f_qn_g": "attn/q_norm/scale", "f_kn_g": "attn/k_norm/scale"}
_LINEAR = {"l_wq": "mixer/q_proj/kernel", "l_wk": "mixer/k_proj/kernel",
           "l_wv": "mixer/v_proj/kernel", "l_wg": "mixer/g_proj/kernel",
           "l_wo": "mixer/o_proj/kernel", "l_wa": "mixer/a_proj/kernel",
           "l_wb": "mixer/b_proj/kernel", "l_A_log": "mixer/A_log",
           "l_dt_bias": "mixer/dt_bias", "l_on_g": "mixer/norm_scale"}
# the program's conv_kernel holds the three convolutions side by side
_CONV = ("l_conv_q", "l_conv_k", "l_conv_v")


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, numpy array), leaf by leaf,
    one layer's array fetched from the device at a time, in the type the
    weights are held in (``param_dtype``)."""
    import numpy as np

    host = np.asarray
    yield "params/token_embed/embedding", host(weights["wte"])
    yield "params/ln_f/scale", host(weights["lnf_g"])
    yield "params/lm_head/kernel", host(weights["lm_head"])
    at = {True: 0, False: 0}
    for i, lin in enumerate(layers(cfg)):
        for name, path in _ALL.items():
            yield f"params/block_{i}/{path}", host(weights[name][i])
        j = at[lin]
        at[lin] = j + 1
        for name, path in (_LINEAR if lin else _FULL).items():
            yield f"params/block_{i}/{path}", host(weights[name][j])
        if lin:
            yield (f"params/block_{i}/mixer/conv_kernel",
                   np.concatenate([host(weights[n][j]) for n in _CONV],
                                  axis=1))
