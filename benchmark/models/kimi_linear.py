"""Builder for the ``kimi_linear`` family (``"builder": "kimi_linear"`` in a
configuration's file; Kimi-Linear-48B-A3B): the function a user would deploy
for it, its weights made from the seed, and their places in the program's
parameter tree.

As ``models/olmo_hybrid.py`` and ``models/mimo_v2.py``: the weights are the
benchmark's, made on the device from the seed, in the reference's layout
(the two norms stacked over all layers; ``k_*`` over the Kimi Delta Attention
layers, ``m_*`` over the latent-attention layers, ``d_*`` over the dense
layers, the router's, the held experts' and the shared expert's over the
expert layers), rounded once to ``param_dtype``. ``assumed.init`` in the
configuration's file says how they are scaled.

The stack is ``linear_attn_config``'s two lists, numbered from 1 and cut in
the file to the layers as run (``published.linear_attn_config`` keeps the
27), with ``first_k_dense_replace`` leading dense layers. The configuration
holds one chip's share of each layer's experts: ``num_experts`` is the count
HELD (``reduced``), ``published.num_experts`` what the router scores,
``experts_held_from`` the first held expert's router output. The program
holds a KDA layer's three convolution kernels side by side as one array (q |
k | v channels) and its ``dt_bias`` by head, where the reference holds three
kernels and a flat bias: the same mathematics."""

from __future__ import annotations

import dataclasses
import importlib.util

from .. import spec

FUNCTION_NAME = "bench-kimi-linear"


def _program_has_the_family() -> bool:
    if importlib.util.find_spec("kubeml_tpu.models.gated_deltanet") is None:
        return False
    from kubeml_tpu.models import gated_deltanet, mla

    return hasattr(gated_deltanet, "KDAConfig") and "mla_use_nope" in {
        f.name for f in dataclasses.fields(mla.MLAConfig)}


# a program from before PR 51 gates its delta rule by a head's scalar alone
# and always rotates under latent attention: say so and exit at once, before
# any weights are made (a SpecError exits non-zero, no result)
if not _program_has_the_family():
    raise spec.SpecError(
        "this program has no delta rule gated per key channel and no latent "
        "attention without rotation (kubeml_tpu/models/gated_deltanet.py "
        "KDAConfig, models/mla.py MLAConfig.mla_use_nope): it cannot run a "
        "kimi_linear configuration")


def layers(cfg: dict) -> tuple:
    """(kda?, experts?) of each layer as run, the first layer first."""
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    assert kda | full == set(range(1, n + 1)) and not kda & full, (kda, full)
    assert cfg["moe_layer_freq"] == 1
    return tuple((i in kda, i > cfg["first_k_dense_replace"])
                 for i in range(1, n + 1))


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published stack (pre-norm RMSNorm blocks, no positional term; Kimi Delta
    Attention layers and latent attention without rotation by
    ``linear_attn_config``; one dense SwiGLU layer, then sigmoid-routed
    experts beside a shared one: this chip's share)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    lin = cfg["linear_attn_config"]
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("q_lora_rank", None), ("mla_use_nope", True),
                      ("rope_scaling", None), ("num_nextn_predict_layers", 0),
                      ("moe_router_activation_func", "sigmoid"),
                      ("moe_renormalize", True), ("use_grouped_topk", True),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("num_shared_experts", 1),
                      ("num_key_value_heads", cfg["num_attention_heads"]),
                      ("v_head_dim", cfg["qk_nope_head_dim"])):
        assert cfg[key] == want, f"kimi_linear builder: {key} = {cfg[key]!r}"
    assert lin["num_heads"] == cfg["num_attention_heads"], lin
    kinds = layers(cfg)
    dense = cfg["first_k_dense_replace"]
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.experts import ExpertsConfig
from kubeml_tpu.models.gated_deltanet import KDAConfig
from kubeml_tpu.models.gpt import AttnKind, CausalTransformer
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
            norm="rmsnorm", ln_eps={cfg["rms_norm_eps"]!r}, pos="none",
            mla=MLAConfig(
                q_lora_rank=None, kv_lora_rank={cfg["kv_lora_rank"]},
                qk_nope_head_dim={cfg["qk_nope_head_dim"]},
                qk_rope_head_dim={cfg["qk_rope_head_dim"]},
                v_head_dim={cfg["v_head_dim"]},
                norm_eps={cfg["rms_norm_eps"]!r}, mla_use_nope=True),
            attn_kinds=(AttnKind(), AttnKind(linear=True)),
            attn_pattern={tuple(int(kda) for kda, _ in kinds)!r},
            gdn=KDAConfig(
                num_heads={lin["num_heads"]}, head_dim={lin["head_dim"]},
                short_conv_kernel_size={lin["short_conv_kernel_size"]},
                norm_eps={cfg["rms_norm_eps"]!r}),
            mlp="experts", mlp_dim={cfg["intermediate_size"]},
            dense_layers={dense},
            experts=ExpertsConfig(
                n_routed_experts={cfg["published"]["num_experts"]},
                num_experts_per_tok={cfg["num_experts_per_token"]},
                moe_intermediate_size={cfg["moe_intermediate_size"]},
                routed_scaling_factor={float(cfg["routed_scaling_factor"])!r},
                scoring_func="sigmoid", norm_topk_prob=True,
                n_shared_experts=1,
                held=({cfg["experts_held_from"]}, {cfg["num_experts"]})))

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


# the selection bias's spread (GLM's), and the spread of a latent-attention
# layer's scores over a row's keys: a PEAKED softmax, as a trained
# attention's is (assumed.init says what each gives, and what a spread of 1
# hid from the check)
SELECT_STD = 0.01
QUERY_SPREAD = 4.0


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind). kind: 'embed' normal(0, 1); 'kernel' normal(0,
    1 / sqrt(fan_in)) with fan_in the axis before the last (a convolution's
    four taps); 'scale' 1 + normal(0, 0.1); 'select' normal(0, SELECT_STD)
    (the selection bias); 'query' normal(0, QUERY_SPREAD / sqrt(fan_in)) (the
    latent attention's W_q); 'a_log' the log of uniform(1, 16), one a head;
    'dt_bias' the inverse softplus of a step log-uniform in (0.001, 0.1),
    one a key channel. The configuration's ``assumed.init`` says why each."""
    c = cfg
    kinds = layers(c)
    lin = c["linear_attn_config"]
    n, e, v, h = (len(kinds), c["hidden_size"], c["vocab_size"],
                  c["num_attention_heads"])
    nk = sum(kda for kda, _ in kinds)
    ne = sum(moe for _, moe in kinds)
    nm, nd = n - nk, n - ne
    d, taps = lin["head_dim"], lin["short_conv_kernel_size"]
    dc, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    i, w = c["intermediate_size"], c["moe_intermediate_size"]
    held, outputs = c["num_experts"], c["published"]["num_experts"]
    return {
        "wte": ((v, e), "embed"), "lnf_g": ((e,), "scale"),
        "lm_head": ((e, v), "kernel"),
        "ln1_g": ((n, e), "scale"), "ln2_g": ((n, e), "scale"),
        "k_wq": ((nk, e, h * d), "kernel"), "k_wk": ((nk, e, h * d), "kernel"),
        "k_wv": ((nk, e, h * d), "kernel"), "k_wo": ((nk, h * d, e), "kernel"),
        "k_wb": ((nk, e, h), "kernel"),
        "k_wfa": ((nk, e, d), "kernel"), "k_wfb": ((nk, d, h * d), "kernel"),
        "k_wga": ((nk, e, d), "kernel"), "k_wgb": ((nk, d, h * d), "kernel"),
        "k_conv_q": ((nk, taps, h * d), "kernel"),
        "k_conv_k": ((nk, taps, h * d), "kernel"),
        "k_conv_v": ((nk, taps, h * d), "kernel"),
        "k_A_log": ((nk, h), "a_log"), "k_dt_bias": ((nk, h * d), "dt_bias"),
        "k_on_g": ((nk, d), "scale"),
        "m_wq": ((nm, e, h * (dn + dr)), "query"),
        "m_wdkv": ((nm, e, dc + dr), "kernel"),
        "m_kvn_g": ((nm, dc), "scale"),
        "m_wukv": ((nm, dc, h * (dn + dv)), "kernel"),
        "m_wo": ((nm, h * dv, e), "kernel"),
        "d_gate": ((nd, e, i), "kernel"), "d_up": ((nd, e, i), "kernel"),
        "d_down": ((nd, i, e), "kernel"),
        "w_r": ((ne, e, outputs), "kernel"), "b_r": ((ne, outputs), "select"),
        "e_gate": ((ne, held, e, w), "kernel"),
        "e_up": ((ne, held, e, w), "kernel"),
        "e_down": ((ne, held, w, e), "kernel"),
        "s_gate": ((ne, e, w), "kernel"), "s_up": ((ne, e, w), "kernel"),
        "s_down": ((ne, w, e), "kernel"),
    }


def _draw(shape: tuple, kind: str, key, host, dtype: str):
    import jax.numpy as jnp
    import numpy as np

    # 'embed', 'kernel', 'scale' and 'dt_bias' are Olmo-Hybrid's rules (one
    # drawing program per distinct size, stacks a leading row at a time)
    from .longcat_flash import _draw as normal
    from .olmo_hybrid import _draw as as_olmo

    if kind == "a_log":
        return jnp.asarray(np.log(1.0 + 15.0 * host.random(shape, np.float32)),
                           dtype)
    if kind == "select":
        return normal(shape, 0.0, SELECT_STD, key, host, dtype)
    if kind == "query":
        return normal(shape, 0.0, QUERY_SPREAD * shape[-2] ** -0.5, key, host,
                      dtype)
    return as_olmo(shape, kind, key, host, dtype)


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names; and
    what the reference needs beside them: the two scalars, the static sizes
    as lengths (``topk_slots``, ``first_slots``) and the two patterns as
    lists of an array a layer whose length is 1 where the layer is a KDA
    layer / an expert layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 51])
    slots = lambda n: jnp.zeros((int(n),), jnp.float32)
    out = {"rope_theta": jnp.asarray(cfg["rope_theta"], jnp.float32),
           "routed_scale": jnp.asarray(cfg["routed_scaling_factor"],
                                       jnp.float32),
           "topk_slots": slots(cfg["num_experts_per_token"]),
           "first_slots": slots(cfg["experts_held_from"]),
           "kda_layers": [slots(kda) for kda, _ in layers(cfg)],
           "moe_layers": [slots(moe) for _, moe in layers(cfg)]}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        out[name] = _draw(shape, kind, jax.random.fold_in(key, j), host,
                          cfg["param_dtype"])
    return out


_ALL = {"ln1_g": "ln1/scale", "ln2_g": "ln2/scale"}
_KDA = {"k_wq": "mixer/q_proj/kernel", "k_wk": "mixer/k_proj/kernel",
        "k_wv": "mixer/v_proj/kernel", "k_wo": "mixer/o_proj/kernel",
        "k_wb": "mixer/b_proj/kernel", "k_wfa": "mixer/f_a_proj/kernel",
        "k_wfb": "mixer/f_b_proj/kernel", "k_wga": "mixer/g_a_proj/kernel",
        "k_wgb": "mixer/g_b_proj/kernel", "k_A_log": "mixer/A_log",
        "k_on_g": "mixer/norm_scale"}
# the program's conv_kernel holds the three convolutions side by side
_CONV = ("k_conv_q", "k_conv_k", "k_conv_v")
_MLA = {"m_wq": "attn/q_proj/kernel", "m_wdkv": "attn/kv_down/kernel",
        "m_kvn_g": "attn/kv_norm/scale", "m_wo": "attn/proj/kernel"}
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
    weights are held in (``param_dtype``)."""
    import numpy as np

    host = np.asarray
    yield "params/token_embed/embedding", host(weights["wte"])
    yield "params/ln_f/scale", host(weights["lnf_g"])
    yield "params/lm_head/kernel", host(weights["lm_head"])
    h, dc = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    at = {id(t): 0 for t in (_KDA, _MLA, _DENSE, _EXPERTS)}
    for i, (kda, moe) in enumerate(layers(cfg)):
        base = f"params/block_{i}"
        for name, path in _ALL.items():
            yield f"{base}/{path}", host(weights[name][i])
        for table in (_KDA if kda else _MLA, _EXPERTS if moe else _DENSE):
            j = at[id(table)]
            at[id(table)] = j + 1
            for name, path in table.items():
                yield f"{base}/{path}", host(weights[name][j])
            if table is _KDA:
                yield (f"{base}/mixer/conv_kernel", np.concatenate(
                    [host(weights[n][j]) for n in _CONV], axis=1))
                # the program holds dt_bias by head: [H, d]
                yield (f"{base}/mixer/dt_bias",
                       host(weights["k_dt_bias"][j]).reshape(h, -1))
            elif table is _MLA:
                # the program holds W_ukv by head: [dc, H, dn + dv]
                yield (f"{base}/attn/kv_up",
                       host(weights["m_wukv"][j]).reshape(dc, h, -1))
