"""Builder for the Falcon-H1 family (``"builder": "falcon_h1"`` in a
configuration's file): the function a user would deploy for it, its weights
made from the seed, and their places in the program's parameter tree.

The weights are the benchmark's, not the program's. The wide arrays are made
on the device, one jitted call each (the widest, a layer stack of an MLP
matrix, is 2.6 GB in float32 before it is rounded), the small ones on the
host, in the reference's own layout (every per-layer array stacked along a leading layer axis, the
mixer's input projection split as z, x, B, C, dt), and rounded once to the
configuration's ``param_dtype`` (bfloat16): program and reference then hold
the same values whatever type the checkpoint file carries. The program gets
them as a finished job's final checkpoint, in float32 files; the reference
gets the same calls' results again after the program is gone.

``assumed.init`` in the configuration's file says how the seeded weights are
scaled: every matrix whose output the model multiplies by a constant is
divided by that constant, so that at the published multipliers each branch
and the logits come out with a root mean square of order 1."""

from __future__ import annotations

import functools
import importlib.util

from .. import spec

FUNCTION_NAME = "bench-falcon-h1"

# a program from before PR 27 has no such block: say so and exit at once,
# before any weights are made (a SpecError exits non-zero with no result)
if importlib.util.find_spec("kubeml_tpu.models.mamba2") is None:
    raise spec.SpecError(
        "this program has no Mamba-2 mixer (kubeml_tpu/models/mamba2.py): "
        "it cannot run a Falcon-H1 configuration")


def function_source(cfg: dict) -> str:
    """What a user deploys: this repo's CausalTransformer configured as the
    published block (RMSNorm, grouped-query attention with rotary positions,
    a Mamba-2 mixer beside it, SwiGLU, the muP multipliers)."""
    dtype = {"bfloat16": "jnp.bfloat16", "float32": "jnp.float32"}[
        cfg["compute_dtype"]]
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("mamba_rms_norm", True),
                      ("mamba_norm_before_gate", False),
                      ("rope_scaling", None), ("tie_word_embeddings", False)):
        assert cfg[key] == want, f"falcon_h1 builder: {key} = {cfg[key]!r}"
    assert cfg["mamba_d_ssm"] == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gpt import CausalTransformer, MuP
from kubeml_tpu.models.mamba2 import SSMConfig
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
            num_heads={cfg["num_attention_heads"]},
            num_kv_heads={cfg["num_key_value_heads"]},
            head_dim={cfg["head_dim"]}, dtype={dtype},
            norm="rmsnorm", ln_eps={cfg["rms_norm_eps"]!r},
            mlp="swiglu", mlp_dim={cfg["intermediate_size"]},
            pos="rope", rope_theta={float(cfg["rope_theta"])!r},
            ssm=SSMConfig(
                d_ssm={cfg["mamba_d_ssm"]}, num_heads={cfg["mamba_n_heads"]},
                head_dim={cfg["mamba_d_head"]},
                n_groups={cfg["mamba_n_groups"]},
                d_state={cfg["mamba_d_state"]}, d_conv={cfg["mamba_d_conv"]},
                chunk_size={cfg["mamba_chunk_size"]},
                conv_bias={cfg["mamba_conv_bias"]!r},
                proj_bias={cfg["mamba_proj_bias"]!r},
                norm_eps={cfg["rms_norm_eps"]!r},
                mup={tuple(float(m) for m in cfg["ssm_multipliers"])!r}),
            mup=MuP(
                embedding={float(cfg["embedding_multiplier"])!r},
                lm_head={float(cfg["lm_head_multiplier"])!r},
                attention_in={float(cfg["attention_in_multiplier"])!r},
                attention_out={float(cfg["attention_out_multiplier"])!r},
                key={float(cfg["key_multiplier"])!r},
                ssm_in={float(cfg["ssm_in_multiplier"])!r},
                ssm_out={float(cfg["ssm_out_multiplier"])!r},
                mlp={tuple(float(m) for m in cfg["mlp_multipliers"])!r}))

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind, divisor). A leading layer axis marks a per-layer
    array. kind: 'embed' normal(0, 1), 'kernel' normal(0, 1 / sqrt(fan_in))
    with fan_in the second axis, 'bias' normal(0, 0.02), 'scale' 1 +
    normal(0, 0.1), 'a_log' log of uniform(1, 16), 'dt_bias' the inverse
    softplus of a step log-uniform in (0.001, 0.1), 'd' 1 + normal(0, 0.1).
    The array is divided by ``divisor``: the constant the model multiplies
    its output by (``assumed.init``)."""
    c = cfg
    n, e, i, v = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    h, hk, d = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    hm, p, g, s, k = (c["mamba_n_heads"], c["mamba_d_head"],
                      c["mamba_n_groups"], c["mamba_d_state"],
                      c["mamba_d_conv"])
    ds = c["mamba_d_ssm"]
    sm = [float(m) * float(c["ssm_in_multiplier"])
          for m in c["ssm_multipliers"]]
    mg, md = (float(m) for m in c["mlp_multipliers"])
    return {
        "wte": ((v, e), "embed", float(c["embedding_multiplier"])),
        "lnf_g": ((e,), "scale", 1.0),
        "lm_head": ((e, v), "kernel", float(c["lm_head_multiplier"])),
        "ln1_g": ((n, e), "scale", 1.0),
        "wq": ((n, e, h * d), "kernel", float(c["attention_in_multiplier"])),
        "wk": ((n, e, hk * d), "kernel",
               float(c["attention_in_multiplier"]) * float(c["key_multiplier"])),
        "wv": ((n, e, hk * d), "kernel", float(c["attention_in_multiplier"])),
        "wo": ((n, h * d, e), "kernel", float(c["attention_out_multiplier"])),
        "w_z": ((n, e, ds), "kernel", sm[0]),
        "w_x": ((n, e, hm, p), "kernel", sm[1]),
        "w_B": ((n, e, g, s), "kernel", sm[2]),
        "w_C": ((n, e, g, s), "kernel", sm[3]),
        "w_dt": ((n, e, hm), "kernel", sm[4]),
        "conv_w": ((n, k, ds + 2 * g * s), "kernel", 1.0),
        "conv_b": ((n, ds + 2 * g * s), "bias", 1.0),
        "A_log": ((n, hm), "a_log", 1.0),
        "D": ((n, hm), "d", 1.0),
        "dt_bias": ((n, hm), "dt_bias", 1.0),
        "mnorm_g": ((n, ds), "scale", 1.0),
        "w_mout": ((n, ds, e), "kernel", float(c["ssm_out_multiplier"])),
        "ln2_g": ((n, e), "scale", 1.0),
        "w_gate": ((n, e, i), "kernel", mg),
        "w_up": ((n, e, i), "kernel", 1.0),
        "w_down": ((n, i, e), "kernel", md),
    }


# arrays under this many elements are drawn on the host (no program to
# compile); the wide ones on the device
_ON_DEVICE = 1 << 20


@functools.lru_cache(maxsize=None)
def _normal_fn(size: int, dtype: str):
    """``mean + std * normal`` over ``size`` elements, rounded to ``dtype``.
    One program per distinct SIZE (five at the published widths: arrays of
    one size share it, mean and std are arguments), drawn with the chip's
    own bit generator (``rbg``): drawing 660M normals with the default
    threefry generator takes the TPU compiler 10 s a program, 150 s of a
    cold set-up over this family's 24 arrays (my chip run, PR 27)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key, mean, std: (
        mean + std * jax.random.normal(key, (size,), jnp.float32)
    ).astype(dtype))


def _draw(kind: str, shape: tuple, divisor: float, key, host, dtype: str):
    import jax.numpy as jnp
    import numpy as np

    size = int(np.prod(shape))
    if kind in ("a_log", "dt_bias"):
        u = host.random(shape, np.float32)
        if kind == "a_log":
            z = np.log(1.0 + 15.0 * u)
        else:
            step = np.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
            z = step + np.log(-np.expm1(-step))       # softplus^-1(step)
        return jnp.asarray(z / divisor, dtype)
    mean, std = {"embed": (0.0, 1.0), "bias": (0.0, 0.02),
                 "scale": (1.0, 0.1), "d": (1.0, 0.1),
                 "kernel": (0.0, shape[1 if len(shape) > 2 else 0] ** -0.5),
                 }[kind]
    mean, std = mean / divisor, std / divisor
    if size < _ON_DEVICE:
        z = mean + std * host.standard_normal(shape, np.float32)
        return jnp.asarray(z, dtype)
    return _normal_fn(size, dtype)(key, mean, std).reshape(shape)


def _scalars(cfg: dict) -> dict:
    """What the reference needs beside the arrays, as float32 scalars."""
    import jax.numpy as jnp

    f = lambda x: jnp.asarray(x, jnp.float32)
    return {"m_embedding": f(cfg["embedding_multiplier"]),
            "m_lm_head": f(cfg["lm_head_multiplier"]),
            "m_attn_in": f(cfg["attention_in_multiplier"]),
            "m_attn_out": f(cfg["attention_out_multiplier"]),
            "m_key": f(cfg["key_multiplier"]),
            "m_ssm_in": f(cfg["ssm_in_multiplier"]),
            "m_ssm_out": f(cfg["ssm_out_multiplier"]),
            "m_ssm": f(cfg["ssm_multipliers"]),
            "m_mlp": f(cfg["mlp_multipliers"]),
            "rope_theta": f(cfg["rope_theta"])}


def init_weights(cfg: dict, seed: int) -> dict:
    """The seed's weights, on the device, rounded to ``param_dtype``: every
    array from its own stream of the seed, in the order of the names."""
    import jax
    import numpy as np

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    host = np.random.default_rng([int(seed), 27])
    out = _scalars(cfg)
    for j, (name, (shape, kind, div)) in enumerate(sorted(shapes(cfg).items())):
        out[name] = _draw(kind, shape, float(div), jax.random.fold_in(key, j),
                          host, cfg["param_dtype"])
    return out


_BLOCK = {  # reference name -> path under params/block_<i>/
    "ln1_g": "ln1/scale", "ln2_g": "ln2/scale",
    "wq": "attn/query/kernel", "wk": "attn/key/kernel",
    "wv": "attn/value/kernel", "wo": "attn/proj/kernel",
    "conv_w": "mixer/conv_kernel", "conv_b": "mixer/conv_bias",
    "A_log": "mixer/A_log", "D": "mixer/D", "dt_bias": "mixer/dt_bias",
    "mnorm_g": "mixer/norm_scale", "w_mout": "mixer/out_proj/kernel",
    "w_gate": "mlp_gate/kernel", "w_up": "mlp_up/kernel",
    "w_down": "mlp_out/kernel",
}
# the program's in_proj holds the five projections side by side, in the
# order of the published split: z | x | B | C | dt
_IN_PROJ = ("w_z", "w_x", "w_B", "w_C", "w_dt")


def program_leaves(cfg: dict, weights: dict):
    """Yield (path in the program's variables, float32 numpy array), leaf by
    leaf, one layer's array fetched from the device at a time (the scalars
    are the reference's alone)."""
    import jax.numpy as jnp
    import numpy as np

    host = lambda a: np.asarray(a.astype(jnp.float32))
    yield "params/token_embed/embedding", host(weights["wte"])
    yield "params/ln_f/scale", host(weights["lnf_g"])
    yield "params/lm_head/kernel", host(weights["lm_head"])
    e = cfg["hidden_size"]
    for i in range(cfg["num_hidden_layers"]):
        for name, path in _BLOCK.items():
            yield f"params/block_{i}/{path}", host(weights[name][i])
        yield (f"params/block_{i}/mixer/in_proj/kernel",
               np.concatenate([host(weights[n][i]).reshape(e, -1)
                               for n in _IN_PROJ], axis=1))
