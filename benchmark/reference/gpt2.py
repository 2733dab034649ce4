"""The plain reference for the GPT-2 family: the published forward pass in
straightforward ``jax.numpy``. Float32, every product at precision
``highest``; no cache, no pages, no batching, no kernel; the weights are an
argument. It imports nothing of the program.

    h = wte[ids] + wpe[0..T)
    per layer:  a = LN1(h);  q, k, v = a Wq + bq, a Wk + bk, a Wv + bv
                h += softmax(causal(q k^T / sqrt(d))) v  Wo + bo
                m = LN2(h);  h += gelu_new(m W_in + b_in) W_out + b_out
    logits = LN_f(h) lm_head

Departures from the release are the configuration file's: an untied
``lm_head`` and three projections where GPT-2 fuses ``c_attn``.

``precision`` chooses the arithmetic of every product. ``"float32"`` is the
reference. The others are the controls that ``correct`` has to fail: the
same mathematics with both operands of every product rounded to a lower
type first (scaled to the type's range by the largest magnitude, per output
channel for a weight and per row for an activation) and accumulated in
float32."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8_e4m3", "int8")


def _round(x, axis: int, precision: str):
    """``x`` as the lower type would hold it, back in float32."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    top = {"fp8_e4m3": 448.0, "int8": 127.0}[precision]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    y = x / scale
    if precision == "int8":
        y = jnp.clip(jnp.round(y), -127, 127)
    else:
        y = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y * scale


def _mm(a, w, precision: str):
    """a [..., K] @ w [K, N] with both operands rounded along K."""
    return jnp.matmul(_round(a, -1, precision), _round(w, 0, precision),
                      precision="highest")


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> the residual stream after the last layer, [T, E]."""
    T = ids.shape[0]
    E = w["wte"].shape[1]
    d = E // n_head
    h = w["wte"][ids] + w["wpe"][:T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    layer_names = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv",
                   "wo", "bo", "ln2_g", "ln2_b", "w_in", "b_in", "w_out",
                   "b_out")

    def layer(h, lw):
        a = _ln(h, lw["ln1_g"], lw["ln1_b"], eps)
        heads = lambda t: t.reshape(T, n_head, d).transpose(1, 0, 2)
        q = heads(_mm(a, lw["wq"], precision) + lw["bq"])
        k = heads(_mm(a, lw["wk"], precision) + lw["bk"])
        v = heads(_mm(a, lw["wv"], precision) + lw["bv"])
        s = jnp.einsum("htd,hsd->hts", _round(q, -1, precision),
                       _round(k, -1, precision),
                       precision="highest") / jnp.sqrt(float(d))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,hsd->htd", _round(p, -1, precision),
                       _round(v, 1, precision), precision="highest")
        o = o.transpose(1, 0, 2).reshape(T, E)
        h = h + _mm(o, lw["wo"], precision) + lw["bo"]
        m = _ln(h, lw["ln2_g"], lw["ln2_b"], eps)
        u = _gelu_new(_mm(m, lw["w_in"], precision) + lw["b_in"])
        return h + _mm(u, lw["w_out"], precision) + lw["b_out"], None

    h, _ = jax.lax.scan(layer, h, {n: w[n] for n in layer_names})
    return h


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: the
    attention is causal)."""
    h = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[at]
    h = _ln(h, w["lnf_g"], w["lnf_b"], eps)
    return _mm(h, w["lm_head"], precision)
