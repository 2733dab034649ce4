"""The plain reference for the ``olmo_hybrid`` family (Olmo-Hybrid-7B): the
forward pass in straightforward ``jax.numpy``. Float32, every product at
precision ``highest``; the recurrence a ``lax.scan`` over positions; no
chunking, no cache, no pages, no batching, no kernel; the weights are an
argument. It imports nothing of the program.

    x = wte[ids]                                      # no positional term at all
    per layer (Olmo 2's block: the norm is on each branch's OUTPUT):
      h = x + RMSNorm(Mixer(x); ln1_g)
      x = h + RMSNorm((silu(h W_gate) * (h W_up)) W_down; ln2_g)
    full-attention layer (H heads of d = 3840 / 30):
      q, k  = RMSNorm(x Wq; qn_g), RMSNorm(x Wk; kn_g)   # over the whole projection
      v     = x Wv
      Mixer = softmax(causal(q k^T / sqrt(d))) v  Wo     # no rotary (rope_theta null)
    linear-attention layer (Gated DeltaNet; H heads, keys d_k, values d_v):
      q,k,v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))   # causal depthwise, no bias
      q_h, k_h = l2norm(q_h) / sqrt(d_k), l2norm(k_h)
      beta  = beta_scale sigmoid(x Wb);   g = -exp(A_log) softplus(x Wa + dt_bias)
      S_t   = exp(g_t) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t
      Mixer = (RMSNorm(o_t; on_g, per head over d_v) * silu(x Wg)) Wo
    logits = RMSNorm(x; lnf_g) lm_head

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it; the linear layers have as many heads): ``l_*`` are
stacked over the linear layers, ``f_*`` over the full layers, the rest over
all layers; ``linear_layers`` is a list of an array a layer whose length is 1
where the layer is a linear one; ``beta_scale`` (2 with
``linear_allow_neg_eigval``) rides in the dict as a scalar. Weights may
arrive in a narrower type: each layer's are upcast to float32 as the layer
runs. What the ``config`` alone does not settle is the configuration file's
``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product (the recurrence's q, k, v, g and beta among them;
its state stays float32) and are the controls that ``correct`` has to fail.
**Three controls leave a mechanism out**, everything else in float32
(``benchmark/probe_control.py`` puts one in ``lower_precision_control``'s
place): ``delta_off`` (``S += beta k v^T``: the state is not asked what it
already answers for ``k``), ``decay_off`` (``g = 0``: nothing is forgotten),
``qknorm_off`` (the full layers' q and k go unnormed): the faults a dropped
``u`` term or a dropped ``e^g`` factor in the state kernel and a skipped
norm would be."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .glm_moe_lite import _rms
from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)

CONTROLS = ("delta_off", "decay_off", "qknorm_off")


def _split(precision: str) -> tuple:
    """(the mechanism left out or None, the precision of every product)."""
    if precision in CONTROLS:
        return precision, "float32"
    return None, precision


def _conv(x, w):
    """x [T, C], w [K, C]: out[t] = sum_j w[j] x[t - (K - 1) + j]."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[j:j + T] * w[j] for j in range(K))


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> the residual stream after the last layer, [T, E]."""
    T, H = ids.shape[0], n_head
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    left_out, precision = _split(precision)
    mm = lambda a, m: _mm(a, m, precision)
    rd = lambda t: _round(t, -1, precision)
    x = f32(w["wte"][ids])
    t = jnp.arange(T)
    causal = t[None, :] <= t[:, None]

    def full(x, i):
        wq, wk, wv, wo = (f32(w[n][i]) for n in ("f_wq", "f_wk", "f_wv",
                                                 "f_wo"))
        d = wq.shape[-1] // H
        q, k = mm(x, wq), mm(x, wk)
        if left_out != "qknorm_off":
            q = _rms(q, f32(w["f_qn_g"][i]), eps)
            k = _rms(k, f32(w["f_kn_g"][i]), eps)
        q, k, v = (a.reshape(T, H, d).transpose(1, 0, 2)
                   for a in (q, k, mm(x, wv)))
        s = jnp.einsum("htd,hsd->hts", rd(q), rd(k),
                       precision="highest") / jnp.sqrt(float(d))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,hsd->htd", rd(p), _round(v, 1, precision),
                       precision="highest")
        return mm(o.transpose(1, 0, 2).reshape(T, H * d), wo)

    def linear(x, i):
        lw = {n: f32(w[n][i]) for n in (
            "l_wq", "l_wk", "l_wv", "l_wa", "l_wb", "l_wg", "l_wo",
            "l_conv_q", "l_conv_k", "l_conv_v", "l_A_log", "l_dt_bias",
            "l_on_g")}
        dk, dv = lw["l_wq"].shape[-1] // H, lw["l_wv"].shape[-1] // H
        q = jax.nn.silu(_conv(mm(x, lw["l_wq"]), lw["l_conv_q"]))
        k = jax.nn.silu(_conv(mm(x, lw["l_wk"]), lw["l_conv_k"]))
        v = jax.nn.silu(_conv(mm(x, lw["l_wv"]), lw["l_conv_v"]))
        q = _l2(q.reshape(T, H, dk)) / jnp.sqrt(float(dk))
        k = _l2(k.reshape(T, H, dk))
        v = v.reshape(T, H, dv)
        beta = f32(w["beta_scale"]) * jax.nn.sigmoid(mm(x, lw["l_wb"]))
        g = -jnp.exp(lw["l_A_log"]) * jax.nn.softplus(
            mm(x, lw["l_wa"]) + lw["l_dt_bias"])              # [T, H]
        if left_out == "decay_off":
            g = jnp.zeros_like(g)
        q, k, v, g, beta = (rd(a) for a in (q, k, v, g, beta))

        def step(S, at):
            qt, kt, vt, gt, bt = at
            S = S * jnp.exp(gt)[:, None, None]
            r = vt if left_out == "delta_off" else vt - jnp.einsum(
                "hkv,hk->hv", S, kt, precision="highest")
            S = S + kt[:, :, None] * (bt[:, None] * r)[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, qt, precision="highest")

        _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                            (q, k, v, g, beta))
        o = _rms(o, lw["l_on_g"], eps)                        # per head
        y = o.reshape(T, H * dv) * jax.nn.silu(mm(x, lw["l_wg"]))
        return mm(y, lw["l_wo"])

    at = {"l": 0, "f": 0}
    for i, is_linear in enumerate(w["linear_layers"]):
        kind = "l" if is_linear.shape[0] else "f"
        j, at[kind] = at[kind], at[kind] + 1
        mixer = linear(x, j) if kind == "l" else full(x, j)
        h = x + _rms(mixer, f32(w["ln1_g"][i]), eps)
        f = mm(jax.nn.silu(mm(h, f32(w["w_gate"][i])))
               * mm(h, f32(w["w_up"][i])), f32(w["w_down"][i]))
        x = h + _rms(f, f32(w["ln2_g"][i]), eps)
    return x


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: attention,
    convolution and recurrence are all causal)."""
    h = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[at]
    h = _rms(h, jnp.asarray(w["lnf_g"], jnp.float32), eps)
    return _mm(h, jnp.asarray(w["lm_head"], jnp.float32),
               _split(precision)[1])
