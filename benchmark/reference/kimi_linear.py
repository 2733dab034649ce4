"""The plain reference for the ``kimi_linear`` family (Kimi-Linear-48B-A3B):
the forward pass in straightforward ``jax.numpy``. Float32, every product at
precision ``highest``; the recurrence a ``lax.scan`` over positions; latent
attention in the expanded form over the whole sequence, a head at a time; the
experts as a masked sum over the experts HELD (every held expert multiplies
every token, the gates pick); no chunking, no cache, no pages, no sorting, no
kernel; the weights are an argument. It imports nothing of the program, and
it never follows the program's routing: it routes by its own float32 scores.

    x = wte[ids]                                      # no positional term at all
    per layer (pre-norm):
      h = x + Mixer(RMSNorm(x; ln1_g));   f = RMSNorm(h; ln2_g);   x = h + FFN(f)
    KDA layer (Kimi Delta Attention; H heads, keys and values d wide), u the normed input:
      q,k,v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))    # causal depthwise, no bias
      q_h, k_h = l2norm(q_h) / sqrt(d), l2norm(k_h)
      beta  = sigmoid(u Wb)                                            # [H]
      a     = -exp(A_log_h) softplus((u Wfa) Wfb + dt_bias)            # [H, d], <= 0
      S_t   = Diag(exp(a_t)) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t
      Mixer = (RMSNorm(o_t; on_g, per head over d) * sigmoid((u Wga) Wgb)) Wo
    MLA layer (latent attention WITHOUT rotation):
      q     = u Wq as H heads of [q_nope dn | q_pe dr]                 # no query latent
      [c|r] = u Wdkv;  c = RMSNorm(c; kvn_g)                           # r is not rotated
      [k_nope_h dn | v_h dv] = c Wukv, per head h
      s_h[t,j] = (q_nope_h[t].k_nope_h[j] + q_pe_h[t].r[j]) / sqrt(dn + dr),  j <= t
      Mixer = concat_h(softmax(s_h) v_h) Wo
    FFN: a dense layer  (silu(f Wg) * (f Wu)) Wd
         an expert layer s = sigmoid(f Wr);  T = the top_k largest of s + b
                         g_i = scale * s_i / (sum_{j in T} s_j + 1e-20), i in T
                         sum_{i in T, first <= i < first + held} g_i E_i(f) + E_shared(f)
    logits = RMSNorm(x; lnf_g) lm_head

**The share.** The weights hold ``held`` experts a layer (``e_gate`` [n, held,
E, w]), the router all its outputs; the held experts are router outputs
``first .. first + held - 1``. A choice of an expert outside that range adds
nothing, here as in the program: the result is this chip's part of the layer
(and the shared expert's whole), and that partial stream is what the next
layer reads.

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it; the KDA layers have as many heads): ``k_*`` are stacked
over the KDA layers, ``m_*`` over the MLA layers, ``d_*`` over the dense
layers, the router's, the held experts' and the shared expert's over the
expert layers, the two norms over all layers; ``kda_layers`` and
``moe_layers`` are lists of an array a layer whose length is 1 where the
layer is a KDA layer / an expert layer; ``top_k`` is the length of
``topk_slots``, ``first`` of ``first_slots``; ``routed_scale`` and
``rope_theta`` (read by the ``nope_off`` control alone) ride in the dict as
scalars. Weights may arrive in a narrower type: each layer's (inside an
expert layer each expert's) are upcast to float32 as they are used. What the
``config`` alone does not settle is the configuration file's ``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product (the router's and the recurrence's q, k, v, a and
beta among them; the state stays float32) and are the controls that
``correct`` has to fail. **Four controls plant a fault in a mechanism**,
everything else in float32 (``benchmark/probe_control.py`` puts one in
``lower_precision_control``'s place): ``channel_gate_off`` (each head's ``d``
decays replaced by their mean: what a kernel with one decay a head would
compute), ``delta_off`` (``S += beta k v^T``: the state is not asked what it
already answers for ``k``), ``nope_off`` (``q_pe`` and ``r`` rotated at
``rope_theta``: what a latent attention that always rotates would compute),
``held_zero`` (the held experts' part of the sum left out)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .glm_moe_lite import _rms, _rope
from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)
from .olmo_hybrid import _conv, _l2

CONTROLS = ("channel_gate_off", "delta_off", "nope_off", "held_zero")
KDA_NAMES = ("k_wq", "k_wk", "k_wv", "k_wo", "k_wb", "k_wfa", "k_wfb",
             "k_wga", "k_wgb", "k_conv_q", "k_conv_k", "k_conv_v", "k_A_log",
             "k_dt_bias", "k_on_g")
MLA_NAMES = ("m_wq", "m_wdkv", "m_kvn_g", "m_wukv", "m_wo")


def _split(precision: str) -> tuple:
    """(the fault planted or None, the precision of every product)."""
    if precision in CONTROLS:
        return precision, "float32"
    return None, precision


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> (the residual stream after the last layer, [T, E]; the
    router outputs each token chose in each expert layer, [layers, T,
    top_k])."""
    T, H = ids.shape[0], n_head
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    fault, precision = _split(precision)
    mm = lambda a, m: _mm(a, m, precision)
    rd = lambda t: _round(t, -1, precision)
    x = f32(w["wte"][ids])
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = f32(w["routed_scale"])
    top_k, first = w["topk_slots"].shape[0], w["first_slots"].shape[0]

    def kda(u, i):
        lw = {n: f32(w[n][i]) for n in KDA_NAMES}
        d = lw["k_wq"].shape[-1] // H
        q = jax.nn.silu(_conv(mm(u, lw["k_wq"]), lw["k_conv_q"]))
        k = jax.nn.silu(_conv(mm(u, lw["k_wk"]), lw["k_conv_k"]))
        v = jax.nn.silu(_conv(mm(u, lw["k_wv"]), lw["k_conv_v"]))
        q = _l2(q.reshape(T, H, d)) / jnp.sqrt(float(d))
        k = _l2(k.reshape(T, H, d))
        v = v.reshape(T, H, d)
        beta = jax.nn.sigmoid(mm(u, lw["k_wb"]))               # [T, H]
        a = -jnp.exp(lw["k_A_log"])[:, None] * jax.nn.softplus(
            mm(mm(u, lw["k_wfa"]), lw["k_wfb"])
            + lw["k_dt_bias"]).reshape(T, H, d)                # [T, H, d]
        if fault == "channel_gate_off":
            a = jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)
        q, k, v, a, beta = (rd(t) for t in (q, k, v, a, beta))

        def step(S, at):
            qt, kt, vt, gt, bt = at
            S = S * jnp.exp(gt)[:, :, None]           # row i by its own decay
            r = vt if fault == "delta_off" else vt - jnp.einsum(
                "hkv,hk->hv", S, kt, precision="highest")
            S = S + kt[:, :, None] * (bt[:, None] * r)[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, qt, precision="highest")

        _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                            (q, k, v, a, beta))
        o = _rms(o, lw["k_on_g"], eps)                         # per head
        gate = jax.nn.sigmoid(mm(mm(u, lw["k_wga"]), lw["k_wgb"]))
        return mm(o.reshape(T, H * d) * gate, lw["k_wo"])

    def mla(u, i):
        lw = {n: f32(w[n][i]) for n in MLA_NAMES}
        dc = lw["m_kvn_g"].shape[-1]
        dr = lw["m_wdkv"].shape[-1] - dc
        dn = lw["m_wq"].shape[-1] // H - dr
        dv = lw["m_wukv"].shape[-1] // H - dn
        q = mm(u, lw["m_wq"]).reshape(T, H, dn + dr)
        ckr = mm(u, lw["m_wdkv"])
        c = _rms(ckr[:, :dc], lw["m_kvn_g"], eps)
        r = ckr[:, dc:]                                        # [T, dr]
        if fault == "nope_off":
            theta = f32(w["rope_theta"])
            r = _rope(r[:, None, :], theta)[:, 0]
            q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
        kv = mm(c, lw["m_wukv"]).reshape(T, H, dn + dv)

        def head(qkv):
            q, kv = qkv                                        # [T, .]
            k = jnp.concatenate([kv[:, :dn], r], -1)
            s = jnp.matmul(rd(q), rd(k).T,
                           precision="highest") / jnp.sqrt(float(dn + dr))
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.matmul(rd(p), _round(kv[:, dn:], 0, precision),
                              precision="highest")

        o = jax.lax.map(head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
        return mm(o.transpose(1, 0, 2).reshape(T, H * dv), lw["m_wo"])

    def swiglu(f, gate, up, down):
        return mm(jax.nn.silu(mm(f, gate)) * mm(f, up), down)

    def experts(f, j):
        s = jax.nn.sigmoid(mm(f, f32(w["w_r"][j])))            # [T, G]
        _, chosen = jax.lax.top_k(s + f32(w["b_r"][j]), top_k)
        picked = (chosen[:, :, None]
                  == jnp.arange(s.shape[-1])[None, None, :]).any(axis=1)
        total = jnp.where(picked, s, 0.0).sum(-1, keepdims=True)
        gates = jnp.where(picked, scale * s / (total + 1e-20), 0.0)
        held = {n: w[n][j] for n in ("e_gate", "e_up", "e_down")}
        n_held = held["e_gate"].shape[0]

        def one(acc, ew):
            e, g = ew
            y = swiglu(f, f32(e["e_gate"]), f32(e["e_up"]), f32(e["e_down"]))
            return acc + g[:, None] * y, None

        routed = jnp.zeros_like(f)
        if fault != "held_zero":
            routed, _ = jax.lax.scan(
                one, routed, (held, gates[:, first:first + n_held].T))
        shared = swiglu(f, *(f32(w[n][j]) for n in ("s_gate", "s_up",
                                                    "s_down")))
        return routed + shared, chosen

    at = {"k": 0, "m": 0, "d": 0, "e": 0}
    chose = []
    for i, (is_kda, is_moe) in enumerate(zip(w["kda_layers"],
                                             w["moe_layers"])):
        mixer = "k" if is_kda.shape[0] else "m"
        j, at[mixer] = at[mixer], at[mixer] + 1
        u = _rms(x, f32(w["ln1_g"][i]), eps)
        h = x + (kda(u, j) if mixer == "k" else mla(u, j))
        f = _rms(h, f32(w["ln2_g"][i]), eps)
        ffn = "e" if is_moe.shape[0] else "d"
        j, at[ffn] = at[ffn], at[ffn] + 1
        if ffn == "e":
            y, chosen = experts(f, j)
            chose.append(chosen)
        else:
            y = swiglu(f, *(f32(w[n][j]) for n in ("d_gate", "d_up",
                                                   "d_down")))
        x = h + y
    return x, jnp.stack(chose)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: attention,
    convolution and recurrence are all causal and the experts work token by
    token)."""
    h = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[0][at]
    h = _rms(h, jnp.asarray(w["lnf_g"], jnp.float32), eps)
    return _mm(h, jnp.asarray(w["lm_head"], jnp.float32),
               _split(precision)[1])


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def routing(w: dict, ids, *, n_head: int, eps: float,
            precision: str = "float32"):
    """The router outputs each position of ``ids`` [T] chose, [expert
    layers, T, top_k] (unordered within a token)."""
    return hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[1]
