"""The plain reference for the Falcon-H1 family: the published forward pass in
straightforward ``jax.numpy``. Float32, every product at precision
``highest``; the recurrence as a ``lax.scan`` over positions; no chunking, no
cache, no pages, no batching, no kernel; the weights are an argument. It
imports nothing of the program.

    h = wte[ids] * embedding_multiplier
    per layer:
      u     = RMSNorm(h; ln1_g)
      q,k,v = a Wq, (a Wk) * key_multiplier, a Wv,    a = u * attention_in_multiplier
      q,k   = RoPE(q), RoPE(k)                         (rotate-half, theta rope_theta)
      attn  = softmax(causal(q k^T / sqrt(d))) v  Wo  (query head h reads K/V head h // (H / Hkv))
      z,x,B,C,dt = (m Wz, m Wx, m WB, m WC, m Wdt) * ssm_multipliers[0..4],   m = u * ssm_in_multiplier
      x|B|C = silu(causal_depthwise_conv1d(x|B|C, conv_w) + conv_b)
      dt    = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t   = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;   y_t = S_t C_t + D x_t
      mix   = RMSNorm_grouped(y * silu(z); mnorm_g, per group)  W_mout
      h    += attn * attention_out_multiplier + mix * ssm_out_multiplier
      f     = RMSNorm(h; ln2_g)
      h    += (silu((f Wg) * mlp_multipliers[0]) * (f Wu)) Wd * mlp_multipliers[1]
    logits = (RMSNorm(h; lnf_g) lm_head) * lm_head_multiplier

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it): ``wq`` [n, E, H d], ``wk`` and ``wv`` [n, E, Hkv d],
``w_x`` [n, E, Hm, P], ``w_B`` and ``w_C`` [n, E, G, N], ``w_dt`` [n, E, Hm].
The multipliers and ``rope_theta`` ride in the weights' dict as scalars
(``m_*``, ``rope_theta``). Weights may arrive in a narrower type (the
configuration holds them in bfloat16): each layer's are upcast to float32
inside the scan over layers, one layer at a time, so the replay fits beside
the stored weights. What the ``config`` alone does not settle is the
configuration file's ``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product to a lower type first (the recurrence's operands
x, B, C and dt among them; its state stays float32) and are the controls
that ``correct`` has to fail."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)

LAYER_NAMES = ("ln1_g", "wq", "wk", "wv", "wo", "w_z", "w_x", "w_B", "w_C",
               "w_dt", "conv_w", "conv_b", "A_log", "D", "dt_bias", "mnorm_g",
               "w_mout", "ln2_g", "w_gate", "w_up", "w_down")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, heads, d], position t at row t; pairs (x[:d/2], x[d/2:])."""
    T, _, d = x.shape
    freq = jnp.exp(-jnp.log(theta) * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _conv(x, w, b):
    """x [T, C], w [K, C]: out[t] = sum_j w[j] x[t - (K - 1) + j] + b."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[j:j + T] * w[j] for j in range(K)) + b


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> the residual stream after the last layer, [T, E]."""
    T = ids.shape[0]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    mm = lambda a, m: _mm(a, m, precision)
    h = f32(w["wte"])[ids] * f32(w["m_embedding"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    m_ssm, m_mlp = f32(w["m_ssm"]), f32(w["m_mlp"])

    def layer(h, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        E = h.shape[-1]
        u = _rms(h, lw["ln1_g"], eps)
        # attention
        d = lw["wq"].shape[-1] // n_head
        n_kv = lw["wk"].shape[-1] // d
        a = u * f32(w["m_attn_in"])
        q = mm(a, lw["wq"]).reshape(T, n_head, d)
        k = (mm(a, lw["wk"]) * f32(w["m_key"])).reshape(T, n_kv, d)
        v = mm(a, lw["wv"]).reshape(T, n_kv, d)
        q, k = _rope(q, f32(w["rope_theta"])), _rope(k, f32(w["rope_theta"]))
        share = n_head // n_kv
        k = jnp.repeat(k, share, axis=1).transpose(1, 0, 2)
        v = jnp.repeat(v, share, axis=1).transpose(1, 0, 2)
        q = q.transpose(1, 0, 2)
        s = jnp.einsum("htd,hsd->hts", _round(q, -1, precision),
                       _round(k, -1, precision),
                       precision="highest") / jnp.sqrt(float(d))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,hsd->htd", _round(p, -1, precision),
                       _round(v, 1, precision), precision="highest")
        attn = mm(o.transpose(1, 0, 2).reshape(T, n_head * d), lw["wo"])
        # mixer
        Hm, P = lw["w_x"].shape[-2:]
        G, N = lw["w_B"].shape[-2:]
        m = u * f32(w["m_ssm_in"])
        flat = lambda name: lw[name].reshape(E, -1)
        z = mm(m, lw["w_z"]) * m_ssm[0]
        xBC = jnp.concatenate([mm(m, flat("w_x")) * m_ssm[1],
                               mm(m, flat("w_B")) * m_ssm[2],
                               mm(m, flat("w_C")) * m_ssm[3]], axis=-1)
        dt = mm(m, lw["w_dt"]) * m_ssm[4]
        xBC = jax.nn.silu(_conv(xBC, lw["conv_w"], lw["conv_b"]))
        x = xBC[:, :Hm * P].reshape(T, Hm, P)
        Bm = xBC[:, Hm * P:Hm * P + G * N].reshape(T, G, N)
        Cm = xBC[:, Hm * P + G * N:].reshape(T, G, N)
        dt = jax.nn.softplus(dt + lw["dt_bias"])             # [T, Hm]
        A = -jnp.exp(lw["A_log"])
        x, Bm, Cm, dt = (_round(t, -1, precision) for t in (x, Bm, Cm, dt))
        per = Hm // G

        def step(S, t):
            xt, Bt, Ct, dtt = t
            Bh = jnp.repeat(Bt, per, axis=0)                 # [Hm, N]
            Ch = jnp.repeat(Ct, per, axis=0)
            S = (S * jnp.exp(dtt * A)[:, None, None]
                 + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :])
            return S, jnp.einsum("hpn,hn->hp", S, Ch, precision="highest")

        _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), jnp.float32),
                            (x, Bm, Cm, dt))
        y = (y + lw["D"][:, None] * x).reshape(T, Hm * P) * jax.nn.silu(z)
        yg = y.reshape(T, G, Hm * P // G)
        yg = yg * jax.lax.rsqrt((yg * yg).mean(-1, keepdims=True) + eps)
        mix = mm(yg.reshape(T, Hm * P) * lw["mnorm_g"], lw["w_mout"])
        h = h + attn * f32(w["m_attn_out"]) + mix * f32(w["m_ssm_out"])
        # MLP
        f = _rms(h, lw["ln2_g"], eps)
        g = jax.nn.silu(mm(f, lw["w_gate"]) * m_mlp[0]) * mm(f, lw["w_up"])
        return h + mm(g, lw["w_down"]) * m_mlp[1], None

    h, _ = jax.lax.scan(layer, h, {n: w[n] for n in LAYER_NAMES})
    return h


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: attention,
    convolution and recurrence are all causal)."""
    h = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[at]
    h = _rms(h, jnp.asarray(w["lnf_g"], jnp.float32), eps)
    return (_mm(h, jnp.asarray(w["lm_head"], jnp.float32), precision)
            * jnp.asarray(w["m_lm_head"], jnp.float32))
