"""The plain reference for the ``xing4_0`` family (Xing4.0-29B-A4B): the
DeepSeek-V3 block (latent attention, dense SwiGLU layers, then routed experts
with a shared one) on a residual path of ``n`` streams mixed by manifold-
constrained hyper-connections (arXiv:2512.24880), with YaRN rotary. Straight-
forward ``jax.numpy``, float32, every product at precision ``highest``;
attention in the expanded form over the whole sequence (one head at a time,
so that 32 heads' 4096 x 4096 scores fit beside the weights); the experts a
masked sum over ALL of them; the Sinkhorn loop a Python loop; no cache, no
pages, no sorting, no kernel; the weights are an argument. It imports nothing
of the program and routes by its own float32 scores.

    X = (wte[ids],) * n                                   # [T, n, E]
    per layer, for each of its two sub-layers F (attention, then the
    feed-forward part), with the sub-layer's own phi, alpha, bias and gain g:
      xs          = vec(X) * rsqrt(mean(vec(X)^2) + eps)            # no gain
      [p | q | r] = xs phi                                          # 2n + n n
      pre  = sigmoid(alpha[0] p + bias[:n]);  post = 2 sigmoid(alpha[1] q + bias[n:2n])
      Z    = clip(alpha[2] mat(r) + mat(bias[2n:]), -clamp, clamp)
      M    = exp(Z - max(Z));  iters times: M /= rowsum + hc_eps; M /= colsum + hc_eps
      u    = sum_i pre[i] X[i];   y = F(RMSNorm(u; g))
      X[i] = sum_j M[i, j] X[j] + post[i] y
    attention F(f):
      cq = RMSNorm(f Wdq; q_norm_g);  q = (cq Wuq) as H heads of [q_nope dn | q_rope dr]
      [ckv | kr] = f Wdkv;  ckv = RMSNorm(ckv; kv_norm_g);  kr = RoPE(kr)
      [k_nope_h dn | v_h dv] = ckv Wukv, per head h
      s_h[t,j] = (q_nope_h[t].k_nope_h[j] + RoPE(q_rope_h)[t].kr[j]) * m^2 / sqrt(dn + dr),  j <= t
      F = concat_h(softmax(s_h) v_h) Wo
      RoPE: rotate-half pairs at YaRN's frequencies inv_i / factor * ramp_i +
      inv_i (1 - ramp_i), inv_i = theta^(-2i/dr), ramp_i = clip((i - low) /
      (high - low), 0, 1), low / high = floor / ceil of dr ln(orig / (beta 2 pi))
      / (2 ln theta) at beta_fast / beta_slow; m = 0.1 mscale_all_dim ln(factor) + 1
    a dense layer's F(f):   (silu(f Wg) * (f Wu)) Wd
    an expert layer's F(f): s = sigmoid(f Wr);  T = the top_k largest of s + b
                            g_i = scale * s_i / (sum_{j in T} s_j + 1e-20), i in T
                            sum_{i in T} g_i E_i(f) + E_shared(f)
    logits = RMSNorm(sum_i X[i]; lnf_g) lm_head

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it), as ``reference/glm_moe_lite.py`` reads them; ``n`` is
``hc1_phi``'s rows over ``E``, ``top_k`` the length of ``topk_slots`` and the
Sinkhorn count the length of ``sinkhorn_slots``. ``rope_theta``,
``routed_scale``, ``hc_eps``, ``hc_clamp`` ride in the dict as scalars and
``yarn`` as ``[factor, beta_fast, beta_slow, mscale, mscale_all_dim,
original_max_position_embeddings]``. Weights may arrive in bfloat16: each
layer's are widened to float32 inside the scan over layers, one layer (and
inside an expert layer one expert) at a time. What the ``config`` alone does
not settle is the configuration file's ``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product (the maps' projection and the router's among them)
to a lower type first and are the controls that ``correct`` has to fail."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)

HC_NAMES = tuple(f"hc{k}_{p}" for k in (1, 2)
                 for p in ("phi", "alpha", "bias"))
ATTN_NAMES = ("ln1_g", "w_dq", "q_norm_g", "w_uq", "w_dkv", "kv_norm_g",
              "w_ukv", "wo", "ln2_g") + HC_NAMES
DENSE_NAMES = ("d_gate", "d_up", "d_down")
EXPERT_NAMES = ("w_r", "b_r", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                "s_down")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def yarn_frequencies(d: int, theta, yarn):
    """The d / 2 pair frequencies and (the softmax scale's factor m, the
    tables' factor) from ``yarn`` = [factor, beta_fast, beta_slow, mscale,
    mscale_all_dim, original_max_position_embeddings]."""
    factor, fast, slow, mscale, mscale_all, orig = (yarn[i] for i in range(6))
    i = jnp.arange(0, d // 2, dtype=jnp.float32)
    inv = jnp.exp(-jnp.log(theta) * 2.0 * i / d)
    pair = lambda turns: (d * jnp.log(orig / (turns * 2.0 * jnp.pi))
                          / (2.0 * jnp.log(theta)))
    low = jnp.clip(jnp.floor(pair(fast)), 0, d - 1)
    high = jnp.clip(jnp.ceil(pair(slow)), 0, d - 1)
    ramp = jnp.clip((i - low) / jnp.maximum(high - low, 1e-3), 0.0, 1.0)
    m = lambda s: 0.1 * s * jnp.log(factor) + 1.0
    return (inv / factor * ramp + inv * (1.0 - ramp), m(mscale_all),
            m(mscale) / m(mscale_all))


def _rope(x, freq, table_scale):
    """x [T, heads, d], position t at row t; pairs (x[:d/2], x[d/2:])."""
    T, _, d = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[:, None, :] * table_scale
    sin = jnp.sin(ang)[:, None, :] * table_scale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hc_maps(X, phi, alpha, bias, *, eps, hc_eps, clamp, iters: int,
            precision: str):
    """The streams X [T, n, E] -> (pre [T, n], post [T, n], M [T, n, n])."""
    T, n, E = X.shape
    v = X.reshape(T, n * E)
    xs = v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True) + eps)
    prj = _mm(xs, phi, precision)
    pre = jax.nn.sigmoid(alpha[0] * prj[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * prj[:, n:2 * n] + bias[n:2 * n])
    Z = jnp.clip(alpha[2] * prj[:, 2 * n:] + bias[2 * n:], -clamp, clamp)
    M = jnp.exp(Z - Z.max(-1, keepdims=True)).reshape(T, n, n)
    for _ in range(iters):
        M = M / (M.sum(axis=2, keepdims=True) + hc_eps)    # rows
        M = M / (M.sum(axis=1, keepdims=True) + hc_eps)    # columns
    return pre, post, M


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> (the streams after the last layer, [T, n, E]; the experts
    each token chose in each expert layer, [layers, T, top_k])."""
    T, H = ids.shape[0], n_head
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    mm = lambda a, m: _mm(a, m, precision)
    rnd = lambda a: _round(a, -1, precision)
    e = f32(w["wte"][ids])
    n = w["hc1_phi"].shape[1] // e.shape[1]
    X = jnp.broadcast_to(e[:, None, :], (T, n, e.shape[1]))
    causal = jnp.tril(jnp.ones((T, T), bool))
    theta, scale = f32(w["rope_theta"]), f32(w["routed_scale"])
    top_k = w["topk_slots"].shape[0]
    hc = dict(eps=eps, hc_eps=f32(w["hc_eps"]), clamp=f32(w["hc_clamp"]),
              iters=w["sinkhorn_slots"].shape[0], precision=precision)

    def sublayer(X, lw, k, gain, branch):
        """One sub-layer around ``branch``; returns the new streams and
        whatever else the branch hands back."""
        pre, post, M = hc_maps(X, lw[f"{k}_phi"], lw[f"{k}_alpha"],
                               lw[f"{k}_bias"], **hc)
        u = jnp.einsum("tn,tne->te", rnd(pre), rnd(X), precision="highest")
        y, extra = branch(_rms(u, gain, eps))
        X = (jnp.einsum("tij,tje->tie", rnd(M), rnd(X), precision="highest")
             + post[:, :, None] * y[:, None, :])
        return X, extra

    def attention(f, lw):
        dc = lw["kv_norm_g"].shape[-1]
        dr = lw["w_dkv"].shape[-1] - dc
        dn = lw["w_uq"].shape[-1] // H - dr
        dv = lw["w_ukv"].shape[-1] // H - dn
        freq, m, table = yarn_frequencies(dr, theta, f32(w["yarn"]))
        cq = _rms(mm(f, lw["w_dq"]), lw["q_norm_g"], eps)
        q = mm(cq, lw["w_uq"]).reshape(T, H, dn + dr)
        ckr = mm(f, lw["w_dkv"])
        ckv = _rms(ckr[:, :dc], lw["kv_norm_g"], eps)
        kr = _rope(ckr[:, None, dc:], freq, table)             # [T, 1, dr]
        kv = mm(ckv, lw["w_ukv"]).reshape(T, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], freq, table)],
                            -1)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(kr, (T, H, dr))], -1)
        v = kv[..., dn:]

        def head(qkv):
            qh, kh, vh = qkv                                   # [T, .]
            s = jnp.einsum("td,sd->ts", rnd(qh), rnd(kh),
                           precision="highest") * (
                               m * m / jnp.sqrt(float(dn + dr)))
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("ts,sd->td", rnd(p), _round(vh, 0, precision),
                              precision="highest")

        o = jax.lax.map(head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
        return mm(o.transpose(1, 0, 2).reshape(T, H * dv), lw["wo"]), None

    def swiglu(f, gate, up, down):
        return mm(jax.nn.silu(mm(f, gate)) * mm(f, up), down)

    def dense_layer(X, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        X, _ = sublayer(X, lw, "hc1", lw["ln1_g"],
                        lambda f: attention(f, lw))
        X, _ = sublayer(X, lw, "hc2", lw["ln2_g"], lambda f: (
            swiglu(f, lw["d_gate"], lw["d_up"], lw["d_down"]), None))
        return X, None

    def expert_layer(X, lw):
        experts = {k: lw[k] for k in ("e_gate", "e_up", "e_down")}
        lw = {k: f32(v) for k, v in lw.items() if k not in experts}

        def routed(f):
            s = jax.nn.sigmoid(mm(f, lw["w_r"]))               # [T, G]
            _, chosen = jax.lax.top_k(s + lw["b_r"], top_k)
            picked = (chosen[:, :, None]
                      == jnp.arange(s.shape[-1])[None, None, :]).any(axis=1)
            total = jnp.where(picked, s, 0.0).sum(-1, keepdims=True)
            gates = jnp.where(picked, scale * s / (total + 1e-20), 0.0)

            def one(acc, ew):
                ex, g = ew
                y = swiglu(f, f32(ex["e_gate"]), f32(ex["e_up"]),
                           f32(ex["e_down"]))
                return acc + g[:, None] * y, None

            out, _ = jax.lax.scan(one, jnp.zeros_like(f), (experts, gates.T))
            return out + swiglu(f, lw["s_gate"], lw["s_up"],
                                lw["s_down"]), chosen

        X, _ = sublayer(X, lw, "hc1", lw["ln1_g"],
                        lambda f: attention(f, lw))
        return sublayer(X, lw, "hc2", lw["ln2_g"], routed)

    n_dense = w["d_gate"].shape[0]
    take = lambda names, sl: {k: w[k][sl] for k in names}
    X, _ = jax.lax.scan(dense_layer, X, {
        **take(ATTN_NAMES, slice(0, n_dense)),
        **take(DENSE_NAMES, slice(None))})
    return jax.lax.scan(expert_layer, X, {
        **take(ATTN_NAMES, slice(n_dense, None)),
        **take(EXPERT_NAMES, slice(None))})


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: attention
    is causal, the experts and the residual maps work token by token)."""
    X = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[0][at]
    h = _rms(X.sum(axis=1), jnp.asarray(w["lnf_g"], jnp.float32), eps)
    return _mm(h, jnp.asarray(w["lm_head"], jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def routing(w: dict, ids, *, n_head: int, eps: float,
            precision: str = "float32"):
    """The experts each position of ``ids`` [T] chose, [expert layers, T,
    top_k] (unordered within a token)."""
    return hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[1]
