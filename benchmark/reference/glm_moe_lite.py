"""The plain reference for the ``glm4_moe_lite`` family (GLM-4.7-Flash): the
published forward pass in straightforward ``jax.numpy``. Float32, every
product at precision ``highest``; attention in the expanded form over the
whole sequence; the experts as a masked sum over ALL of them (every expert
multiplies every token, the gates pick); no cache, no pages, no sorting, no
kernel; the weights are an argument. It imports nothing of the program, and
it never follows the program's routing: it routes by its own float32 scores.

    h = wte[ids]
    per layer:
      u  = RMSNorm(h; ln1_g)
      cq = RMSNorm(u Wdq; q_norm_g)
      q  = (cq Wuq) as H heads of [q_nope dn | q_rope dr]
      [ckv | kr] = u Wdkv;  ckv = RMSNorm(ckv; kv_norm_g);  kr = RoPE(kr)
      [k_nope_h dn | v_h dv] = ckv Wukv, per head h
      s_h[t,j] = (q_nope_h[t].k_nope_h[j] + RoPE(q_rope_h)[t].kr[j]) / sqrt(dn + dr),  j <= t
      h += concat_h(softmax(s_h) v_h) Wo
      f  = RMSNorm(h; ln2_g)
      a dense layer:   h += (silu(f Wg) * (f Wu)) Wd
      an expert layer: s = sigmoid(f Wr);  T = the top_k largest of s + b
                       g_i = scale * s_i / (sum_{j in T} s_j + 1e-20), i in T
                       h += sum_{i in T} g_i E_i(f) + E_shared(f)
    logits = RMSNorm(h; lnf_g) lm_head

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it): ``w_dkv`` [n, E, dc + dr] with ``kv_norm_g`` [n, dc],
``w_uq`` [n, rq, H (dn + dr)], ``w_ukv`` [n, dc, H (dn + dv)]; the dense
layers' ``d_*`` arrays lead the stack and the expert layers' ``e_*`` / ``s_*``
/ ``w_r`` / ``b_r`` follow; ``top_k`` is the length of ``topk_slots``.
``rope_theta`` and ``routed_scale`` ride in the dict as scalars. Weights may
arrive in a narrower type (the configuration holds them in bfloat16): each
layer's are upcast to float32 inside the scan over layers, one layer (and
inside an expert layer one expert) at a time, so the replay fits beside the
stored weights. What the ``config`` alone does not settle is the
configuration file's ``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product (the router's among them) to a lower type first
and are the controls that ``correct`` has to fail."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)

ATTN_NAMES = ("ln1_g", "w_dq", "q_norm_g", "w_uq", "w_dkv", "kv_norm_g",
              "w_ukv", "wo", "ln2_g")
DENSE_NAMES = ("d_gate", "d_up", "d_down")
EXPERT_NAMES = ("w_r", "b_r", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                "s_down")


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


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> (the residual stream after the last layer, [T, E]; the
    experts each token chose in each expert layer, [layers, T, top_k])."""
    T, H = ids.shape[0], n_head
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    mm = lambda a, m: _mm(a, m, precision)
    h = f32(w["wte"][ids])
    causal = jnp.tril(jnp.ones((T, T), bool))
    theta, scale = f32(w["rope_theta"]), f32(w["routed_scale"])
    top_k = w["topk_slots"].shape[0]

    def attention(h, lw):
        u = _rms(h, lw["ln1_g"], eps)
        dc = lw["kv_norm_g"].shape[-1]
        dr = lw["w_dkv"].shape[-1] - dc
        dn = lw["w_uq"].shape[-1] // H - dr
        dv = lw["w_ukv"].shape[-1] // H - dn
        cq = _rms(mm(u, lw["w_dq"]), lw["q_norm_g"], eps)
        q = mm(cq, lw["w_uq"]).reshape(T, H, dn + dr)
        ckr = mm(u, lw["w_dkv"])
        ckv = _rms(ckr[:, :dc], lw["kv_norm_g"], eps)
        kr = _rope(ckr[:, None, dc:], theta)                  # [T, 1, dr]
        kv = mm(ckv, lw["w_ukv"]).reshape(T, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(kr, (T, H, dr))], -1)
        v = kv[..., dn:]
        q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))
        s = jnp.einsum("htd,hsd->hts", _round(q, -1, precision),
                       _round(k, -1, precision),
                       precision="highest") / jnp.sqrt(float(dn + dr))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,hsd->htd", _round(p, -1, precision),
                       _round(v, 1, precision), precision="highest")
        h = h + mm(o.transpose(1, 0, 2).reshape(T, H * dv), lw["wo"])
        return h, _rms(h, lw["ln2_g"], eps)

    def swiglu(f, gate, up, down):
        return mm(jax.nn.silu(mm(f, gate)) * mm(f, up), down)

    def dense_layer(h, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        h, f = attention(h, lw)
        return h + swiglu(f, lw["d_gate"], lw["d_up"], lw["d_down"]), None

    def expert_layer(h, lw):
        experts = {k: lw[k] for k in ("e_gate", "e_up", "e_down")}
        lw = {k: f32(v) for k, v in lw.items() if k not in experts}
        h, f = attention(h, lw)
        s = jax.nn.sigmoid(mm(f, lw["w_r"]))                   # [T, G]
        _, chosen = jax.lax.top_k(s + lw["b_r"], top_k)
        picked = (chosen[:, :, None]
                  == jnp.arange(s.shape[-1])[None, None, :]).any(axis=1)
        total = jnp.where(picked, s, 0.0).sum(-1, keepdims=True)
        gates = jnp.where(picked, scale * s / (total + 1e-20), 0.0)

        def one(acc, ew):
            e, g = ew
            y = swiglu(f, f32(e["e_gate"]), f32(e["e_up"]), f32(e["e_down"]))
            return acc + g[:, None] * y, None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (experts, gates.T))
        return h + routed + swiglu(f, lw["s_gate"], lw["s_up"],
                                   lw["s_down"]), chosen

    n_dense = w["d_gate"].shape[0]
    take = lambda names, sl: {n: w[n][sl] for n in names}
    h, _ = jax.lax.scan(dense_layer, h, {
        **take(ATTN_NAMES, slice(0, n_dense)),
        **take(DENSE_NAMES, slice(None))})
    return jax.lax.scan(expert_layer, h, {
        **take(ATTN_NAMES, slice(n_dense, None)),
        **take(EXPERT_NAMES, slice(None))})


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: attention
    is causal and the experts work token by token)."""
    h = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[0][at]
    h = _rms(h, jnp.asarray(w["lnf_g"], jnp.float32), eps)
    return _mm(h, jnp.asarray(w["lm_head"], jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def routing(w: dict, ids, *, n_head: int, eps: float,
            precision: str = "float32"):
    """The experts each position of ``ids`` [T] chose, [expert layers, T,
    top_k] (unordered within a token): how often a lower ``precision`` flips
    a choice is what decides how far its logits can stray (PERF.md, PR 32)."""
    return hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[1]
