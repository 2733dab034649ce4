"""The plain reference for the ``longcat_flash`` family (LongCat-Flash, the
language model of LongCat-Flash-Omni): the published forward pass in
straightforward ``jax.numpy``. Float32, every product at precision
``highest``; attention in the expanded form over the whole sequence, a head
at a time; the experts as a masked sum over the experts HELD (every held
expert multiplies every token, the gates pick); no cache, no pages, no
sorting, no kernel; the weights are an argument. It imports nothing of the
program, and it never follows the program's routing: it routes by its own
float32 scores.

    h = wte[ids]
    per block (a double layer):
      h += MLA_0(RMSNorm(h));  u = RMSNorm(h);  s = MoE(u)
      h += SwiGLU_0(u)
      h += MLA_1(RMSNorm(h));  h += SwiGLU_1(RMSNorm(h))
      h += s
    logits = RMSNorm(h; lnf_g) lm_head

    MLA(x):  cq = RMSNorm(x Wdq; q_norm_g) * sqrt(E / rq)
             q  = (cq Wuq) as H heads of [q_nope dn | q_rope dr]
             [c | r] = x Wdkv;  c = RMSNorm(c; kv_norm_g) * sqrt(E / dc);  r = RoPE(r)
             [k_nope_h dn | v_h dv] = c Wukv, per head h
             s_h[t,j] = (q_nope_h[t].k_nope_h[j] + RoPE(q_rope_h)[t].r[j]) / sqrt(dn + dr),  j <= t
             out = concat_h(softmax(s_h) v_h) Wo
    MoE(u):  p = softmax(u Wr) over G + Z outputs;  T = the top_k largest of p + b
             g_i = scale * p_i, i in T            (not normalised over T)
             out = sum_{i in T, first <= i < first + held} g_i E_i(u)
                 + (sum_{i in T, i >= G} g_i) u   (the Z identity experts)

**The share.** The weights hold ``held`` experts a layer (``e_gate`` [n,
held, E, w]), the router all ``G + Z`` outputs; the held experts are router
outputs ``first .. first + held - 1``. A choice of a routed expert outside
that range adds nothing, here as in the program: the result is this chip's
part of the layer, and that partial stream is what the next block reads.

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it): the attention's and the dense SwiGLUs' arrays are
stacked over the ``2 n`` sub-layers (block b's are rows 2b and 2b + 1), the
experts' over the ``n`` blocks; ``top_k`` is the length of ``topk_slots``,
``Z`` of ``zero_slots``, ``first`` of ``first_slots``. ``rope_theta`` and
``routed_scale`` ride in the dict as scalars. Weights may arrive in a
narrower type: each sub-layer's are upcast to float32 inside the scan over
blocks, one at a time. What the ``config`` alone does not settle is the
configuration file's ``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product (the router's among them) to a lower type first
and are the controls that ``correct`` has to fail. **A control on the held
experts alone** is ``"held_" + how``: everything in float32 but the held
experts' part of the sum, which is left out (``held_zero``) or whose three
products are made in a lower type (``held_fp8_e4m3``): the fault a wrong
grouped product on this chip would be, and what ``correct`` has to see of
the mechanism this configuration exists for (``benchmark/probe_control.py``
puts one in ``lower_precision_control``'s place)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .glm_moe_lite import _rms, _rope
from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)

HELD = "held_"   # prefix of a control on the held experts' part alone
SUB_NAMES = ("ln1_g", "w_dq", "q_norm_g", "w_uq", "w_dkv", "kv_norm_g",
             "w_ukv", "wo", "ln2_g", "d_gate", "d_up", "d_down")
EXPERT_NAMES = ("w_r", "b_r", "e_gate", "e_up", "e_down")


def _split(precision: str) -> tuple:
    """(how the held experts' part is made, the precision of every other
    product)."""
    if precision.startswith(HELD):
        return precision[len(HELD):], "float32"
    return precision, precision


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> (the residual stream after the last block, [T, E]; the
    router outputs each token chose in each block, [blocks, T, top_k])."""
    T, H = ids.shape[0], n_head
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    held_as, precision = _split(precision)
    mm = lambda a, m: _mm(a, m, precision)
    h = f32(w["wte"][ids])
    E = h.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    theta, scale = f32(w["rope_theta"]), f32(w["routed_scale"])
    top_k, Z, first = (w[k].shape[0] for k in
                       ("topk_slots", "zero_slots", "first_slots"))

    def attention(h, lw):
        u = _rms(h, lw["ln1_g"], eps)
        rq, dc = lw["q_norm_g"].shape[-1], lw["kv_norm_g"].shape[-1]
        dr = lw["w_dkv"].shape[-1] - dc
        dn = lw["w_uq"].shape[-1] // H - dr
        dv = lw["w_ukv"].shape[-1] // H - dn
        cq = _rms(mm(u, lw["w_dq"]), lw["q_norm_g"], eps) * (E / rq) ** 0.5
        q = mm(cq, lw["w_uq"]).reshape(T, H, dn + dr)
        ckr = mm(u, lw["w_dkv"])
        c = _rms(ckr[:, :dc], lw["kv_norm_g"], eps) * (E / dc) ** 0.5
        kr = _rope(ckr[:, None, dc:], theta)[:, 0]            # [T, dr]
        kv = mm(c, lw["w_ukv"]).reshape(T, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)

        def head(qkv):
            q, kv = qkv                                        # [T, .]
            k = jnp.concatenate([kv[:, :dn], kr], -1)
            s = jnp.matmul(_round(q, -1, precision),
                           _round(k, -1, precision).T,
                           precision="highest") / jnp.sqrt(float(dn + dr))
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.matmul(_round(p, -1, precision),
                              _round(kv[:, dn:], 0, precision),
                              precision="highest")

        o = jax.lax.map(head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
        h = h + mm(o.transpose(1, 0, 2).reshape(T, H * dv), lw["wo"])
        return h, _rms(h, lw["ln2_g"], eps)

    def swiglu(f, gate, up, down, mm=mm):
        return mm(jax.nn.silu(mm(f, gate)) * mm(f, up), down)

    def experts(u, lw, held):
        p = jax.nn.softmax(mm(u, lw["w_r"]), axis=-1)          # [T, G + Z]
        G = p.shape[-1] - Z
        _, chosen = jax.lax.top_k(p + lw["b_r"], top_k)
        picked = (chosen[:, :, None]
                  == jnp.arange(G + Z)[None, None, :]).any(axis=1)
        gates = jnp.where(picked, scale * p, 0.0)
        n_held = held["e_gate"].shape[0]

        def one(acc, ew):
            e, g = ew
            if held_as == "zero":
                return acc, None
            y = swiglu(u, f32(e["e_gate"]), f32(e["e_up"]), f32(e["e_down"]),
                       lambda a, m: _mm(a, m, held_as))
            return acc + g[:, None] * y, None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(u),
            (held, gates[:, first:first + n_held].T))
        return routed + gates[:, G:].sum(-1, keepdims=True) * u, chosen

    def block(h, bw):
        sub = lambda j: {k: f32(bw[k][j]) for k in SUB_NAMES}
        lw = sub(0)
        h, u = attention(h, lw)
        s, chosen = experts(
            u, {k: f32(bw[k]) for k in ("w_r", "b_r")},
            {k: bw[k] for k in ("e_gate", "e_up", "e_down")})
        h = h + swiglu(u, lw["d_gate"], lw["d_up"], lw["d_down"])
        lw = sub(1)
        h, u = attention(h, lw)
        h = h + swiglu(u, lw["d_gate"], lw["d_up"], lw["d_down"])
        return h + s, chosen

    n = w["w_r"].shape[0]
    return jax.lax.scan(block, h, {
        **{k: w[k].reshape((n, 2) + w[k].shape[1:]) for k in SUB_NAMES},
        **{k: w[k] for k in EXPERT_NAMES}})


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_at(w: dict, ids, at, *, n_head: int, eps: float,
              precision: str = "float32"):
    """Logits [len(at), V] at positions ``at`` of the sequence ``ids`` [T]
    (right padding after the last position asked for is harmless: attention
    is causal and the experts work token by token)."""
    h = hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[0][at]
    h = _rms(h, jnp.asarray(w["lnf_g"], jnp.float32), eps)
    return _mm(h, jnp.asarray(w["lm_head"], jnp.float32),
               _split(precision)[1])


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def routing(w: dict, ids, *, n_head: int, eps: float,
            precision: str = "float32"):
    """The router outputs each position of ``ids`` [T] chose, [blocks, T,
    top_k] (unordered within a token; ``>= G`` is an identity expert)."""
    return hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[1]
