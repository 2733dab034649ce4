"""The plain reference for the ``mimo_v2`` family (MiMo-V2-Flash): the
published forward pass in straightforward ``jax.numpy``. Float32, every
product at precision ``highest``; attention as full ``[T, T]`` scores a head
with the causal mask and the window's band written as masks, the sink as an
extra column of the softmax that is dropped; the experts as a masked sum
over the experts HELD (every held expert multiplies every token, the gates
pick); no cache, no pages, no ring, no sorting, no kernel; the weights are
an argument. It imports nothing of the program, and it never follows the
program's routing: it routes by its own float32 scores.

    x = wte[ids]
    per layer l:   h = x + Attn_l(RMSNorm(x));  x = h + FFN_l(RMSNorm(h))
    logits = RMSNorm(x; lnf_g) lm_head

    Attn_l(u): q = u Wq as H heads of dk;  k = u Wk as kv heads of dk;
               v = u Wv as kv heads of dv;  kv = 4 (full) or 8 (window)
               rotary on the first ``rot`` lanes of each q and k head
               (rotate-half pairs within them), base theta (full) or
               swa_theta (window); the other lanes pass
               s[t, j] = q_h[t] . k_{h // (H / kv)}[j] / sqrt(dk),  j <= t,
                         and in a window layer j > t - window
               full:    p = softmax_j(s)
               window:  p = softmax over [s | b_h], the sink's column dropped
               out = value_scale * concat_h(p v) Wo
    FFN_l(u):  layer 0:  (silu(u Wg) * (u Wu)) Wd
               else:     sigma = sigmoid(u Wr);  T = the top_k largest of
                         sigma + b;  g_i = sigma_i / sum_{j in T} sigma_j
                         out = sum_{i in T, first <= i < first + held} g_i E_i(u)

**The share.** The weights hold ``held`` experts a layer (``e_gate`` [layers,
held, E, w]), the router all its outputs; the held experts are router outputs
``first .. first + held - 1``. A choice of an expert outside that range adds
nothing, here as in the program: the result is this chip's part of the
layer, and that partial stream is what the next layer reads.

The sizes come from the weights' shapes (``n_head`` alone is an argument, as
the check passes it). What differs by layer is said by the structure of the
dict, which is static under ``jit``: ``swa_layers`` and ``moe_layers`` are
lists with one array a layer whose LENGTH is 1 where the layer is a window
layer / an expert layer and 0 where it is not; ``f_*`` are stacked over the
full layers, ``s_*`` over the window layers, ``d_*`` over the dense layers,
``w_r`` / ``b_r`` / ``e_*`` over the expert layers, everything else over all
layers. ``window``, ``rot``, ``top_k`` and ``first`` are the lengths of
``window_slots``, ``rotary_slots``, ``topk_slots``, ``first_slots``;
``rope_theta``, ``swa_rope_theta`` and ``value_scale`` ride in the dict as
scalars. Weights may arrive in a narrower type: each layer's are upcast to
float32 as the layer runs. What the ``config`` alone does not settle is the
configuration file's ``assumed``.

``precision`` chooses the arithmetic of every product, as in
``reference/gpt2.py``: ``"float32"`` is the reference; the others round both
operands of every product (the router's among them) to a lower type first
and are the controls that ``correct`` has to fail. **Three controls leave a
mechanism out**, everything else in float32 (``benchmark/probe_control.py``
puts one in ``lower_precision_control``'s place): ``window_off`` (a window
layer attends to every key before it), ``sink_off`` (no sink in a window
layer's softmax), ``held_zero`` (the held experts' part of the sum left
out): the faults a missing lower clamp, a dropped ``finalize`` term and a
wrong grouped product on this chip would be."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .glm_moe_lite import _rms
from .gpt2 import PRECISIONS, _mm, _round  # noqa: F401  (one rounding rule)

CONTROLS = ("window_off", "sink_off", "held_zero")


def _split(precision: str) -> tuple:
    """(the mechanism left out or None, the precision of every product)."""
    if precision in CONTROLS:
        return precision, "float32"
    return None, precision


def _rope(x, theta, rot: int):
    """x [T, heads, d], position t at row t: rotate-half pairs within the
    first ``rot`` lanes, frequencies over ``rot``; the others pass."""
    T = x.shape[0]
    freq = jnp.exp(-jnp.log(theta)
                   * jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], -1)


def hidden(w: dict, ids, *, n_head: int, eps: float, precision: str):
    """ids [T] -> (the residual stream after the last layer, [T, E]; the
    router outputs each token chose in each expert layer, [layers, T,
    top_k])."""
    T, H = ids.shape[0], n_head
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    left_out, precision = _split(precision)
    mm = lambda a, m: _mm(a, m, precision)
    x = f32(w["wte"][ids])
    window, rot, top_k, first = (w[k].shape[0] for k in (
        "window_slots", "rotary_slots", "topk_slots", "first_slots"))
    t = jnp.arange(T)
    causal = t[None, :] <= t[:, None]
    band = causal & (t[None, :] > t[:, None] - window)
    value_scale = f32(w["value_scale"])

    def attention(u, wq, wk, wv, wo, theta, mask, sink):
        dk, dv = wq.shape[-1] // H, wo.shape[0] // H
        kv = wk.shape[-1] // dk
        q = _rope(mm(u, wq).reshape(T, H, dk), theta, rot)
        k = _rope(mm(u, wk).reshape(T, kv, dk), theta, rot)
        v = mm(u, wv).reshape(T, kv, dv)
        # query head h reads K/V head h // (H / kv)
        k, v = (jnp.repeat(a, H // kv, axis=1) for a in (k, v))

        def head(qkvb):
            q, k, v, b = qkvb                                  # [T, .]
            s = jnp.matmul(_round(q, -1, precision),
                           _round(k, -1, precision).T,
                           precision="highest") / jnp.sqrt(float(dk))
            s = jnp.where(mask, s, -jnp.inf)
            if sink is not None:
                # one more column, the head's learned logit, then dropped
                s = jnp.concatenate([s, jnp.full((T, 1), b)], axis=-1)
            p = jax.nn.softmax(s, axis=-1)[:, :T]
            return jnp.matmul(_round(p, -1, precision),
                              _round(v, 0, precision), precision="highest")

        o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2),
                               jnp.zeros((H,)) if sink is None else sink))
        return mm(value_scale * o.transpose(1, 0, 2).reshape(T, H * dv), wo)

    def swiglu(f, gate, up, down):
        return mm(jax.nn.silu(mm(f, gate)) * mm(f, up), down)

    def experts(u, w_r, b_r, held):
        sigma = jax.nn.sigmoid(mm(u, w_r))                     # [T, G]
        G = sigma.shape[-1]
        _, chosen = jax.lax.top_k(sigma + b_r, top_k)
        picked = (chosen[:, :, None]
                  == jnp.arange(G)[None, None, :]).any(axis=1)
        gates = jnp.where(picked, sigma, 0.0)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        n_held = held["e_gate"].shape[0]
        if left_out == "held_zero":
            return jnp.zeros_like(u), chosen

        def one(acc, ew):
            e, g = ew
            y = swiglu(u, f32(e["e_gate"]), f32(e["e_up"]), f32(e["e_down"]))
            return acc + g[:, None] * y, None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(u), (held, gates[:, first:first + n_held].T))
        return routed, chosen

    at = {"f": 0, "s": 0, "d": 0, "e": 0}   # the next layer of each kind
    routes = []
    for l, (swa, moe) in enumerate(zip(w["swa_layers"], w["moe_layers"])):
        u = _rms(x, f32(w["ln1_g"][l]), eps)
        if swa.shape[0]:
            i, at["s"] = at["s"], at["s"] + 1
            x = x + attention(
                u, f32(w["w_q"][l]), f32(w["s_wk"][i]), f32(w["s_wv"][i]),
                f32(w["wo"][l]), f32(w["swa_rope_theta"]),
                causal if left_out == "window_off" else band,
                None if left_out == "sink_off" else f32(w["s_sink"][i]))
        else:
            i, at["f"] = at["f"], at["f"] + 1
            x = x + attention(
                u, f32(w["w_q"][l]), f32(w["f_wk"][i]), f32(w["f_wv"][i]),
                f32(w["wo"][l]), f32(w["rope_theta"]), causal, None)
        u = _rms(x, f32(w["ln2_g"][l]), eps)
        if moe.shape[0]:
            i, at["e"] = at["e"], at["e"] + 1
            y, chosen = experts(
                u, f32(w["w_r"][i]), f32(w["b_r"][i]),
                {k: w[k][i] for k in ("e_gate", "e_up", "e_down")})
            routes.append(chosen)
            x = x + y
        else:
            i, at["d"] = at["d"], at["d"] + 1
            x = x + swiglu(u, f32(w["d_gate"][i]), f32(w["d_up"][i]),
                           f32(w["d_down"][i]))
    return x, jnp.stack(routes)


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
    """The router outputs each position of ``ids`` [T] chose, [expert
    layers, T, top_k] (unordered within a token)."""
    return hidden(w, ids, n_head=n_head, eps=eps, precision=precision)[1]
