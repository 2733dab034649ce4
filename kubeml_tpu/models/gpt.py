"""Causal transformer LM — the long-context / multi-axis-parallel flagship.

No counterpart in the reference (CNNs only; SURVEY §5 long-context: absent).
Every weight is annotated with ``nn.with_partitioning`` mesh-axis names so
``nn.get_partition_spec`` yields the tensor-parallel sharding directly
(megatron-style: qkv/mlp-in column-sharded over ``tp``, proj/mlp-out
row-sharded; XLA inserts the psum on the row-sharded matmuls). Attention runs
as ring attention over the ``sp`` axis when a mesh with sp > 1 is attached
(jax.shard_map inside jit), else as plain full attention.

``dtype`` is the computation dtype (bf16 compute / f32 params mixed precision):
matmuls run in ``dtype``, the norms and attention softmax stay f32, parameters
are initialised and trained f32, and logits are returned f32 for the loss. A
server holds in ``dtype`` every parameter this file only ever casts to it (the
products' kernels and biases; the norms' and both embeddings' f32 values are
used), or all in ``Config.serving_param_dtype`` (docs/design.md §26).

One block, configured: LayerNorm or RMSNorm; a GELU MLP at a ratio, SwiGLU at
a width, or routed experts with a shared one (models/experts.py); multi-head
or grouped-query attention at a head size of its own, or multi-head latent
attention over a latent cache (models/mla.py); learned or rotary positions;
optionally a Mamba-2 mixer in parallel with attention (models/mamba2.py) and
a muP checkpoint's multipliers. The stack may be a pattern: ``dense_layers``
leading SwiGLU layers before the expert layers. The residual path is a kind
too: one stream and ``x = x + y``, or ``hc_mult`` streams that every sub-layer
reads, writes and mixes through maps made from the token's own streams
(ops/hyper_connection.py). And the layer itself may be LongCat-Flash's double
layer (``mlp="shortcut"``, :class:`ShortcutBlock`): two attentions, two dense
SwiGLUs, and an expert layer on a shortcut around the second pair. And the ATTENTION may differ by layer (``attn_kinds``
/ ``attn_pattern``, :class:`AttnKind`): full layers beside window layers,
each kind with its own K/V head count, rotary base, window and sink, over K
heads and V heads of different widths with rotary on a share of a head
(MiMo-V2-Flash's ``hybrid_layer_pattern``). Or a layer's token mixer may not
be attention at all: a kind with ``linear`` set runs a Gated DeltaNet mixer
(models/gated_deltanet.py) in attention's place, keeps a recurrent state a
program row and no pages. The norms may sit on each branch's OUTPUT
(``norm_at="output"``: Olmo 2's block), the whole query and key projections
may be normed (``qk_norm``), and a stack may have no positional term at all
(``pos="none"``): Olmo-Hybrid is those three with three linear layers to
every full one. Kimi-Linear is the same pattern UNDER latent attention:
three Kimi Delta Attention layers (a decay a key channel) to every latent
layer that rotates nothing, a state beside a latent arena. GPT-2 is
the defaults; Falcon-H1 is rmsnorm +
swiglu + GQA + rope + ssm + mup; GLM-4.7-Flash is rmsnorm + rope + mla +
experts behind one dense layer; Xing4.0 is that with YaRN rotary on four
hyper-connected streams.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from dataclasses import dataclass
from typing import Any, Callable, ClassVar, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import dot_product_attention
from ..ops.hyper_connection import HCConfig, hc_post, hc_pre
from ..parallel.ring import ring_attention
from .experts import ExpertMLP, ExpertsConfig
from .gated_deltanet import GatedDeltaNet, GDNConfig, KDAConfig
from .layers import QuantizableDense
from .mamba2 import Mamba2Mixer, SSMConfig
from .mla import MLAConfig, MLAttention

PAD_ID = 0


@dataclass(frozen=True)
class MuP:
    """The constant multipliers a muP-parametrised checkpoint is served
    with, under the names of Falcon-H1's ``config`` (``ssm_multipliers``
    ride :class:`SSMConfig`). All 1.0 = none."""

    embedding: float = 1.0       # embedding_multiplier
    lm_head: float = 1.0         # lm_head_multiplier
    attention_in: float = 1.0    # attention_in_multiplier
    attention_out: float = 1.0   # attention_out_multiplier
    key: float = 1.0             # key_multiplier
    ssm_in: float = 1.0          # ssm_in_multiplier
    ssm_out: float = 1.0         # ssm_out_multiplier
    mlp: Tuple[float, float] = (1.0, 1.0)   # mlp_multipliers: gate, down


def _scaled(t, m: float):
    """``t`` times a muP constant, the product taken in float32 (a constant
    rounded to bfloat16 would be off by up to 0.2%, the same way for every
    element); 1.0 is no operation at all."""
    if m == 1.0:
        return t
    return (t.astype(jnp.float32) * m).astype(t.dtype)


def _norm(kind: str, name: str, eps: float):
    """The block's normalisation, float32: ``layernorm`` or ``rmsnorm``."""
    if kind == "layernorm":
        return nn.LayerNorm(name=name, dtype=jnp.float32, epsilon=eps)
    if kind == "rmsnorm":
        return nn.RMSNorm(name=name, dtype=jnp.float32, epsilon=eps)
    raise ValueError(f"unknown norm {kind!r} (valid: 'layernorm', 'rmsnorm')")


def _part(names):
    return lambda init: nn.with_partitioning(init, names)


@dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer of a stack that mixes them, under the
    names of the published ``config`` (MiMo-V2-Flash: ``num_key_value_heads``
    and ``rope_theta`` for a full layer, ``swa_num_key_value_heads``,
    ``swa_rope_theta``, ``sliding_window`` and
    ``add_swa_attention_sink_bias`` for a window layer). ``window`` > 0: a
    query sees that many keys, its own among them, and the layer's paged
    cache is a RING of ``ops/paged_attention.ring_pages`` pages a row
    (serving/kvpool.py); 0: every key before it. ``sink``: one learned logit
    a head joins the softmax's denominator and takes no value. ``linear``:
    the layer's token mixer is not attention but the stack's delta-rule
    mixer (``CausalTransformer.gdn``; Olmo-Hybrid's ``layer_types``
    ``linear_attention``, Kimi-Linear's ``kda_layers``): it keeps a recurrent
    state a program row and NO paged cache, and the other fields say nothing
    of it. Under latent attention (``CausalTransformer.mla``) a kind is
    ``linear`` or it is the stack's latent attention, which has no field
    here to differ by."""

    num_kv_heads: int = 0
    rope_theta: float = 10000.0
    window: int = 0
    sink: bool = False
    linear: bool = False


class CausalSelfAttention(nn.Module):
    num_heads: int
    mesh: Optional[Mesh] = None
    dtype: Any = jnp.float32
    use_bias: bool = False  # GPT-2-family checkpoints carry qkv/proj biases
    # sequence-parallel scheme when mesh.sp > 1: "ring" (ppermute K/V rotation,
    # kubeml_tpu.parallel.ring) or "ulysses" (head<->sequence all_to_all,
    # kubeml_tpu.parallel.ulysses — needs the per-tp-shard head count,
    # num_heads/tp, divisible by sp)
    sp_impl: str = "ring"
    # KV-cache capacity for autoregressive decode (models.generation); set by
    # the parent from max_len. 0 = training/scoring only, no cache variables.
    cache_len: int = 0
    # rotary position embeddings applied to q/k (ops.rotary): position enters
    # the dot product as a phase, so there is no table and plain forward is
    # not capped by max_len (the parent skips its learned pos_embed add)
    rope: bool = False
    rope_theta: float = 10000.0
    # PAGED KV cache (kubeml_tpu.serving.kvpool): when a block table is
    # passed at call time the cache collection holds one shared physical
    # arena of token rows ``[kv_pages, page_tokens, W]`` (K of every K/V
    # head, then V: ops/paged_attention.kv_row_width) instead of per-row
    # ``[B, max_len, ...]`` stripes; rows address it through per-row page
    # tables, so rows of different lengths share one step program without
    # padding every row to max_len. 0/0 (default) = dense cache only.
    # The paged branch below says why the arena is laid out so.
    # This page-granular layout is also what makes a live request's decode
    # state PORTABLE: serving/kvsnap.py gathers a row's written pages out
    # of the arena into a KMS1 frame and scatters them back into any
    # byte-compatible arena (same page_tokens/kv_quant), mid-stream
    # (docs/design.md §24).
    page_tokens: int = 0
    kv_pages: int = 0
    # how the paged path READS the arena (KUBEML_PAGED_ATTN): "gather"
    # materializes each row's table as a contiguous [B, tw*pt, H, D] block
    # and attends over it (the original path — the parity oracle);
    # "pallas" attends straight through the page table with the streaming
    # kernel (ops/paged_attention.py — KV traffic scales with occupancy,
    # no contiguous copy); "auto" = pallas on TPU, gather elsewhere
    paged_attn: str = "auto"
    # paged-arena STORAGE dtype (KUBEML_KV_QUANT): "off" keeps the compute
    # dtype; "int8" stores pages int8 with per-page-per-head running-absmax
    # scale arenas [kv_pages, H] (k_scale/v_scale) — the write scatter
    # quantizes, both read paths dequantize, and the same arena byte budget
    # holds 2-4x the tokens (ops/paged_attention.resolve_kv_quant)
    kv_quant: str = "off"
    # grouped-query attention: ``num_kv_heads`` K/V heads, each shared by
    # num_heads / num_kv_heads query heads (query head h reads K/V head
    # h // that ratio); caches and the paged arena hold the K/V heads only.
    # 0 = one K/V head per query head. ``head_dim`` 0 = embed_dim / num_heads
    # (Falcon-H1: 20 query heads of 128 on a 5120-wide stream).
    num_kv_heads: int = 0
    head_dim: int = 0
    key_mult: float = 1.0   # a muP multiplier on the keys (key_multiplier)
    # what MiMo-V2-Flash's ``config`` adds, under its names: V heads of a
    # width of their own (``v_head_dim``; 0 = ``head_dim``), rotary on the
    # first ``partial_rotary_factor`` of a head's lanes (ops/rotary.py
    # rotary_width), the output times ``value_scale``
    # (``attention_value_scale``), and the layer's kind (:class:`AttnKind`):
    # ``window`` keys a query sees (0 = all), a learned ``sink`` a head
    v_head_dim: int = 0
    partial_rotary_factor: float = 1.0
    value_scale: float = 1.0
    window: int = 0
    sink: bool = False
    # RMSNorm over the WHOLE query and key projections (all heads' lanes
    # together, before the split into heads; Olmo 2's ``q_norm``/``k_norm``)
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6

    @nn.nowrap   # no scope of its own: the walk is ``%attn`` in every layer
    def _window_paged(self, ckv, q, k, v, positions, pages, seq_lens, valid,
                      sink):
        """A window layer's paged decode: ``pages`` ``[B, ring]`` is the
        row's RING (page of position ``p`` = slot ``(p // pt) mod ring``),
        ``ckv`` the layer's arena of ``kv_pages`` pages of them. A step (``L == 1``) writes
        its token into the ring and attends through it, at most ``window``
        keys back. Anything longer is an admission of a row's whole prompt
        (the serving layer refuses what would continue one: prefix hits,
        chunked prefill, verify windows): it attends over the bucket's OWN
        keys in a band, through the page walk's tile body over the bucket's
        rows as they stand (no arena of the bucket's length exists in any
        program), and keeps in the ring only the tail a step will read."""
        from ..ops.paged_attention import (pack_kv_rows, paged_attention,
                                           resolve_paged_attn, unpack_kv_rows)

        B, L, H, D = q.shape
        Hkv, Dv = k.shape[2], v.shape[3]
        pt, ring = self.page_tokens, pages.shape[1]
        if ring * pt < self.window + pt:
            raise ValueError(f"a ring of {ring} pages of {pt} does not hold "
                             f"a window of {self.window}")
        kernel = resolve_paged_attn(self.paged_attn) == "pallas"
        kinds = dict(kv_heads=Hkv, v_head_dim=Dv if Dv != D else 0,
                     window=self.window, sink=sink,
                     value_scale=self.value_scale)
        shared = lambda t: (t if Hkv == H
                            else jnp.repeat(t, H // Hkv, axis=2))
        rows = pack_kv_rows(k, v)                              # [B, L, W]
        n = (seq_lens if seq_lens is not None
             else valid.astype(jnp.int32).sum(axis=1))         # real tokens
        # the tail the ring keeps: the bucket's last ring-worth of positions
        # (all of a step's one), those of them that are real and lie in the
        # ring's newest turn; the others go to the trash page
        T = min(L, ring * pt)
        at = jnp.clip(n - T, 0, L - T)[:, None] + jnp.arange(T)  # [B, T]
        pos = positions[:, None] + at
        newest = (positions + jnp.maximum(n, 1) - 1) // pt
        keep = (at < n[:, None]) & (pos // pt > newest[:, None] - ring)
        phys = jnp.where(keep, jnp.take_along_axis(
            pages, (pos // pt) % ring, axis=1), 0)
        tail = rows if T == L else jnp.take_along_axis(
            rows, at[:, :, None], axis=1)
        ckv.value = ckv.value.at[phys, pos % pt].set(tail)
        if L == 1:
            if kernel:
                return paged_attention(q, ckv.value, pages, positions,
                                       **kinds)
            kg, vg = unpack_kv_rows(ckv.value[pages], Hkv, D, Dv)
            kg = kg.reshape(B, ring * pt, Hkv, D)
            vg = vg.reshape(B, ring * pt, Hkv, Dv)
            # slot s holds the newest logical page congruent to s at or
            # before the query's own
            col = jnp.arange(ring * pt)[None, :]
            cur = (positions // pt)[:, None]
            k_pos = (cur - (cur - col // pt) % ring) * pt + col % pt
            here = positions[:, None]
            mask = ((k_pos <= here) & (k_pos > here - self.window)
                    & (k_pos >= 0))[:, None, None, :]
        else:
            if kernel:
                # the bucket's own rows as a table of whole pages
                Lp = -(-L // pt) * pt
                own = jnp.pad(rows, ((0, 0), (0, Lp - L), (0, 0))).reshape(
                    B * (Lp // pt), pt, rows.shape[-1])
                table = jnp.arange(B * (Lp // pt), dtype=jnp.int32).reshape(
                    B, Lp // pt)
                return paged_attention(q, own, table,
                                       jnp.zeros((B,), jnp.int32), **kinds)
            kg, vg = k, v
            i = jnp.arange(L)
            mask = ((i[None, :] <= i[:, None])
                    & (i[None, :] > i[:, None] - self.window))[None, None]
        out = dot_product_attention(q, shared(kg), shared(vg), mask=mask,
                                    sink=sink)
        return _scaled(out, self.value_scale)

    @nn.compact
    def __call__(self, x, valid, decode: bool = False, positions=None,
                 pages=None, seq_lens=None):
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sp_impl {self.sp_impl!r} (valid: 'ring', 'ulysses')"
            )
        B, L, E = x.shape
        H = self.num_heads
        D = self.head_dim or E // H
        Dv = self.v_head_dim or D
        Hkv = self.num_kv_heads or H
        # what this layer's kind adds to the plain causal softmax over
        # equal heads; none of it: the block every other model has
        plain = not (self.window or self.sink or Dv != D
                     or self.value_scale != 1.0)
        if H % Hkv:
            raise ValueError(f"num_heads {H} is not a multiple of "
                             f"num_kv_heads {Hkv}")
        # each K/V head once per query head that shares it, for the read
        # paths that take one K/V head per query head
        shared = ((lambda t: t) if Hkv == H
                  else (lambda t: jnp.repeat(t, H // Hkv, axis=2)))
        # 2-D kernels with manual head reshape: column-sharding [E, H*D] over
        # tp IS head-sharding (heads are the leading factor of the columns).
        # QuantizableDense == nn.Dense until the serving layer hands it an
        # int8 kernel (KUBEML_INT8_MATMUL decode, models/layers.py)
        dense = lambda feats, names, name: QuantizableDense(
            feats, name=name,
            kernel_init=_part(names)(nn.initializers.lecun_normal()),
            use_bias=self.use_bias, dtype=self.dtype,
        )
        # ``qk_norm``: the whole projection normed, before its heads split
        normed = lambda name, t: nn.RMSNorm(
            name=name, dtype=jnp.float32, epsilon=self.qk_norm_eps)(
                t).astype(self.dtype) if self.qk_norm else t
        q = normed("q_norm", dense(H * D, (None, "tp"), "query")(x)).reshape(
            B, L, H, D)
        k = normed("k_norm", dense(Hkv * D, (None, "tp"), "key")(x)).reshape(
            B, L, Hkv, D)
        v = dense(Hkv * Dv, (None, "tp"), "value")(x).reshape(B, L, Hkv, Dv)
        k = _scaled(k, self.key_mult)
        out_proj = dense(E, ("tp", None), "proj")
        sink = (self.param("sink", nn.initializers.zeros, (H,))
                if self.sink else None)
        if self.rope:
            from ..ops.rotary import apply_rope, rotary_width

            rot = (0 if self.partial_rotary_factor == 1.0
                   else rotary_width(D, self.partial_rotary_factor))
            rope = lambda t, at: apply_rope(t, at, self.rope_theta,
                                            rotary_dim=rot)

        if decode:
            # KV-cache decode (models.generation): write this call's K/V at
            # the cache cursor, attend q against the whole cache prefix. One
            # code path serves prefill (L = prompt len, cursor 0) and the
            # per-token steps (L = 1) — all shapes static, writes via
            # dynamic_update_slice, so the step jits once and the cursor is
            # a runtime scalar.
            if self.cache_len <= 0:
                raise ValueError("decode=True needs cache_len > 0 "
                                 "(CausalTransformer sets it from max_len)")
            if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
                raise ValueError("decode does not run under sequence "
                                 "parallelism; use an sp=1 mesh for serving")
            if pages is not None:
                # PAGED decode (serving.kvpool): the cache is one shared
                # physical arena of token rows [kv_pages, pt, W]; each row
                # addresses its own logical window through ``pages`` [B, P]
                # (logical page j of row b lives at physical page pages[b, j]).
                # ``positions`` [B] is the logical position of each row's
                # FIRST token this call — L == 1 per-token steps and L > 1
                # suffix prefill (shared-prefix reuse: the cached prefix is
                # already in the arena, only the suffix runs) share this one
                # code path. Writes are coordinate scatters at
                # (physical page, offset); invalid positions (bucket
                # padding, rows the host retired) are redirected to
                # physical page 0 — the pool's reserved trash page — so a
                # stale row can never corrupt a reallocated page. Reads
                # attend under the purely positional causal mask — every
                # logical position <= the query's is real by construction
                # (prompts are dense, decode writes are contiguous) —
                # either straight through the page table (the Pallas
                # streaming kernel, ops/paged_attention.py) or by
                # gathering the row's whole table into a contiguous
                # [B, tw*pt, H, D] block (the fallback + parity oracle);
                # ``paged_attn`` selects.
                if self.page_tokens <= 0 or self.kv_pages <= 0:
                    raise ValueError(
                        "paged decode needs page_tokens/kv_pages > 0 on the "
                        "module (the serving layer clones them in)")
                if positions is None:
                    raise ValueError("paged decode needs per-row positions")
                pt, npg = self.page_tokens, self.kv_pages
                tw = pages.shape[1]  # table width (logical pages per row)
                from ..ops.paged_attention import (kv_row_width,
                                                   pack_kv_rows,
                                                   resolve_kv_quant,
                                                   unpack_kv_rows)

                kvq = resolve_kv_quant(self.kv_quant)
                store_dtype = jnp.int8 if kvq == "int8" else k.dtype
                # ONE arena a layer, a token a row: K of the Hkv heads, then
                # V, in W lanes — a whole number of 128-lane rows (2,560 at
                # GPT-2 large, 3,200 at XL, 1,024 at Falcon-H1; a narrower
                # model's row ends in zero lanes). The write is a scatter
                # over (page, offset) whose window is the whole minor
                # dimension, the kernel's page block (1, pt, W) has the
                # array's own trailing dimensions, and XLA keeps the
                # default layout for both. The layout before this one, K
                # and V each [kv_pages, Hkv, pt, D] written by
                # ``.at[phys, :, off].set``, had its two index dimensions
                # straddle the heads: XLA laid each arena out token-major
                # for the scatter, row-major again for the Mosaic call and
                # a third time for the next use — six pool-sized copies a
                # layer in a GPT-2 decode step and in a one-row admit, four
                # under Falcon-H1's heads of 128 (compiled ahead of time
                # for a v5e; on the chip 26-31 ms of a 48-62 ms step).
                # tests/test_arena_copies.py holds the count at zero.
                W = kv_row_width(Hkv, D, Dv)
                ckv = self.variable("cache", "kv_rows", jnp.zeros,
                                    (npg, pt, W), store_dtype)
                if kvq == "int8":
                    # per-page-per-head running absmax: a page's int8 value
                    # q reconstructs as q * scale / 127. Scales live in the
                    # same cache collection and are addressed by PHYSICAL
                    # page, so shared prefix pages carry their scales with
                    # them — trie reuse stays free.
                    ks = self.variable("cache", "k_scale", jnp.zeros,
                                       (npg, Hkv), jnp.float32)
                    vs = self.variable("cache", "v_scale", jnp.zeros,
                                       (npg, Hkv), jnp.float32)
                pos_full = positions[:, None] + jnp.arange(L)  # [B, L]
                if self.rope:
                    q, k = rope(q, pos_full), rope(k, pos_full)
                if kvq == "int8" and not plain:
                    raise ValueError("int8 pages do not cover a window, a "
                                     "sink or V heads of their own width")
                if self.window:
                    # the arena above holds the rows' rings
                    return out_proj(self._window_paged(
                        ckv, q, k, v, positions, pages, seq_lens, valid,
                        sink).reshape(B, L, H * Dv))
                wvalid = (jnp.arange(L)[None, :] < seq_lens[:, None]
                          if seq_lens is not None
                          else valid.astype(jnp.bool_))
                # writes past the row table's addressable range go to the
                # trash page, NOT clamped onto the last logical page (the
                # page_idx clip below would otherwise scatter a speculative
                # lookahead overflow over live data). Only emissions the
                # engine masks anyway can involve such positions, so
                # trash-redirecting them is exact.
                wvalid = wvalid & (pos_full < tw * pt)
                page_idx = jnp.clip(pos_full // pt, 0, tw - 1)
                phys = jnp.take_along_axis(pages, page_idx, axis=1)  # [B, L]
                phys = jnp.where(wvalid, phys, 0)
                off = pos_full % pt
                if kvq == "int8":
                    # quantized scatter write, three moves riding the same
                    # (phys, off) coordinates: (1) scatter-max the new
                    # tokens' per-head absmax into the touched pages'
                    # scales (monotone — a spec-rollback's rejected drafts
                    # leave only a bounded precision loss, never a leak);
                    # (2) requantize the touched pages' EXISTING rows for
                    # the scale growth (duplicate page gathers all derive
                    # identical bytes from the old arena + final scale, so
                    # the duplicate scatter writes agree); (3) quantize and
                    # scatter this call's K/V at the final scale. Trash
                    # page 0 takes redirected writes exactly as before —
                    # its scale grows with the garbage, and nothing reads
                    # it meaningfully. K and V ride one pass: a row's 2*Hkv
                    # head slices against the K scales beside the V scales.
                    xf = jnp.concatenate([k, v], axis=2).astype(jnp.float32)
                    scales = jnp.concatenate([ks.value, vs.value], axis=1)
                    amax = jnp.abs(xf).max(axis=-1)          # [B, L, 2Hkv]
                    new_s = scales.at[phys].max(amax)        # [npg, 2Hkv]
                    old_at = scales[phys]                    # [B, L, 2Hkv]
                    new_at = new_s[phys]                     # [B, L, 2Hkv]
                    ratio = jnp.where(new_at > 0.0,
                                      old_at / jnp.maximum(new_at, 1e-30),
                                      1.0)
                    # [B, L, pt, 2Hkv, D]: the touched pages, head by head
                    old_q = jnp.concatenate(unpack_kv_rows(
                        ckv.value[phys].astype(jnp.float32), Hkv, D), axis=3)
                    req = jnp.clip(
                        jnp.round(old_q * ratio[:, :, None, :, None]),
                        -127, 127).astype(jnp.int8)
                    arena = ckv.value.at[phys].set(
                        pack_kv_rows(req[..., :Hkv, :], req[..., Hkv:, :]))
                    qv = jnp.clip(
                        jnp.round(xf * 127.0
                                  / jnp.maximum(new_at, 1e-30)[..., None]),
                        -127, 127).astype(jnp.int8)
                    ckv.value = arena.at[phys, off].set(
                        pack_kv_rows(qv[:, :, :Hkv], qv[:, :, Hkv:]))
                    ks.value, vs.value = new_s[:, :Hkv], new_s[:, Hkv:]
                else:
                    # (page, offset): the window is a token's whole row
                    ckv.value = ckv.value.at[phys, off].set(
                        pack_kv_rows(k, v))
                from ..ops.paged_attention import resolve_paged_attn

                if resolve_paged_attn(self.paged_attn) == "pallas":
                    # stream pages through VMEM with the online-softmax
                    # kernel: the arena gather happens per block inside
                    # the kernel's DMA walk and reads stop at each row's
                    # live depth — no [B, tw*pt, H, D] copy in HBM. In
                    # int8 mode the per-page scales ride the same page
                    # walk and dequant happens inside the kernel blocks.
                    from ..ops.paged_attention import paged_attention

                    if kvq == "int8":
                        out = paged_attention(q, ckv.value, pages, positions,
                                              kv_heads=Hkv, k_scale=ks.value,
                                              v_scale=vs.value)
                    elif plain:
                        out = paged_attention(q, ckv.value, pages, positions,
                                              kv_heads=Hkv)
                    else:
                        out = paged_attention(
                            q, ckv.value, pages, positions, kv_heads=Hkv,
                            v_head_dim=Dv if Dv != D else 0, sink=sink,
                            value_scale=self.value_scale)
                else:
                    # [B, tw, pt, Hkv, D]: rows come out token-major as is
                    kg, vg = unpack_kv_rows(ckv.value[pages], Hkv, D, Dv)
                    if kvq == "int8":
                        # gather-path dequant: the parity oracle for the
                        # quantized STORAGE format itself (same q*s/127
                        # reconstruction as the kernel's VMEM dequant)
                        kg = (kg.astype(jnp.float32)
                              * (ks.value[pages] / 127.0)[:, :, None, :, None]
                              ).astype(q.dtype)
                        vg = (vg.astype(jnp.float32)
                              * (vs.value[pages] / 127.0)[:, :, None, :, None]
                              ).astype(q.dtype)
                    kg = kg.reshape(B, tw * pt, Hkv, D)
                    vg = vg.reshape(B, tw * pt, Hkv, Dv)
                    k_pos = jnp.arange(tw * pt)[None, None, None, :]
                    # [B, 1, L, tw*pt]
                    mask = k_pos <= pos_full[:, None, :, None]
                    if plain:
                        out = dot_product_attention(q, shared(kg), shared(vg),
                                                    mask=mask)
                    else:
                        out = _scaled(dot_product_attention(
                            q, shared(kg), shared(vg), mask=mask, sink=sink),
                            self.value_scale)
                return out_proj(out.reshape(B, L, H * Dv))
            if not plain:
                raise ValueError(
                    "a window, a sink, V heads of their own width and a "
                    "value scale decode through the paged arena only (no "
                    "dense cache holds them)")
            Lc = self.cache_len
            ck = self.variable("cache", "k", jnp.zeros, (B, Lc, Hkv, D),
                               k.dtype)
            cv = self.variable("cache", "v", jnp.zeros, (B, Lc, Hkv, D),
                               v.dtype)
            cvalid = self.variable("cache", "valid", jnp.zeros, (B, Lc), jnp.bool_)
            cursor = self.variable("cache", "index",
                                   lambda: jnp.zeros((), jnp.int32))
            if positions is not None:
                # PER-ROW cursors [B] (continuous batching, kubeml_tpu.serving):
                # every slot sits at its own depth, so writes are one-row
                # scatters at (b, positions[b]) and the causal mask compares
                # key slots against each row's own position. One-token steps
                # only — prefill goes through the contiguous scalar path.
                if L != 1:
                    raise ValueError("per-row positions decode is one token "
                                     "per step (L == 1); prefill uses the "
                                     "scalar-cursor path")
                if self.rope:
                    q = rope(q, positions[:, None])
                    k = rope(k, positions[:, None])
                # per-row writes as a coordinate scatter at (row, position).
                # Chip-measured: this beats a vmapped dynamic_update_slice
                # (batched dynamic starts lower worse than the scatter —
                # 2.9 vs 4.5 ms/step on GPT-2-small x 16 slots), and the
                # whole positions path costs ~28% over the scalar-cursor
                # step (2.9 vs 2.25 ms/step) — the price of per-row depth
                rows = jnp.arange(B)
                ck.value = ck.value.at[rows, positions].set(k[:, 0])
                cv.value = cv.value.at[rows, positions].set(v[:, 0])
                cvalid.value = cvalid.value.at[rows, positions].set(
                    valid[:, 0].astype(jnp.bool_))
                k_pos = jnp.arange(Lc)[None, None, None, :]
                mask = cvalid.value[:, None, None, :] & (
                    k_pos <= positions[:, None, None, None])
                out = dot_product_attention(q, shared(ck.value),
                                            shared(cv.value), mask=mask)
                return out_proj(out.reshape(B, L, H * D))
            i0 = cursor.value
            if self.rope:
                # keys are cached ALREADY rotated by their absolute position,
                # so cached entries never need re-rotation as the cursor moves
                pos = i0 + jnp.arange(L)
                q, k = rope(q, pos), rope(k, pos)
            ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, i0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, i0, 0, 0))
            cvalid.value = jax.lax.dynamic_update_slice(
                cvalid.value, valid.astype(jnp.bool_), (0, i0))
            cursor.value = i0 + L
            # [B, 1, L, Lc]: attend to written, valid cache slots at or before
            # each query's absolute position i0 + l
            k_pos = jnp.arange(Lc)[None, None, None, :]
            q_pos = (i0 + jnp.arange(L))[None, None, :, None]
            mask = cvalid.value[:, None, None, :] & (k_pos <= q_pos)
            out = dot_product_attention(q, shared(ck.value), shared(cv.value),
                                        mask=mask)
            return out_proj(out.reshape(B, L, H * D))

        if self.rope:
            pos = jnp.arange(L)
            q, k = rope(q, pos), rope(k, pos)
        k, v = shared(k), shared(v)

        if not plain:
            # the whole-sequence forward of such a layer: dense scores under
            # the causal mask, the window's band and the sink
            if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
                raise ValueError("sequence parallelism does not cover a "
                                 "window, a sink or V heads of their own "
                                 "width")
            i = jnp.arange(L)
            mask = (i[None, :] <= i[:, None])[None, None] & valid.astype(
                jnp.bool_)[:, None, None, :]
            if self.window:
                mask = mask & (i[None, :] > i[:, None] - self.window)
            out = _scaled(dot_product_attention(q, k, v, mask=mask, sink=sink),
                          self.value_scale)
            return out_proj(out.reshape(B, L, H * Dv))
        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
            if self.sp_impl == "ulysses":
                from ..parallel.ulysses import ulysses_attention

                sp_fn = lambda q, k, v, val: ulysses_attention(
                    q, k, v, axis_name="sp", causal=True, kv_valid=val
                )
            else:
                sp_fn = lambda q, k, v, val: ring_attention(
                    q, k, v, axis_name="sp", causal=True, kv_valid=val
                )
            attn = jax.shard_map(
                sp_fn,
                mesh=self.mesh,
                in_specs=(
                    P("dp", "sp", "tp", None),
                    P("dp", "sp", "tp", None),
                    P("dp", "sp", "tp", None),
                    P("dp", "sp"),
                ),
                out_specs=P("dp", "sp", "tp", None),
                check_vma=False,
            )
            out = attn(q, k, v, valid)
        else:
            out = dot_product_attention(q, k, v, causal=True, kv_valid=valid)
        return out_proj(out.reshape(B, L, H * Dv))


class GPTBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    mesh: Optional[Mesh] = None
    sp_impl: str = "ring"
    dtype: Any = jnp.float32
    ln_eps: float = 1e-6    # GPT-2 checkpoints use 1e-5
    attn_bias: bool = False
    cache_len: int = 0
    rope: bool = False
    rope_theta: float = 10000.0
    page_tokens: int = 0
    kv_pages: int = 0
    paged_attn: str = "auto"
    kv_quant: str = "off"
    # --- the block's kinds (CausalTransformer documents them) ---
    norm: str = "layernorm"
    mlp: str = "gelu"
    mlp_dim: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    ssm: Optional[SSMConfig] = None
    mup: Optional[MuP] = None
    state_rows: int = 0
    mla: Optional[MLAConfig] = None
    experts: Optional[ExpertsConfig] = None
    hc: Optional[HCConfig] = None
    # the attention's own additions (CausalSelfAttention documents them);
    # ``window`` and ``sink`` are the layer's kind (AttnKind)
    v_head_dim: int = 0
    partial_rotary_factor: float = 1.0
    value_scale: float = 1.0
    window: int = 0
    sink: bool = False
    qk_norm: bool = False
    # where ``ln1`` / ``ln2`` sit: on each branch's "input" (``x + f(norm(
    # x))``) or on its "output" (``x + norm(f(x))``)
    norm_at: str = "input"
    # the layer's token mixer is ``gdn``'s Gated DeltaNet, not attention
    # (AttnKind.linear)
    linear: bool = False
    gdn: Optional[Union[GDNConfig, KDAConfig]] = None
    # paged caches a layer of this class holds (models/cache_spec.py
    # cache_spec): one attention, one cache
    cache_sublayers: ClassVar[int] = 1

    def _hc_maps(self, name: str, width: int):
        """A sub-layer's ``phi``, ``alpha`` and ``bias`` (ops/
        hyper_connection.py). A fresh model starts near the single stream:
        ``pre`` 1/n, ``post`` 1, ``M`` close to the identity."""
        n, c = self.hc.mult, self.hc.maps
        bias = lambda *_: jnp.concatenate([
            jnp.full((n,), -jnp.log(n - 1.0)), jnp.zeros((n,)),
            (4.0 * jnp.eye(n) - 2.0).reshape(n * n)])
        return {
            "phi": self.param(f"{name}_phi", _part((None, None))(
                nn.initializers.lecun_normal()), (n * width, c)),
            "alpha": self.param(f"{name}_alpha",
                                nn.initializers.constant(0.01), (3,)),
            "bias": self.param(f"{name}_bias", bias, (c,))}

    @nn.compact
    def __call__(self, x, valid, train: bool = False, decode: bool = False,
                 positions=None, pages=None, seq_lens=None, rows=None,
                 branch: Optional[Callable] = None):
        """``branch(u, real)``, where given, is called with the
        feed-forward's own normed input and the mask of the tokens that are
        real, before the feed-forward: a side branch of the caller's
        (:class:`ShortcutBlock`'s experts) that reads what the feed-forward
        reads. What it makes is the caller's to keep."""
        mup = self.mup or MuP()
        hc = self.hc
        if self.norm_at not in ("input", "output"):
            raise ValueError(f"unknown norm_at {self.norm_at!r} (valid: "
                             f"'input', 'output')")
        after = self.norm_at == "output"
        if (after or self.linear) and (
                hc is not None or self.ssm is not None or branch is not None):
            raise ValueError("a norm on a branch's output and a linear "
                             "mixer are the plain residual block's: no "
                             "hyper-connections, parallel mixer or side "
                             "branch around them")
        # a branch's norm, before it or after it
        before = lambda name, t: t.astype(self.dtype) if after else _norm(
            self.norm, name, self.ln_eps)(t).astype(self.dtype)
        behind = lambda name, t: _norm(self.norm, name, self.ln_eps)(
            t).astype(self.dtype) if after else t
        if hc is not None:
            if self.ssm is not None or branch is not None:
                raise ValueError("hyper-connections around a parallel mixer "
                                 "or a side branch are not defined")
            # x is the n streams, flat [B, L, n E]; the Pallas kernels serve
            # decode applies on a TPU (they have no backward)
            E = x.shape[-1] // hc.mult
            kernel = None if decode else False
            xs, (x, post, mix) = x, hc_pre(x, self._hc_maps("hc1", E), hc,
                                           kernel=kernel)
        u = before("ln1", x)
        if self.linear:
            if self.gdn is None:
                raise ValueError("a linear layer needs the stack's GDNConfig "
                                 "(CausalTransformer.gdn)")
            y = GatedDeltaNet(self.gdn, dtype=self.dtype,
                              state_rows=self.state_rows, name="mixer")(
                u, decode=decode, positions=positions, seq_lens=seq_lens,
                rows=rows)
        elif self.mla is not None:
            if (self.window or self.sink or self.v_head_dim
                    or self.partial_rotary_factor != 1.0
                    or self.value_scale != 1.0):
                raise ValueError("latent attention has no window, sink, V "
                                 "width or rotary share of its own here")
            attn = MLAttention(self.num_heads, self.mla, dtype=self.dtype,
                               rope_theta=self.rope_theta,
                               page_tokens=self.page_tokens,
                               kv_pages=self.kv_pages,
                               paged_attn=self.paged_attn, name="attn")
        else:
            attn = CausalSelfAttention(
                self.num_heads, mesh=self.mesh, sp_impl=self.sp_impl,
                dtype=self.dtype, use_bias=self.attn_bias,
                cache_len=self.cache_len, rope=self.rope,
                rope_theta=self.rope_theta, page_tokens=self.page_tokens,
                kv_pages=self.kv_pages, paged_attn=self.paged_attn,
                kv_quant=self.kv_quant, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, key_mult=mup.key, name="attn",
                v_head_dim=self.v_head_dim,
                partial_rotary_factor=self.partial_rotary_factor,
                value_scale=self.value_scale, window=self.window,
                sink=self.sink, qk_norm=self.qk_norm,
                qk_norm_eps=self.ln_eps)
        if not self.linear:
            y = attn(_scaled(u, mup.attention_in), valid, decode=decode,
                     positions=positions, pages=pages, seq_lens=seq_lens)
            y = _scaled(y, mup.attention_out)
        y = behind("ln1", y)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        if hc is None:
            x = x + y
        else:
            xs = hc_post(xs, y, post, mix, kernel=kernel)
            x, post, mix = hc_pre(xs, self._hc_maps("hc2", E), hc,
                                  kernel=kernel)
        if self.ssm is not None:
            # the mixer reads the SAME normed input as attention and both
            # land on the residual together (Falcon-H1's parallel block)
            m = Mamba2Mixer(self.ssm, dtype=self.dtype,
                            state_rows=self.state_rows, name="mixer")(
                _scaled(u, mup.ssm_in), decode=decode, positions=positions,
                seq_lens=seq_lens, rows=rows)
            x = x + _scaled(m, mup.ssm_out)
        y = before("ln2", x)
        E = x.shape[-1]
        # the tokens given to experts: a decode apply's real positions (a
        # dead row, a bucket's padding are not), else the non-pad ids
        real = lambda: (
            jnp.arange(x.shape[1])[None, :] < seq_lens[:, None]
            if decode and seq_lens is not None else valid.astype(jnp.bool_))
        if branch is not None:
            branch(y, real())
        if self.mlp == "gelu":
            y = QuantizableDense(
                self.mlp_dim or E * self.mlp_ratio, name="mlp_in",
                dtype=self.dtype,
                kernel_init=_part((None, "tp"))(
                    nn.initializers.lecun_normal()),
                bias_init=_part(("tp",))(nn.initializers.zeros))(y)
            y = nn.gelu(y)
            y = QuantizableDense(
                E, name="mlp_out", dtype=self.dtype,
                kernel_init=_part(("tp", None))(
                    nn.initializers.lecun_normal()))(y)
        elif self.mlp == "swiglu":
            # silu(gate) * up -> down, no biases, at a width of its own
            wide = lambda name: QuantizableDense(
                self.mlp_dim or E * self.mlp_ratio, name=name,
                use_bias=False, dtype=self.dtype,
                kernel_init=_part((None, "tp"))(
                    nn.initializers.lecun_normal()))
            y = nn.silu(_scaled(wide("mlp_gate")(y), mup.mlp[0])) \
                * wide("mlp_up")(y)
            y = _scaled(QuantizableDense(
                E, name="mlp_out", use_bias=False, dtype=self.dtype,
                kernel_init=_part(("tp", None))(
                    nn.initializers.lecun_normal()))(y), mup.mlp[1])
        elif self.mlp == "experts":
            y = ExpertMLP(self.experts, dtype=self.dtype, name="experts")(
                y, real(), decode=decode)
        else:
            raise ValueError(f"unknown mlp {self.mlp!r} (valid: 'gelu', "
                             f"'swiglu', 'experts')")
        y = nn.Dropout(self.dropout, deterministic=not train)(behind("ln2", y))
        if hc is not None:
            return hc_post(xs, y, post, mix, kernel=kernel)
        return x + y


class ShortcutBlock(GPTBlock):
    """LongCat-Flash's double layer (shortcut-connected experts,
    arXiv:2509.01322), ``mlp="shortcut"``:

        h = h + MLA_0(norm(h));  u = norm(h);  s = Experts(u)
        h = h + SwiGLU_0(u)
        h = h + MLA_1(norm(h));  h = h + SwiGLU_1(norm(h))
        h = h + s

    ``sub_0`` and ``sub_1`` are ordinary SwiGLU blocks; the experts are a
    side branch of ``sub_0`` that reads its feed-forward's normed input
    (``branch``; their weights lie under ``sub_0/experts``), and nothing
    between there and the end reads ``s``, which is what lets a deployment's
    expert exchange run behind ``SwiGLU_0`` and ``MLA_1`` (here, on one
    chip, it only leaves XLA free to order the branch). It is a ``GPTBlock``
    by its fields: the stack makes a layer of either class from the same
    arguments, and both sub-blocks take every one of them but ``mlp`` and
    ``experts``. Two attentions a layer, two caches; each is named ``attn``
    under its sub-layer, so a device trace shows all of a program's page
    walks under one name."""

    cache_sublayers: ClassVar[int] = 2

    @nn.compact
    def __call__(self, x, valid, train: bool = False, decode: bool = False,
                 positions=None, pages=None, seq_lens=None, rows=None):
        if self.mlp != "shortcut" or self.experts is None:
            raise ValueError("a ShortcutBlock is mlp='shortcut' with an "
                             "ExpertsConfig")
        sub = functools.partial(GPTBlock, mlp="swiglu", **{
            f.name: getattr(self, f.name) for f in dataclasses.fields(self)
            if f.name not in ("parent", "name", "mlp", "experts")})
        at = dict(positions=positions, pages=pages, seq_lens=seq_lens,
                  rows=rows)
        s = []
        x = sub(name="sub_0")(
            x, valid, train, decode, **at,
            branch=lambda u, real: s.append(ExpertMLP(
                self.experts, dtype=self.dtype, name="experts")(
                    u, real, decode=decode)))
        x = sub(name="sub_1")(x, valid, train, decode, **at)
        return x + s[0]


_block_traces = 0   # times _decode_block's body has run: once per trace
_block_traces_lock = threading.Lock()   # engines trace on their own threads


def block_traces() -> int:
    """How often this process has traced :func:`_decode_block`. A decode
    program costs about one for each kind of layer in the stack (one; two
    with ``dense_layers`` before expert layers), whatever its depth; a count
    that grows by the depth says the layers stopped sharing a trace (serving
    telemetry shows it as ``block_traces`` beside ``compiled_programs``)."""
    return _block_traces


@functools.partial(jax.jit, static_argnums=0, inline=True)
def _decode_block(block, variables, x, positions, pages, seq_lens, rows):
    """One layer of a decode apply: ``block``, a detached :class:`GPTBlock`,
    on ``variables`` = that layer's ``params`` and, once it exists, its
    ``cache``; returns the stream and the layer's new cache subtree.

    Jitted with the module static, so a program's layers of one kind,
    whose parameters and cache differ in value only, share one trace: this
    body runs for the first layer of each kind, and jax copies the equations
    it recorded into the program for every other. A Python ``for`` over bound submodules ran the block's
    Python and traced its kernels again for every layer, 1.4 s a layer and
    program on a v5e host, found in no cache. ``inline=True`` makes the
    copy land in the caller's own equations, so the program XLA is handed
    is the unrolled one, operation for operation; left as a function called
    ``depth`` times, XLA's TPU pipeline simplifies the body alone before it
    inlines it and compiles a slightly different decode step (PERF.md, PR
    29). The layers' kernel equations are then one object, which is what
    lets jax lower the kernel to Mosaic once a program and not once a layer.
    Training keeps the loop over bound blocks: its remat wrapper, dropout
    rngs and sown MoE losses thread through the parent's scope."""
    global _block_traces
    with _block_traces_lock:
        _block_traces += 1
    # decode trusts every token as real (CausalTransformer.__call__)
    valid = jnp.ones(x.shape[:2], jnp.bool_)
    x, mutated = block.apply(variables, x, valid, False, True,
                             positions=positions, pages=pages,
                             seq_lens=seq_lens, rows=rows, mutable=["cache"])
    return x, mutated["cache"]


class CausalTransformer(nn.Module):
    """Decoder-only LM over int32 token ids [B, L]; id 0 = padding.

    ``moe_every > 0`` replaces every ``moe_every``-th block's MLP with routed
    experts (kubeml_tpu.parallel.moe, sharded over the ``ep`` mesh axis),
    GShard-style interleaving for training; 0 (default) is the dense model.
    The expert layer that serves is ``mlp="experts"`` (below)."""

    vocab_size: int = 32000
    max_len: int = 2048
    embed_dim: int = 512
    depth: int = 8
    num_heads: int = 8
    mlp_ratio: int = 4
    dropout: float = 0.0
    mesh: Optional[Mesh] = None
    sp_impl: str = "ring"  # sequence-parallel scheme: "ring" | "ulysses"
    dtype: Any = jnp.float32  # computation dtype; params stay f32
    # rematerialize dense blocks in backward (jax.checkpoint): trades ~1/3 more
    # FLOPs for O(depth) -> O(1) activation memory — the standard long-context
    # HBM lever. MoE blocks are left unrematerialized (their sown aux-loss
    # collection does not thread through nn.remat).
    remat: bool = False
    # --- HF GPT-2 compatibility (kubeml_tpu.interop.import_hf_gpt2) ---
    ln_eps: float = 1e-6    # GPT-2 uses 1e-5
    attn_bias: bool = False
    # --- positions: "learned" (GPT-2 style absolute table, capped at
    # max_len), "rope" (ops.rotary — no table; plain forward extrapolates
    # past max_len, which then only gates the decode cache capacity) or
    # "none" (no positional term anywhere: the order of the tokens reaches
    # the model through its causal mask and its recurrent layers alone) ---
    pos: str = "learned"
    rope_theta: float = 10000.0
    # --- MoE interleaving ---
    moe_every: int = 0
    num_experts: int = 8
    top_k: int = 2
    # per-expert capacity at TRAINING time (Switch-style; overflow falls
    # through the residual). Decode always routes uncapped — capacity
    # competition is not causally consistent (parallel/moe.py)
    moe_capacity: float = 1.25
    # --- paged KV cache (decode only; kubeml_tpu.serving.kvpool clones
    # these in — page_tokens tokens per physical page, kv_pages pages in
    # the shared arena). 0/0 keeps the dense per-row cache. ``paged_attn``
    # picks the arena READ path: "pallas" streams pages through the
    # ops/paged_attention.py kernel, "gather" materializes the table as a
    # contiguous block (parity oracle), "auto" = pallas on TPU only.
    # ``kv_quant`` picks the arena STORAGE dtype: "int8" quantizes pages
    # with per-page-per-head scale arenas so the same byte budget holds
    # 2-4x the tokens; "off" (default) stores the compute dtype. ---
    page_tokens: int = 0
    kv_pages: int = 0
    paged_attn: str = "auto"
    kv_quant: str = "off"
    # --- the block's kinds. ``norm``: "layernorm" | "rmsnorm" (also the
    # final norm). ``mlp``: "gelu" (biased, at mlp_ratio) | "swiglu" (gated,
    # no bias); ``mlp_dim`` > 0 sets the MLP's width outright.
    # ``num_kv_heads`` / ``head_dim``: grouped-query attention at a head size
    # of its own (0 = num_heads, embed_dim / num_heads). ``ssm``: a Mamba-2
    # mixer beside attention in every block (models/mamba2.py); its
    # recurrent state lives in the cache collection, ``state_rows`` rows of
    # it (the serving layer clones that in with the arena's sizes; 0 = the
    # batch). ``mup``: the constant multipliers of a muP checkpoint.
    # ``mla``: multi-head latent attention in place of K/V heads
    # (models/mla.py; rotary positions, or none at all with its
    # ``mla_use_nope``; its paged arena holds one latent a token,
    # ``mla.latent_width`` values, and it has no dense decode cache).
    # ``mlp="experts"`` with ``experts``: routed experts and a shared one
    # (models/experts.py), after ``dense_layers`` leading layers whose MLP
    # is a SwiGLU of ``mlp_dim``: the one layer pattern a stack can have.
    # ``mlp="shortcut"`` with ``experts``: every layer a :class:`ShortcutBlock`
    # (two attentions, two SwiGLUs of ``mlp_dim``, the experts on a shortcut),
    # so ``depth`` counts double layers and a layer holds two caches. ---
    norm: str = "layernorm"
    mlp: str = "gelu"
    mlp_dim: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    ssm: Optional[SSMConfig] = None
    mup: Optional[MuP] = None
    state_rows: int = 0
    mla: Optional[MLAConfig] = None
    experts: Optional[ExpertsConfig] = None
    dense_layers: int = 0
    # --- the residual path. ``hc_mult`` 0: one stream, ``x = x + y``.
    # ``hc_mult`` n > 0: manifold-constrained hyper-connections over n
    # streams (ops/hyper_connection.py; the other three are the published
    # ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_max``). The
    # embedding fans out into n copies, the stream between layers is
    # [B, L, n E], and the last layer's streams are summed before ``ln_f``.
    # Every program takes the stream's shape from here; nothing in the
    # serving layer knows its width. ---
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    # --- attention that differs by layer. ``attn_kinds``: the kinds of
    # attention layer the stack has (:class:`AttnKind`: K/V head count,
    # rotary base, window, sink); ``attn_pattern[i]``: which of them layer
    # ``i`` is (MiMo-V2-Flash's ``hybrid_layer_pattern``: 0 full, 1 window).
    # Empty: every layer attends alike, by ``num_kv_heads`` and
    # ``rope_theta`` above. A kind of LAYER is then its attention kind and
    # its MLP kind together, one trace of ``_decode_block`` each. For every
    # kind alike: ``v_head_dim`` (V heads' width; 0 = ``head_dim``),
    # ``partial_rotary_factor`` (the share of a head that turns),
    # ``value_scale`` (``attention_value_scale``). A window layer's paged
    # cache is a ring of pages a row in an arena of ``window_pages`` pages
    # (the serving layer clones that in beside ``kv_pages``), addressed
    # through a table of its own: ``pages`` is then ``(full layers' table,
    # window layers' rings)``. ---
    attn_kinds: Tuple[AttnKind, ...] = ()
    attn_pattern: Tuple[int, ...] = ()
    v_head_dim: int = 0
    partial_rotary_factor: float = 1.0
    value_scale: float = 1.0
    window_pages: int = 0
    # --- a token mixer that is not attention, by the same pattern: a kind
    # with ``AttnKind.linear`` is a delta-rule layer of ``gdn``'s sizes
    # (models/gated_deltanet.py: a ``GDNConfig``'s Gated DeltaNet, one decay
    # a head, or a ``KDAConfig``'s Kimi Delta Attention, one a key channel):
    # a recurrent state a program row (``state_rows``), no paged cache. The
    # pattern holds under ``mla`` too: a layer is then ``linear`` or the
    # stack's latent attention, and only the latter holds a latent arena.
    # ``norm_at``: "input" (``x +
    # f(norm(x))``) or "output" (``x + norm(f(x))``, Olmo 2's block) for
    # every layer's two norms. ``qk_norm``: RMSNorm over the whole query and
    # key projections of every attention layer. ---
    gdn: Optional[Union[GDNConfig, KDAConfig]] = None
    norm_at: str = "input"
    qk_norm: bool = False

    @property
    def layer_cls(self):
        """The class of every layer of the stack."""
        return ShortcutBlock if self.mlp == "shortcut" else GPTBlock

    def attn_kind(self, i: int) -> AttnKind:
        """Layer ``i``'s kind of attention."""
        if not self.attn_kinds:
            return AttnKind(self.num_kv_heads, self.rope_theta)
        return self.attn_kinds[self.attn_pattern[i]]

    @nn.compact
    def __call__(self, token_ids, train: bool = False, decode: bool = False,
                 return_hidden: bool = False, positions=None, pages=None,
                 seq_lens=None, exit_layer: Optional[int] = None, rows=None,
                 head_positions=None):
        # ``rows`` [B] (recurrent models, decode only): each batch row's
        # place in the cache's per-row state, for programs whose rows are
        # not the state's (an admission); None = row b is state row b
        # ``head_positions`` [B] (traced ints in [0, L)): the ONE position
        # of each row, within this call's L, that goes on to the output
        # head; the logits come back [B, 1, vocab]. An admission samples
        # one token a row, and the read-out, ln_f and lm_head are all
        # per-position, so the row is gathered before them: no [L, vocab]
        # product, no float32 logits of the whole bucket. None = every
        # position (training, generate, a decode step, and the speculative
        # verify, which accepts against all k + 1 rows)
        # ``exit_layer`` (a TRACE-TIME int in [1, depth]) runs only the
        # first ``exit_layer`` blocks, then ln_f + lm_head — the early-exit
        # self-drafting head for speculative decoding (models.generation /
        # serving spec mode). Untouched blocks' cache variables pass through
        # the mutable collection unchanged, so a truncated drafter forward
        # and the full verify forward share one paged arena: the drafter
        # writes layers < exit_layer, the verify re-writes them with
        # identical bytes and fills the rest.
        token_ids = token_ids.astype(jnp.int32)
        B, L = token_ids.shape
        if decode:
            # Decode trusts every input token as real: prompts must be dense
            # (models.generation's contract) and the sampling loop may
            # legitimately emit id 0 (a live vocab token in e.g. GPT-2) —
            # deriving validity from != PAD_ID here would silently drop such
            # tokens from the cache's attention window.
            valid = jnp.ones((B, L), jnp.bool_)
        else:
            valid = token_ids != PAD_ID
        if self.pos not in ("learned", "rope", "none"):
            raise ValueError(f"unknown pos {self.pos!r} (valid: 'learned', "
                             f"'rope', 'none')")
        use_rope = self.pos == "rope"
        table = self.pos == "learned"
        x = nn.Embed(self.vocab_size, self.embed_dim, name="token_embed",
                     embedding_init=_part((None, "tp"))(nn.initializers.normal(0.02)))(token_ids)
        mup = self.mup or MuP()
        x = _scaled(x, mup.embedding)
        if table:
            pos = self.param("pos_embed",
                             _part((None, None, "tp"))(nn.initializers.normal(0.02)),
                             (1, self.max_len, self.embed_dim))
        if decode:
            # absolute positions continue from the shared cache cursor (the
            # per-layer attention caches keep their own identical copies; this
            # one feeds the position embedding / exists for parity under rope)
            cursor = self.variable("cache", "index",
                                   lambda: jnp.zeros((), jnp.int32))
            if positions is not None:
                # per-row cursors (continuous batching): the shared scalar is
                # meaningless, each row's position embedding is its own
                # gather. ``positions`` is the logical position of the FIRST
                # token this call (L == 1 per-token steps; L > 1 paged
                # suffix prefill) — the clip keeps bucket-padding rows,
                # whose nominal positions can run past the table, from an
                # out-of-bounds gather (their output is discarded anyway).
                if not table:
                    x = x.astype(self.dtype)
                else:
                    pos_full = jnp.clip(
                        positions[:, None] + jnp.arange(L),
                        0, self.max_len - 1)  # [B, L]
                    x = (x + pos[0][pos_full]).astype(self.dtype)
            else:
                i0 = cursor.value
                cursor.value = i0 + L
                if not table:
                    x = x.astype(self.dtype)  # position enters inside attention
                else:
                    pos_slice = jax.lax.dynamic_slice(
                        pos, (0, i0, 0), (1, L, self.embed_dim))
                    x = (x + pos_slice).astype(self.dtype)
        elif not table:
            x = x.astype(self.dtype)
        else:
            x = (x + pos[:, :L]).astype(self.dtype)
        if pages is not None and self.moe_every > 0:
            # MoEBlock's expert attention has no paged path; the serving
            # layer probes this and falls back to the dense engine
            raise ValueError("paged decode does not cover MoE-interleaved "
                             "models; serve them through the dense cache")
        if exit_layer is not None:
            if not (1 <= int(exit_layer) <= self.depth):
                raise ValueError(
                    f"exit_layer must be in [1, depth={self.depth}], got "
                    f"{exit_layer}")
            if self.moe_every > 0 or self.mlp in ("experts", "shortcut"):
                raise ValueError("early-exit drafting does not cover "
                                 "expert models")
        run_depth = self.depth if exit_layer is None else int(exit_layer)
        fields = dict(
            mesh=self.mesh, sp_impl=self.sp_impl, dtype=self.dtype,
            ln_eps=self.ln_eps, attn_bias=self.attn_bias,
            cache_len=self.max_len if decode else 0,
            rope=use_rope, rope_theta=self.rope_theta,
            page_tokens=self.page_tokens, kv_pages=self.kv_pages,
            paged_attn=self.paged_attn, kv_quant=self.kv_quant,
            norm=self.norm, mlp=self.mlp, mlp_dim=self.mlp_dim,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            ssm=self.ssm, mup=self.mup, state_rows=self.state_rows,
            mla=self.mla, experts=self.experts,
            v_head_dim=self.v_head_dim,
            partial_rotary_factor=self.partial_rotary_factor,
            value_scale=self.value_scale, qk_norm=self.qk_norm,
            norm_at=self.norm_at, gdn=self.gdn,
            hc=HCConfig(self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps,
                        self.hc_clamp, self.ln_eps) if self.hc_mult else None)
        if self.attn_kinds:
            if len(self.attn_pattern) < self.depth or not all(
                    0 <= a < len(self.attn_kinds) for a in self.attn_pattern):
                raise ValueError(
                    f"attn_pattern names a kind of attn_kinds for each of "
                    f"the {self.depth} layers")
            if self.mlp == "shortcut":
                raise ValueError("attention kinds by layer are one "
                                 "attention a layer's, not a double layer's")
            if self.mla is not None and any(
                    a != AttnKind(linear=a.linear) for a in self.attn_kinds):
                raise ValueError("under latent attention a kind of layer "
                                 "is AttnKind(linear=True) or AttnKind(): "
                                 "K/V heads, a rotary base, a window and a "
                                 "sink are K/V-head attention's")
        # the full layers' page table and the window layers' rings
        tables = (tuple(pages) if isinstance(pages, (tuple, list))
                  else (pages, None))
        if self.hc_mult:
            if self.moe_every > 0:
                raise ValueError("hyper-connections do not cover "
                                 "moe_every's block (parallel/moe.py)")
            x = jnp.tile(x, (1, 1, self.hc_mult))
        if (self.mlp in ("experts", "shortcut")) != (self.experts is not None):
            raise ValueError("mlp='experts' (or 'shortcut') and an "
                             "ExpertsConfig go together")
        if self.mlp == "shortcut" and (self.dense_layers or self.moe_every):
            raise ValueError("a stack of shortcut layers has no other kind "
                             "of layer (dense_layers, moe_every)")
        if (self.mla is not None and not use_rope
                and not self.mla.mla_use_nope):
            raise ValueError("latent attention takes rotary positions "
                             "(pos='rope') unless it rotates nothing "
                             "(MLAConfig.mla_use_nope)")
        # the stack's pattern: layer i's MLP kind, and with attn_kinds its
        # attention kind beside it
        mlp_of = lambda i: ("swiglu" if self.mlp == "experts"
                            and i < self.dense_layers else self.mlp)
        kind_of = lambda i: (mlp_of(i) if not self.attn_kinds
                             else (self.attn_pattern[i], mlp_of(i)))

        shared_fields = fields

        def fields_of(kind) -> dict:
            if not self.attn_kinds:
                return {**shared_fields, "mlp": kind}
            a = self.attn_kinds[kind[0]]
            if self.mla is not None:
                # the kind says linear or latent, and nothing else
                return {**shared_fields, "mlp": kind[1], "linear": a.linear}
            return {**shared_fields, "mlp": kind[1], "num_kv_heads": a.num_kv_heads,
                    "rope_theta": a.rope_theta, "window": a.window,
                    "sink": a.sink, "linear": a.linear, "kv_pages": (
                        self.window_pages if a.window else self.kv_pages)}

        def table_of(i):
            if not self.attn_kind(i).window:
                return tables[0]
            if pages is not None and tables[1] is None:
                raise ValueError("a window layer's paged decode needs its "
                                 "rings: pages = (table, rings)")
            return tables[1]

        # a decode apply sends every layer of a kind through the one trace
        # of _decode_block: the kind's block, detached from the module and
        # so equal for all its layers, is the static argument
        layer_cls = self.layer_cls
        detached = ({kind: layer_cls(self.num_heads, self.mlp_ratio,
                                     self.dropout, parent=None,
                                     **fields_of(kind))
                     for kind in {kind_of(i) for i in range(run_depth)}}
                    if decode and not self.is_initializing() else None)
        for i in range(run_depth):
            name = f"block_{i}"
            fields = fields_of(kind_of(i))
            if self.moe_every > 0 and (i + 1) % self.moe_every == 0:
                from ..parallel.moe import MoEBlock

                x = MoEBlock(self.num_heads, self.num_experts, self.mlp_ratio,
                             self.top_k, self.moe_capacity, self.dropout,
                             mesh=self.mesh,
                             sp_impl=self.sp_impl, dtype=self.dtype,
                             rope=use_rope, rope_theta=self.rope_theta,
                             cache_len=self.max_len if decode else 0,
                             name=name)(x, valid, train=train, decode=decode,
                                        positions=positions)
            elif detached is not None:
                # the layer's own subtrees go in as arguments, and its new
                # cache lands where a bound block_i would have left it (it
                # is absent on the way in while the cache is being sized)
                vs = {"params": self.get_variable("params", name)}
                if self.has_variable("cache", name):
                    vs["cache"] = self.get_variable("cache", name)
                x, cache = _decode_block(detached[kind_of(i)], vs, x,
                                         positions, table_of(i), seq_lens,
                                         rows)
                self.put_variable("cache", name, cache)
            else:
                # static_argnums counts self as 0, so `train` (a trace-time
                # bool steering dropout determinism) is positional arg 3 and
                # `decode` arg 4; decode has no backward, so only training
                # takes the remat wrapper, and its call stays positional
                block_cls = (
                    layer_cls if decode or not self.remat
                    else nn.remat(layer_cls, static_argnums=(3, 4))
                )
                at = dict(positions=positions, pages=table_of(i),
                          seq_lens=seq_lens, rows=rows) if decode else {}
                x = block_cls(self.num_heads, self.mlp_ratio, self.dropout,
                              name=name, **fields)(x, valid, train, decode,
                                                   **at)
        if head_positions is not None:
            x = jnp.take_along_axis(x, head_positions[:, None, None], axis=1)
            L = 1
        if self.hc_mult:
            # the read-out: the streams' sum, in float32 as the norm is
            x = x.astype(jnp.float32).reshape(
                B, L, self.hc_mult, self.embed_dim).sum(axis=2)
        x = _norm(self.norm, "ln_f", self.ln_eps)(x).astype(self.dtype)
        if return_hidden:
            # final hidden states [B, L, E] for a chunked lm_head+loss
            # (parallel.trainer.chunked_lm_loss): at very long context the
            # full [B, L, vocab] logits tensor is the HBM wall AFTER flash
            # attention removes the L^2 one (measured: L=64k x 32k vocab
            # wants 8.4 GB f32), so the loss streams vocab chunks instead.
            # lm_head params still exist (init runs with the default False).
            return x
        logits = QuantizableDense(
            self.vocab_size, name="lm_head", use_bias=False, dtype=self.dtype,
            kernel_init=_part((None, "tp"))(nn.initializers.lecun_normal()))(x)
        logits = logits.astype(jnp.float32)
        return logits if mup.lm_head == 1.0 else logits * mup.lm_head


def GPTTiny(vocab_size: int = 1000, max_len: int = 128, mesh=None,
            dtype: Any = jnp.float32) -> CausalTransformer:
    """Test-sized config."""
    return CausalTransformer(vocab_size=vocab_size, max_len=max_len, embed_dim=64,
                             depth=2, num_heads=4, mesh=mesh, dtype=dtype)


def GPTSmall(vocab_size: int = 32000, max_len: int = 2048, mesh=None,
             dtype: Any = jnp.float32, attn_bias: bool = False,
             ln_eps: float = 1e-6) -> CausalTransformer:
    """GPT-2-small-ish (124M). For importing an HF gpt2 checkpoint pass
    ``vocab_size=50257, max_len=1024, attn_bias=True, ln_eps=1e-5``
    (kubeml_tpu.interop.import_hf_gpt2)."""
    return CausalTransformer(vocab_size=vocab_size, max_len=max_len, embed_dim=768,
                             depth=12, num_heads=12, mesh=mesh, dtype=dtype,
                             attn_bias=attn_bias, ln_eps=ln_eps)
