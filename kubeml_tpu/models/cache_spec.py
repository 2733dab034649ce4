"""What a model's paged caches are, said once by the model's side.

:func:`cache_spec` reads a module's declared fields (models/gpt.py
``CausalTransformer``) into one frozen :class:`CacheSpec`: every sub-layer
that keeps a paged cache, in the stack's order, and what rides beside the
caches (recurrent state, routed experts, residual streams). A serving engine
asks once, at its construction, and reads every size, sum and property off
the answer; nothing above this module looks at the fields again. ``layers``
lists the sub-layers that PAGE (a layer whose token mixer keeps a recurrent
state and no keys, models/gated_deltanet.py, is not among them: it counts in
``state_layers``), so a stack of 8 layers may hold 2 arenas, of K and V
heads or of latents alike. How a row
is STORED stays where the arenas are written (``ops/paged_attention.py
kv_row_width``, ``models/mla.py MLAConfig.row_width``) and is called from
here.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Optional, Tuple

import jax.numpy as jnp

from ..ops.paged_attention import kv_row_width, ring_pages

# what a model's caches can be, in the order a refusal names them
PROPERTIES = ("recurrent", "latent", "window", "experts")


@dataclass(frozen=True)
class CacheLayer:
    """One sub-layer's paged cache. K and V of ``kv_heads`` heads, ``k_dim``
    and ``v_dim`` wide: a page for every ``page_tokens`` positions
    (``window`` 0, a full layer) or a ring of pages a row (``window`` > 0
    keys a query sees, models/gpt.py AttnKind). Or, ``latent_width`` > 0,
    one latent vector a token and no heads (models/mla.py): that many live
    values stored in ``latent_row_width`` lanes."""

    kv_heads: int = 0
    k_dim: int = 0
    v_dim: int = 0
    window: int = 0
    latent_width: int = 0
    latent_row_width: int = 0

    @property
    def live(self) -> int:
        """Values a cached token holds here: what reading it touches."""
        return self.latent_width or self.kv_heads * (self.k_dim + self.v_dim)

    @property
    def lanes(self) -> int:
        """Lanes its arena stores them in: whole 128-lane rows."""
        return self.latent_row_width or kv_row_width(
            self.kv_heads, self.k_dim, self.v_dim)


@dataclass(frozen=True)
class CacheSpec:
    """A model's caches. ``layers`` is empty for a module that doesn't
    expose the transformer geometry: every sum is then 0 and the KV
    accounting is skipped."""

    layers: Tuple[CacheLayer, ...] = ()
    # layers whose program rows carry recurrent state (a Mamba-2 mixer
    # beside attention; a Gated DeltaNet mixer in its place), and the bytes
    # one row's state takes in one of them, its convolution's tail included
    state_layers: int = 0
    state_row_bytes: int = 0
    # values a row's gate carries a step and head in such a layer: 1 (one
    # decay a head) or the keys' width (one a key channel)
    state_gate_width: int = 0
    # layers whose feed-forward is routed experts, the choices a token
    # makes in each, and the experts of a layer whose weights are here
    expert_layers: int = 0
    experts_per_token: int = 0
    experts_held: int = 0
    # sub-layers whose residual path mixes several streams, and the streams
    residual_sublayers: int = 0
    residual_streams: int = 1
    itemsize: int = 4   # of the compute type, which the arenas store

    @property
    def sublayers(self) -> int:
        return len(self.layers)

    @property
    def recurrent(self) -> bool:
        """Rows carry recurrent state beside (or in place of) pages."""
        return self.state_layers > 0

    def state_bytes(self, rows: int) -> int:
        """HBM bytes the recurrent state of ``rows`` program rows takes,
        all layers that keep one."""
        return rows * self.state_layers * self.state_row_bytes

    @cached_property
    def window_layers(self) -> int:
        return sum(1 for layer in self.layers if layer.window)

    @property
    def full_layers(self) -> int:
        return self.sublayers - self.window_layers

    @cached_property
    def window(self) -> int:
        """The widest window (every window layer's ring is that wide, so
        one table of rings serves them all); 0 without window layers."""
        return max((layer.window for layer in self.layers), default=0)

    @cached_property
    def latent(self) -> Optional[CacheLayer]:
        """The latent layers' one shape; None for a model that pages K/V."""
        return next((layer for layer in self.layers if layer.latent_width),
                    None)

    @cached_property
    def properties(self) -> frozenset:
        """Which of :data:`PROPERTIES` the model has: what an engine's
        table of features is keyed by (serving/batcher.py CACHE_FEATURES)."""
        has = {"recurrent": self.recurrent, "latent": self.latent is not None,
               "window": self.window_layers > 0,
               "experts": self.expert_layers > 0}
        return frozenset(name for name in PROPERTIES if has[name])

    def ring_pages(self, page_tokens: int) -> int:
        """Pages of a window layer's ring a row; 0 without window layers
        or without a page size."""
        if not self.window or not page_tokens:
            return 0
        return ring_pages(self.window, page_tokens)

    def token_bytes(self, kv_quant: str = "off",
                    first: Optional[int] = None) -> int:
        """HBM bytes attention reads per CACHED TOKEN per forward pass in
        the layers that are not window layers (of the ``first`` sub-layers:
        an early-exit drafter's stack): K and V by each layer's own heads
        and widths, or its one latent vector, once. The KV-read accounting
        (kubeml_serving_kv_read_bytes_total) multiplies this by the
        host-modeled gathered-token count per dispatch: a geometry model of
        the device's read traffic, not a hardware counter. It models
        STORAGE bytes, so an int8 arena reads one byte an element (the
        per-page scale reads are noise against the element reads and stay
        unmodeled)."""
        itemsize = 1 if kv_quant == "int8" else self.itemsize
        return itemsize * sum(layer.live for layer in self.layers[:first]
                              if not layer.window)

    def window_token_bytes(self) -> int:
        """HBM bytes the WINDOW layers read per key a query sees, all of
        them. A step reads at most ``window`` keys a row in such a layer,
        whatever the row's depth."""
        return self.itemsize * sum(layer.live for layer in self.layers
                                   if layer.window)

    def page_bytes(self, page_tokens: int, kv_quant: str = "off") -> int:
        """HBM bytes ONE physical page occupies across the arenas of the
        layers that are not window layers: the unit of the arena byte
        budget, ``page_tokens`` rows as each arena stores them (zero lanes
        past a narrow model's K and V count; 640 lanes hold the published
        latent's 576). int8 mode adds the page's per-head f32 scale rows
        (k_scale / v_scale, [kv_pages, H]) so the capacity derivation
        charges quantization's real overhead."""
        full = [layer for layer in self.layers if not layer.window]
        if kv_quant == "int8":
            return sum(page_tokens * layer.lanes + 2 * layer.kv_heads * 4
                       for layer in full)
        return page_tokens * self.itemsize * sum(l.lanes for l in full)

    def ring_page_bytes(self, page_tokens: int) -> int:
        """HBM bytes ONE page of a ring occupies across the window layers'
        arenas."""
        return page_tokens * self.itemsize * sum(
            layer.lanes for layer in self.layers if layer.window)


def cache_spec(module) -> CacheSpec:
    """The :class:`CacheSpec` of ``module``, from its declared fields alone
    (no trace, no device work)."""
    depth = int(getattr(module, "depth", 0) or 0)
    heads = int(getattr(module, "num_heads", 0) or 0)
    embed = int(getattr(module, "embed_dim", 0) or 0)
    # what a layer of the stack's class holds: one cache; two where a layer
    # is a double layer of two attentions (models/gpt.py ShortcutBlock)
    per_layer = getattr(getattr(module, "layer_cls", None),
                        "cache_sublayers", 1)
    mla = getattr(module, "mla", None)
    layers: Tuple[CacheLayer, ...] = ()
    # the stack's kinds of token mixer, where they differ by layer; a
    # ``linear`` layer keeps a state and no pages
    kinds = ([module.attn_kind(i) for i in range(depth)]
             if getattr(module, "attn_kinds", ()) else None)
    linear = sum(1 for a in kinds if a.linear) if kinds else 0
    if mla is not None:
        layers = (CacheLayer(latent_width=int(mla.latent_width),
                             latent_row_width=int(mla.row_width)),
                  ) * ((depth - linear) * per_layer)
    elif heads and embed:
        k_dim = int(getattr(module, "head_dim", 0) or embed // heads)
        v_dim = int(getattr(module, "v_head_dim", 0) or k_dim)
        if kinds:
            by_layer = [(int(a.num_kv_heads or heads), int(a.window))
                        for a in kinds if not a.linear]
        else:
            by_layer = [(int(getattr(module, "num_kv_heads", 0) or heads),
                         0)] * depth
        layers = tuple(CacheLayer(kv_heads, k_dim, v_dim, window)
                       for kv_heads, window in by_layer
                       for _ in range(per_layer))
    # layers whose feed-forward is routed experts: all but the
    # ``dense_layers`` leading ones (``mlp="shortcut"``: one in every double
    # layer); the training-side ``moe_every`` interleaving has no paged path
    # and counts none
    expert_layers = (
        max(0, depth - int(getattr(module, "dense_layers", 0)))
        if getattr(module, "mlp", None) in ("experts", "shortcut") else 0)
    experts = module.experts if expert_layers else None
    streams = int(getattr(module, "hc_mult", 0) or 0)
    # a model's one kind of state: a mixer beside attention in every layer,
    # or the mixer of its linear layers
    ssm = getattr(module, "ssm", None)
    mixer = ssm if ssm is not None else getattr(module, "gdn", None)
    state_layers = depth if ssm is not None else linear
    return CacheSpec(
        layers=layers,
        state_layers=state_layers,
        state_row_bytes=int(mixer.state_row_bytes) if state_layers else 0,
        state_gate_width=(int(getattr(mixer, "gate_width", 1))
                          if state_layers else 0),
        expert_layers=expert_layers,
        experts_per_token=int(experts.num_experts_per_tok) if experts else 0,
        experts_held=int(experts.held_range[1]) if experts else 0,
        # hyper-connections (ops/hyper_connection.py): two a layer
        residual_sublayers=2 * depth if streams else 0,
        residual_streams=streams or 1,
        itemsize=int(
            jnp.dtype(getattr(module, "dtype", jnp.float32)).itemsize))
