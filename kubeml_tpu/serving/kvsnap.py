"""Portable per-request KV snapshots: the ``KMS1`` frame (ISSUE 20).

The paged engine made a live request's serving state fully explicit — a
page table (serving/kvpool.py lease) plus page contents in the arena
(models/gpt.py ``kv_rows``: token rows of K‖V, so a page's K and V are
lane slices of its rows and a frame's token-major tensors come out of the
arena as they lie) plus a handful of host scalars
(prompt, emitted tokens, sampler key-split chain position). This module
serializes that state into one versioned binary frame so a generation can
be *moved*: across an engine fault (snapshot-before-reinit, replay after
rebuild), across a PS restart (graceful drain to ``KUBEML_SNAP_DIR``,
restore on next boot), and — the ROADMAP tentpoles this primitive exists
for — across replicas (prefill/decode disaggregation, elastic rebalance).

Frame layout (``application/x-kubeml-kvsnap``), the serving sibling of the
KMW1 weight wire in engine/dataplane.py::

    b"KMS1" | u8 version | u32le header_len | header JSON | chunks...

    header = {"format": "KMS1", "version": 1, "model", "request_id",
              "page_tokens", "kv_quant", "spec", "prompt_len", "out_len",
              "max_new", "temp", "topk", "eos", "key": [u32, u32],
              "npages", "compress": "raw"|"q8",
              "layers": [{"name", "dtype", "page_shape": [pt, H, D],
                          "enc": "raw"|"q8", "scales": bool}, ...]}

Chunks concatenate in a fixed order: prompt tokens (i32 LE), emitted
tokens (i32 LE), then per layer: ``k_scale`` f32 ``[npages, H]`` (int8
storage arenas only), K page data, ``v_scale``, V page data. Under
``compress="q8"`` a float K/V tensor ships a ``_q8_scale`` f32 scale
(dataplane's delta-int8 per-output-channel convention over the last axis,
i.e. per head-dim channel) followed by int8 data — lossy, so it is OFF by
default: the restore-parity guarantee (greedy continuation bit-identical
to the uninterrupted run) holds for matching storage dtype, which raw
framing preserves exactly. Int8-quantized arenas (KUBEML_SERVING_KV_QUANT)
are *already* int8 on device, so their pages always ship raw bytes plus
the arena's own per-(page, head) scale rows — bit-exact by construction.

Only pages holding **written** positions travel: a row that has emitted
``m`` tokens has attention history through position ``prompt_len + m - 2``
(the step that produced emission ``m`` wrote its input at
``prompt_len + m - 2``; the *next* step will write ``prompt_len + m - 1``),
so ``npages = ceil((prompt_len + m - 1) / page_tokens)``. Junk in the last
page's tail is harmless — decode masks by position.

The sampler chain is captured by its *root* key plus the emission count:
serving/batcher.py advances each row's key as ``k <- split(k, 2)[1]`` once
per emission, so :func:`replay_keys` reconstructs the exact device key
after ``m`` emissions from the root. Greedy rows (temp <= 0) never touch
their key; restore writes zeros, same as admission.
"""

from __future__ import annotations

import json
import math
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..engine.dataplane import DataPlaneError, _np_dtype, _q8_scale

MAGIC = b"KMS1"
VERSION = 1
CONTENT_TYPE = "application/x-kubeml-kvsnap"

# file extension the PS drain path writes under KUBEML_SNAP_DIR
SNAP_SUFFIX = ".kms"


class SnapshotError(DataPlaneError):
    """Malformed KMS1 payload or snapshot/engine geometry mismatch."""


@dataclass
class LayerSnapshot:
    """One transformer layer's gathered K/V pages.

    ``k``/``v`` are ``[npages, page_tokens, heads, head_dim]`` in the
    arena's storage dtype; ``k_scale``/``v_scale`` are the arena's
    per-(page, head) f32 dequant rows ``[npages, heads]`` when the storage
    dtype is int8, else None."""

    name: str
    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None


@dataclass
class RequestSnapshot:
    """Everything needed to rebuild one live row in any compatible arena."""

    model: str
    request_id: str
    page_tokens: int
    kv_quant: str           # arena storage mode: "none" | "int8"
    spec: str               # engine spec mode at snapshot time
    prompt: List[int]
    out: List[int]          # emitted tokens (m = len(out))
    max_new: int
    temp: float
    topk: int
    eos: int
    key: Tuple[int, int]    # ROOT sampler key (uint32 pair); chain = replay_keys
    layers: List[LayerSnapshot] = field(default_factory=list)

    @property
    def npages(self) -> int:
        return snapshot_pages_needed(len(self.prompt), len(self.out),
                                     self.page_tokens)


def snapshot_pages_needed(prompt_len: int, out_len: int,
                          page_tokens: int) -> int:
    """Pages holding written history for a row that emitted ``out_len``
    tokens: positions ``0 .. prompt_len + out_len - 2`` inclusive. Zero
    emissions means zero written pages worth shipping (the row re-prefills
    from its prompt on restore)."""
    if out_len <= 0:
        return 0
    written = prompt_len + out_len - 1
    return int(math.ceil(written / page_tokens))


def replay_keys(root: Sequence[int], emissions: int) -> np.ndarray:
    """Reconstruct the device sampler key after ``emissions`` tokens:
    the engine's per-emission advance is ``k <- jax.random.split(k, 2)[1]``
    (serving/batcher.py ``_split_rows``), starting from the row's root."""
    import jax

    key = np.asarray(root, dtype=np.uint32)
    if key.shape != (2,):
        raise SnapshotError(f"sampler key must be a uint32 pair, got "
                            f"shape {key.shape}")
    k = key
    for _ in range(int(emissions)):
        k = np.asarray(jax.random.split(k, 2)[1], dtype=np.uint32)
    return k


# --- arena access (models/gpt.py paged cache layout) ---

def paged_cache_layers(cache: dict) -> List[Tuple[str, dict]]:
    """The arena's attention blocks in layer order:
    ``[("block_0", {"kv_rows", "k_scale"?, "v_scale"?}), ...]``.
    Raises :class:`SnapshotError` for a non-paged cache."""
    blocks = []
    for name in sorted((n for n in cache if n.startswith("block_")),
                       key=lambda n: int(n.split("_", 1)[1])):
        attn = cache[name].get("attn") if isinstance(cache[name], dict) else None
        if not isinstance(attn, dict) or "kv_rows" not in attn:
            raise SnapshotError(f"cache {name!r} is not a paged attention "
                                "arena (no kv_rows)")
        blocks.append((name, attn))
    if not blocks:
        raise SnapshotError("cache holds no block_* attention arenas")
    return blocks


def gather_pages(cache: dict, pages: Sequence[int], kv_heads: int,
                 head_dim: int) -> List[LayerSnapshot]:
    """Read ``pages`` (physical page ids) out of every layer's arena onto
    the host. The indexed read serializes after every dispatched program
    that wrote the arena (value dependency), so the bytes are the true
    state through the last consumed emission. ``kv_heads`` x ``head_dim``
    is the model's K (or V) of one token: an arena row's width alone does
    not say where its zero lanes start."""
    from ..ops.paged_attention import unpack_kv_rows

    idx = np.asarray(list(pages), dtype=np.int32)
    out: List[LayerSnapshot] = []
    for name, attn in paged_cache_layers(cache):
        # [n, pt, W] rows -> the frame's [n, pt, Hkv, D] K and V
        k, v = unpack_kv_rows(np.asarray(attn["kv_rows"][idx]), kv_heads,
                              head_dim)
        ks = vs = None
        if "k_scale" in attn:
            ks = np.asarray(attn["k_scale"][idx], dtype=np.float32)
            vs = np.asarray(attn["v_scale"][idx], dtype=np.float32)
        out.append(LayerSnapshot(name=name, k=k, v=v, k_scale=ks, v_scale=vs))
    return out


def scatter_pages(cache: dict, pages: Sequence[int],
                  layers: List[LayerSnapshot]) -> dict:
    """Write snapshot pages into fresh physical ``pages`` of ``cache``;
    returns the updated cache tree (functional ``.at[].set`` — the caller
    swaps it into the slab)."""
    from ..ops.paged_attention import pack_kv_rows

    idx = np.asarray(list(pages), dtype=np.int32)
    blocks = paged_cache_layers(cache)
    if len(blocks) != len(layers):
        raise SnapshotError(f"snapshot has {len(layers)} layers but the "
                            f"arena has {len(blocks)}")
    new = {k: (dict(v) if isinstance(v, dict) else v) for k, v in cache.items()}
    for (name, attn), layer in zip(blocks, layers):
        a = dict(attn)
        rows = pack_kv_rows(layer.k, layer.v)
        if rows.shape[1:] != attn["kv_rows"].shape[1:]:
            raise SnapshotError(
                f"snapshot pages of {layer.k.shape[1:]} make rows of "
                f"{rows.shape[1:]}, the arena's are "
                f"{attn['kv_rows'].shape[1:]}")
        a["kv_rows"] = attn["kv_rows"].at[idx].set(
            rows.astype(attn["kv_rows"].dtype))
        if "k_scale" in attn:
            if layer.k_scale is None or layer.v_scale is None:
                raise SnapshotError(
                    f"arena layer {name!r} stores int8 pages but the "
                    "snapshot carries no scale rows")
            a["k_scale"] = attn["k_scale"].at[idx].set(
                layer.k_scale.astype(attn["k_scale"].dtype))
            a["v_scale"] = attn["v_scale"].at[idx].set(
                layer.v_scale.astype(attn["v_scale"].dtype))
        new[name] = dict(new[name])
        new[name]["attn"] = a
    return new


# --- wire codec ---

def snapshot_nbytes(snap: RequestSnapshot) -> int:
    """Dense payload size of the page data (histogram fodder)."""
    n = 4 * (len(snap.prompt) + len(snap.out))
    for layer in snap.layers:
        n += layer.k.nbytes + layer.v.nbytes
        if layer.k_scale is not None:
            n += layer.k_scale.nbytes + layer.v_scale.nbytes
    return n


def _emit_tensor(chunks: List[bytes], arr: np.ndarray,
                 compress: bool) -> str:
    """Append one K or V tensor; returns its wire encoding. ``q8`` ships
    the dataplane per-channel scale then int8 data (float tensors only)."""
    if compress and arr.dtype != np.int8 and arr.size:
        d = arr.astype(np.float32)
        scale = _q8_scale(d)
        q = np.clip(np.round(d / scale), -127, 127).astype(np.int8)
        chunks.append(scale.tobytes())
        chunks.append(q.tobytes())
        return "q8"
    chunks.append(np.ascontiguousarray(arr).tobytes())
    return "raw"


def encode_snapshot(snap: RequestSnapshot, compress: bool = False) -> bytes:
    """Serialize to one KMS1 frame. ``compress=True`` int8-quantizes
    float/bf16 page tensors via the dataplane scale convention (lossy —
    breaks the bit-parity guarantee; int8 arenas always ship raw)."""
    chunks: List[bytes] = [
        np.asarray(snap.prompt, dtype=np.int32).tobytes(),
        np.asarray(snap.out, dtype=np.int32).tobytes(),
    ]
    layers_meta: List[dict] = []
    for layer in snap.layers:
        if layer.k.shape != layer.v.shape:
            raise SnapshotError(f"layer {layer.name!r} K/V shape mismatch: "
                                f"{layer.k.shape} vs {layer.v.shape}")
        enc = None
        for tensor, scale in ((layer.k, layer.k_scale),
                              (layer.v, layer.v_scale)):
            if scale is not None:
                chunks.append(np.ascontiguousarray(
                    scale.astype(np.float32)).tobytes())
            enc = _emit_tensor(chunks, tensor, compress)
        layers_meta.append({
            "name": layer.name,
            "dtype": str(layer.k.dtype),
            "page_shape": list(layer.k.shape[1:]),
            "enc": enc,
            "scales": layer.k_scale is not None,
        })
    header = json.dumps({
        "format": "KMS1", "version": VERSION,
        "model": snap.model, "request_id": snap.request_id,
        "page_tokens": int(snap.page_tokens),
        "kv_quant": snap.kv_quant, "spec": snap.spec,
        "prompt_len": len(snap.prompt), "out_len": len(snap.out),
        "max_new": int(snap.max_new), "temp": float(snap.temp),
        "topk": int(snap.topk), "eos": int(snap.eos),
        "key": [int(snap.key[0]), int(snap.key[1])],
        "npages": int(snap.npages),
        "compress": "q8" if compress else "raw",
        "layers": layers_meta,
    }).encode()
    return b"".join([MAGIC, bytes([VERSION]),
                     struct.pack("<I", len(header)), header] + chunks)


def peek_header(payload: bytes) -> dict:
    """Parse and validate the frame header only (no chunk decode) — the PS
    boot-restore scan routes frames to decoders by ``header['model']``
    without materializing page bytes."""
    if len(payload) < 9 or payload[:4] != MAGIC:
        raise SnapshotError("not a KMS1 snapshot frame (bad magic)")
    ver = payload[4]
    if ver != VERSION:
        raise SnapshotError(f"KMS1 frame version {ver} unsupported "
                            f"(this build speaks v{VERSION})")
    (hlen,) = struct.unpack("<I", payload[5:9])
    try:
        header = json.loads(payload[9:9 + hlen])
    except ValueError as e:
        raise SnapshotError(f"malformed KMS1 header: {e}")
    if header.get("format") != "KMS1":
        raise SnapshotError("KMS1 header missing format tag")
    return header


def _read(payload: bytes, off: int, dtype: np.dtype,
          shape: Tuple[int, ...]) -> Tuple[np.ndarray, int]:
    count = int(np.prod(shape, dtype=np.int64))
    nbytes = count * dtype.itemsize
    if off + nbytes > len(payload):
        raise SnapshotError("KMS1 frame truncated (chunk overruns payload)")
    arr = np.frombuffer(payload, dtype=dtype, count=count,
                        offset=off).reshape(shape).copy()
    return arr, off + nbytes


def decode_snapshot(payload: bytes) -> RequestSnapshot:
    """Parse one KMS1 frame back into a :class:`RequestSnapshot`.
    Validates magic, version, and that chunks exactly consume the payload."""
    header = peek_header(payload)
    (hlen,) = struct.unpack("<I", payload[5:9])
    off = 9 + hlen
    plen = int(header["prompt_len"])
    olen = int(header["out_len"])
    prompt, off = _read(payload, off, np.dtype(np.int32), (plen,))
    out, off = _read(payload, off, np.dtype(np.int32), (olen,))
    npages = int(header["npages"])
    layers: List[LayerSnapshot] = []
    for meta in header["layers"]:
        dtype = _np_dtype(meta["dtype"])
        page_shape = tuple(int(x) for x in meta["page_shape"])
        if len(page_shape) != 3:
            raise SnapshotError(f"layer {meta['name']!r} page_shape must be "
                                f"[page_tokens, heads, head_dim], got "
                                f"{list(page_shape)}")
        heads = page_shape[1]
        shape = (npages,) + page_shape
        tensors: List[np.ndarray] = []
        scales: List[Optional[np.ndarray]] = []
        for _ in ("k", "v"):
            s = None
            if meta.get("scales"):
                s, off = _read(payload, off, np.dtype(np.float32),
                               (npages, heads))
            if meta["enc"] == "q8":
                qs, off = _read(payload, off, np.dtype(np.float32),
                                (1,) * (len(shape) - 1) + (page_shape[-1],))
                q, off = _read(payload, off, np.dtype(np.int8), shape)
                t = (q.astype(np.float32) * qs).astype(dtype)
            elif meta["enc"] == "raw":
                t, off = _read(payload, off, dtype, shape)
            else:
                raise SnapshotError(f"unknown layer encoding {meta['enc']!r}")
            tensors.append(t)
            scales.append(s)
        layers.append(LayerSnapshot(name=meta["name"], k=tensors[0],
                                    v=tensors[1], k_scale=scales[0],
                                    v_scale=scales[1]))
    if off != len(payload):
        raise SnapshotError(f"KMS1 frame has {len(payload) - off} trailing "
                            "bytes after the last chunk")
    return RequestSnapshot(
        model=header["model"], request_id=header["request_id"],
        page_tokens=int(header["page_tokens"]),
        kv_quant=header.get("kv_quant", "none"),
        spec=header.get("spec", "off"),
        prompt=[int(t) for t in prompt],
        out=[int(t) for t in out],
        max_new=int(header["max_new"]), temp=float(header["temp"]),
        topk=int(header["topk"]), eos=int(header["eos"]),
        key=(int(header["key"][0]), int(header["key"][1])),
        layers=layers)
