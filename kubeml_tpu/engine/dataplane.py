"""Weight-movement data plane: delta-encoded, compressed round updates.

What a K-AVG round pays end to end over its time on the device is staging: the
reimagined RedisAI weight hop (the reference publishes the FULL model through
RedisAI every K-AVG round, ml/pkg/model/model.go:135-161) plus host->HBM slab
staging. This module attacks the weight bytes themselves, in the spirit of
gradient compression applied to local-SGD round updates: ship the delta, not
the tree.

Three codecs behind one wire format (``KUBEML_DATAPLANE_CODEC``):

* ``raw`` — the full tree as binary chunks (already ~2x smaller than the
  JSON-of-floats the round-1 HTTP seams carried, and zero-copy to decode);
* ``delta`` — lossless: only leaves whose bytes changed since the receiver's
  last synced version ship (raw); unchanged leaves ship as ``skip`` markers.
  Frozen leaves (embeddings during fine-tune, BatchNorm constants) cost 0;
* ``delta-int8`` — the round update quantized: each changed float leaf ships
  ``round((leaf - synced)/scale)`` as int8 with the per-output-channel scale
  machinery of ops/int8_matmul.py (scale over the last axis, symmetric 127),
  an ~4x cut on the dominant f32 leaves. An **error-feedback residual** keeps
  the stream convergent: the delta is taken against the receiver-SYNCED
  state, which algebraically equals the true round update plus the residual
  of every past round's quantization error (``w_n - synced = (w_n - w_{n-1})
  + residual``) — EF-SGD with the carry folded into the base, so the
  reconstruction tracks the true weights with bounded, non-accumulating
  error.

Wire format (``application/x-kubeml-weights``)::

    b"KMW1" | u8 codec | u32le header_len | header JSON | chunks...

    header = {"codec", "version", "base_version",
              "leaves": [{"path", "dtype", "shape", "enc", "nbytes",
                          "snbytes"?}, ...]}

``enc`` is ``raw`` (nbytes of little-endian array data), ``skip`` (no bytes;
the receiver keeps its copy), or ``q8`` (snbytes of f32 scales, then nbytes
of int8 deltas). Chunks concatenate in leaf order. ``base_version`` names the
version the encoder assumed the receiver holds — a receiver at any other
version must refuse (``BaseVersionMismatch``) and re-pull a full snapshot.

Encoder and decoder are STATEFUL mirrors: after every encode/decode pair both
hold the identical reconstructed tree, which is what makes multi-round delta
chains (and error feedback) sound. :class:`WeightsWire` packages the encoder
for the serving seam: the job runner publishes each epoch's reference weights
into it and ``GET /weights?since=N`` answers with the delta when the client
is exactly one version behind, a full snapshot otherwise, and 204 when the
client is current (engine/job_runner.py, ps/parameter_server.py).
"""

from __future__ import annotations

import json
import struct
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

MAGIC = b"KMW1"
CODECS = ("raw", "delta", "delta-int8")
_CODEC_ID = {c: i for i, c in enumerate(CODECS)}

# leaves smaller than this ship raw even under delta-int8: the f32 scale
# vector + header overhead eats the win, and small leaves (biases, norm
# scales) are disproportionately quality-sensitive — same reasoning as
# serving/quant.py's MIN_QUANT_SIZE
MIN_Q8_SIZE = 1024

CONTENT_TYPE = "application/x-kubeml-weights"
VERSION_HEADER = "X-KubeML-Weights-Version"


class DataPlaneError(ValueError):
    """Malformed payload or codec misuse."""


class BaseVersionMismatch(DataPlaneError):
    """The payload's delta base is not the version this decoder holds —
    the caller must re-pull a full snapshot (``since`` unset)."""


def codec_from_env() -> str:
    from ..api.config import get_config

    codec = get_config().dataplane_codec
    if codec not in CODECS:
        import logging

        logging.getLogger("kubeml.dataplane").warning(
            "KUBEML_DATAPLANE_CODEC=%r not in %s; using 'delta'", codec, CODECS)
        return "delta"
    return codec


def _is_float_dtype(dt: np.dtype) -> bool:
    """True for any real-float dtype INCLUDING bfloat16 — ml_dtypes
    registers bf16 with kind 'V', so ``np.issubdtype(dt, np.floating)``
    alone would silently ship every bf16 leaf raw under delta-int8."""
    if np.issubdtype(dt, np.floating):
        return True
    try:
        import ml_dtypes

        return dt == np.dtype(ml_dtypes.bfloat16)
    except ImportError:
        return False


def _np_dtype(name: str) -> np.dtype:
    """Dtype from its wire name; bfloat16 needs ml_dtypes (numpy cannot
    construct it by name)."""
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def _flatten_pairs(variables: dict) -> List[Tuple[str, np.ndarray]]:
    from ..storage.checkpoint import _flatten

    return [(k, np.ascontiguousarray(a)) for k, a in _flatten(variables)]


def _unflatten(pairs: Dict[str, np.ndarray]) -> dict:
    from ..storage.checkpoint import _unflatten as _unf

    return _unf(pairs)


def _q8_scale(d: np.ndarray) -> np.ndarray:
    """Per-output-channel symmetric scale over the LAST axis for matrices
    (ops/int8_matmul.py's channel convention), per-tensor for vectors.
    Also the scale convention KMS1 request snapshots reuse for their
    optional lossy float-page compression (serving/kvsnap.py)."""
    if d.ndim >= 2:
        absmax = np.max(np.abs(d), axis=tuple(range(d.ndim - 1)),
                        keepdims=True)
    else:
        absmax = np.max(np.abs(d), keepdims=True).reshape((1,) * max(d.ndim, 1))
    return np.maximum(absmax, 1e-12).astype(np.float32) / 127.0


def _account(phase: str, nbytes: int, seconds: Optional[float],
             **attrs: Any) -> None:
    try:
        from ..utils import profiler

        if seconds is None:
            profiler.account(phase, nbytes)
        else:
            profiler.record_io(phase, nbytes, seconds, **attrs)
    except Exception:
        pass  # accounting must never fail the data path


class DeltaEncoder:
    """Stateful encoder for one receiver chain.

    ``synced`` is the receiver's reconstructed tree after its last decode
    (exactly — including quantization and dtype-cast error); the
    error-feedback carry for delta-int8 is implicit in it (the residual at
    any point is ``truth - synced``, re-shipped by the next delta). The
    first encode (no base) always ships a full raw snapshot."""

    def __init__(self, codec: str = "raw"):
        if codec not in CODECS:
            raise DataPlaneError(f"unknown codec {codec!r} (valid: {CODECS})")
        self.codec = codec
        self.version: Optional[int] = None
        self.synced: Dict[str, np.ndarray] = {}

    # -- encoding --

    def encode(self, variables: dict, version: int) -> bytes:
        """One update payload: ``variables`` at ``version`` against the
        current synced state (full snapshot when there is none)."""
        import time

        t0 = time.perf_counter()
        pairs = _flatten_pairs(variables)
        base = self.version if self.synced else None
        fresh = base is None
        leaves: List[dict] = []
        chunks: List[bytes] = []
        dense = 0
        new_synced: Dict[str, np.ndarray] = {}
        for path, arr in pairs:
            dense += arr.nbytes
            entry: Dict[str, Any] = {
                "path": path, "dtype": str(arr.dtype),
                "shape": list(arr.shape),
            }
            prev = None if fresh else self.synced.get(path)
            if fresh or self.codec == "raw" or prev is None \
                    or prev.dtype != arr.dtype or prev.shape != arr.shape:
                self._emit_raw(entry, chunks, arr)
            elif self.codec == "delta":
                if np.array_equal(prev, arr):
                    entry["enc"], entry["nbytes"] = "skip", 0
                    new_synced[path] = prev
                else:
                    self._emit_raw(entry, chunks, arr)
            else:  # delta-int8
                self._emit_q8(entry, chunks, path, prev, arr, new_synced)
            if entry["enc"] != "skip" and entry["enc"] != "q8":
                new_synced[path] = arr
            leaves.append(entry)
        header = json.dumps({
            "codec": self.codec, "version": int(version),
            "base_version": base, "leaves": leaves,
        }).encode()
        payload = b"".join(
            [MAGIC, bytes([_CODEC_ID[self.codec]]),
             struct.pack("<I", len(header)), header] + chunks)
        # encoder chains from what the receiver reconstructs, not the truth
        self.synced = new_synced
        self.version = int(version)
        _account(f"weights.encode.{self.codec}", len(payload),
                 time.perf_counter() - t0, dense_bytes=dense, version=version)
        _account("weights.encode.dense", dense, None)
        return payload

    @staticmethod
    def _emit_raw(entry: dict, chunks: List[bytes], arr: np.ndarray) -> None:
        data = arr.tobytes()
        entry["enc"], entry["nbytes"] = "raw", len(data)
        chunks.append(data)

    def _emit_q8(self, entry: dict, chunks: List[bytes], path: str,
                 prev: np.ndarray, arr: np.ndarray,
                 new_synced: Dict[str, np.ndarray]) -> None:
        if np.array_equal(prev, arr):
            # the receiver holds this leaf bit-exactly (frozen embedding,
            # BatchNorm constant): a skip marker costs 0 — without this a
            # frozen quantizable leaf would ship a full all-zero q8 payload
            # + scale vector every round forever
            entry["enc"], entry["nbytes"] = "skip", 0
            new_synced[path] = prev
            return
        quantizable = _is_float_dtype(arr.dtype) and arr.size >= MIN_Q8_SIZE
        if not quantizable:
            self._emit_raw(entry, chunks, arr)
            new_synced[path] = arr
            return
        # the delta against the RECEIVER-SYNCED state is algebraically the
        # true round update PLUS the error-feedback residual:
        #   w_n - synced_{n-1} = (w_n - w_{n-1}) + (w_{n-1} - synced_{n-1})
        # so every past round's quantization (and dtype-cast) error feeds
        # back into this round's update and the chain error stays bounded
        # instead of random-walking — EF-SGD with the residual carried
        # implicitly by the base. (Adding the tracked residual EXPLICITLY
        # on top would double-count it; measured to overshoot ~10x.)
        d = arr.astype(np.float32) - prev.astype(np.float32)
        scale = _q8_scale(d)
        q = np.clip(np.round(d / scale), -127, 127).astype(np.int8)
        recon = (prev.astype(np.float32) + q.astype(np.float32) * scale
                 ).astype(arr.dtype)
        new_synced[path] = recon
        sdata = scale.tobytes()
        qdata = q.tobytes()
        entry.update(enc="q8", nbytes=len(qdata), snbytes=len(sdata),
                     sshape=list(scale.shape))
        chunks.append(sdata)
        chunks.append(qdata)


class DeltaDecoder:
    """The receiving mirror: holds the reconstructed flat tree + version and
    applies raw/skip/q8 chunks. ``decode`` returns the nested variables tree
    (fresh leaf arrays each update — previously returned trees stay valid)."""

    def __init__(self):
        self.version: Optional[int] = None
        self.tree: Dict[str, np.ndarray] = {}

    def decode(self, payload: bytes) -> Tuple[dict, int]:
        import time

        t0 = time.perf_counter()
        if len(payload) < 9 or payload[:4] != MAGIC:
            raise DataPlaneError("not a kubeml weights payload (bad magic)")
        (hlen,) = struct.unpack("<I", payload[5:9])
        try:
            header = json.loads(payload[9:9 + hlen])
        except ValueError as e:
            raise DataPlaneError(f"malformed payload header: {e}")
        codec = header.get("codec")
        base = header.get("base_version")
        version = int(header["version"])
        if base is not None and base != self.version:
            raise BaseVersionMismatch(
                f"payload delta base is v{base} but this decoder holds "
                f"{'nothing' if self.version is None else f'v{self.version}'}")
        off = 9 + hlen
        tree: Dict[str, np.ndarray] = {}
        for leaf in header["leaves"]:
            path, enc = leaf["path"], leaf["enc"]
            dtype = _np_dtype(leaf["dtype"])
            shape = tuple(leaf["shape"])
            if enc == "skip":
                if path not in self.tree:
                    raise DataPlaneError(
                        f"skip chunk for {path!r} but no synced copy held")
                tree[path] = self.tree[path]
                continue
            if enc == "raw":
                n = leaf["nbytes"]
                # copy out of the payload: a frombuffer VIEW would keep the
                # whole payload bytes alive for as long as the leaf is
                # skip-forwarded — one frozen leaf from the initial full
                # snapshot would pin an entire model's bytes in the decoder
                # forever (and hand out read-only arrays)
                tree[path] = np.frombuffer(
                    payload, dtype=dtype, count=int(np.prod(shape, dtype=np.int64)),
                    offset=off).reshape(shape).copy()
                off += n
                continue
            if enc != "q8":
                raise DataPlaneError(f"unknown leaf encoding {enc!r}")
            prev = self.tree.get(path)
            if prev is None:
                raise DataPlaneError(
                    f"q8 delta for {path!r} but no synced copy held")
            sn, n = leaf["snbytes"], leaf["nbytes"]
            scale = np.frombuffer(payload, np.float32,
                                  count=sn // 4, offset=off
                                  ).reshape(tuple(leaf["sshape"]))
            off += sn
            q = np.frombuffer(payload, np.int8, count=n,
                              offset=off).reshape(shape)
            off += n
            tree[path] = (prev.astype(np.float32)
                          + q.astype(np.float32) * scale).astype(dtype)
        self.tree = tree
        self.version = version
        _account(f"weights.decode.{codec}", len(payload),
                 time.perf_counter() - t0, version=version)
        return _unflatten(tree), version


def encode_tree(variables: dict, version: int = 1,
                codec: str = "raw") -> bytes:
    """One-shot full-snapshot encode (no delta chain)."""
    return DeltaEncoder(codec).encode(variables, version)


def decode_tree(payload: bytes) -> Tuple[dict, int]:
    """One-shot decode of a full-snapshot payload."""
    return DeltaDecoder().decode(payload)


class WeightsWire:
    """Server-side publisher for the HTTP weight seam.

    One delta chain serves every puller: publish N encodes the delta
    ``N-1 -> N`` once; a client at ``since == N-1`` gets that cached delta,
    a client further behind (or fresh) gets a full raw snapshot of the
    RECONSTRUCTED tree (so its future deltas chain bit-identically), and a
    current client gets ``("current", N)``. State is O(1 model) regardless
    of client count."""

    def __init__(self, codec: Optional[str] = None):
        self.codec = codec or codec_from_env()
        self._encoder = DeltaEncoder(self.codec)
        self._lock = threading.Lock()
        self._delta: Optional[bytes] = None  # prev_version -> version
        self._prev_version: Optional[int] = None
        self._full: Optional[bytes] = None  # lazy snapshot cache
        self.version: Optional[int] = None

    def publish(self, variables: dict, version: int) -> None:
        with self._lock:
            prev = self._encoder.version if self._encoder.synced else None
            payload = self._encoder.encode(variables, version)
            if prev is None:
                # the first encode IS the full snapshot
                self._delta, self._prev_version, self._full = None, None, payload
            else:
                self._delta, self._prev_version, self._full = payload, prev, None
            self.version = int(version)

    def get(self, since: Optional[int] = None):
        """``None`` when nothing is published yet; ``("current", version)``
        when ``since`` is up to date; else ``(payload, version)``."""
        with self._lock:
            if self.version is None:
                return None
            if since is not None and since == self.version:
                return ("current", self.version)
            if (since is not None and self._delta is not None
                    and since == self._prev_version):
                return (self._delta, self.version)
            if self._full is None:
                # snapshot of the reconstructed chain state, version preserved
                full = DeltaEncoder("raw")
                self._full = full.encode(
                    _unflatten(dict(self._encoder.synced)), self.version)
            return (self._full, self.version)
