"""Continuous batching for KV-cache decode (the TPU serving engine).

One resident "slab" of S decode slots lives on device: per-layer KV caches
``[S, max_len, H, D]``, per-slot cursors, liveness, sampling knobs, and PRNG
keys. Requests are split into rows; each row is admitted into a free slot by
ONE fused prefill+admit program (per prompt-length bucket), and all live
slots advance together through one jitted multi-token step program.
Admission and eviction happen at chunk boundaries — the decode loop never
recompiles as traffic changes.

Why this shape on TPU:

* Decode is HBM-bound (every step re-reads the weights), so stepping 8 slots
  costs ~the same wall clock as stepping 1 — batched decode is nearly free
  throughput (chip-measured 14x from batch 1 -> 16, round 3).
* All shapes are static: S, max_len, and the chunk length T are compile-time
  constants; per-row depth differences are runtime data (a ``positions``
  vector), so XLA compiles exactly two programs (prefill+admit per bucket,
  step-chunk) for the life of the server.
* Per-row sampling knobs (temperature / top_k / eos) are runtime tensors, not
  trace constants — one program serves every knob combination, killing the
  compile-per-knob DoS surface the one-shot path has
  (``models.generation.make_generate_fn`` keys its LRU by knobs).
* The dispatch chain is PIPELINED: results are fetched up to
  ``pipeline_depth`` programs behind the newest dispatch, so the device
  never idles on host round trips (see _loop). The paged engine runs only
  as far ahead as its host's turnaround needs (``run_ahead_depth``), with
  ``pipeline_depth`` as the ceiling.

The reference has no serving runtime at all to compare against; the closest
analogue is its one-pod-per-function Fission serving
(/root/reference/ml/pkg/controller/api.go:121-160), which this replaces with
one resident program.
"""

from __future__ import annotations

import contextlib
import logging
import math
import queue
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.errors import KubeMLError
from ..models.cache_spec import PROPERTIES, CacheSpec, cache_spec
from ..models.generation import GenerationInputError, init_cache
from ..models.gpt import PAD_ID, block_traces
from ..utils import tracing
from .stats import COMPILE_PHASES

log = logging.getLogger("kubeml.serving")

# Static width of the on-device top-k scratch: per-row runtime top_k values
# are applied by thresholding against the k-th of these. Requests cap top_k
# at this bound (api.types.GENERATE_MAX_TOP_K mirrors it on the wire).
TOP_K_MAX = 128

# what _run_program enters around a jitted call when the tracer is off
_NO_ANNOTATION = contextlib.nullcontext()

# default decode-row count shared by both engines: PagedBatchingDecoder must
# size its arena BEFORE the base __init__ resolves slots, so the fallback
# lives in one place instead of two drifting literals
DEFAULT_SLOTS = 8

class DecoderClosed(KubeMLError):
    def __init__(self):
        super().__init__("decoder is shut down", 503)


# What a property of a model's caches (models/cache_spec.py PROPERTIES)
# does to a feature of the engines: "refuse" (409, CacheFeatureUnsupported)
# or "off" (served with the feature switched off, said once in the log). A
# pair that is not here is served. docs/design.md section 27 holds the same
# table beside the reasons; tests/test_cache_spec.py spells every cell out.
FEATURES = ("slot_engine", "prefix_sharing", "chunked_prefill", "int8_pages",
            "spec_self", "spec_draft", "snapshot")
CACHE_FEATURES = {
    ("recurrent", "slot_engine"): "refuse",
    ("recurrent", "prefix_sharing"): "off",
    ("recurrent", "spec_self"): "refuse",
    ("recurrent", "spec_draft"): "refuse",
    ("recurrent", "snapshot"): "refuse",
    ("latent", "slot_engine"): "refuse",
    ("latent", "int8_pages"): "refuse",
    ("latent", "snapshot"): "refuse",
    **{("window", feature): "refuse" for feature in FEATURES},
    ("experts", "spec_self"): "refuse",
}
# a property in a refusal's words, and why nothing that moves, shares or
# scales pages alone covers it
_PROPERTY_WORDS = {
    "recurrent": ("a model with recurrent state",
                  "only its KV pages would be moved, not the state beside "
                  "them"),
    "latent": ("a model with a latent KV cache",
               "its pages hold one latent vector a token, not K and V heads"),
    "window": ("a model with window layers",
               "a window layer's row holds a ring of pages, not a page for "
               "every position"),
    "experts": ("a model with routed-expert layers",
                "an early-exit drafter stops inside the stack, where the "
                "layers that differ most between tokens have not run"),
}
_FEATURE_WORDS = {
    "slot_engine": "the slot engine",
    "prefix_sharing": "prefix sharing (serving_prefix_cache)",
    "chunked_prefill": "chunked prefill (prefill_chunk_tokens)",
    "int8_pages": "int8 page storage (kv_quant=int8)",
    "spec_self": "speculative decoding (spec='self')",
    "spec_draft": "speculative decoding (spec='draft')",
    "snapshot": "taking or restoring a mid-stream snapshot",
}


class CacheFeatureUnsupported(KubeMLError):
    """A feature of the engines that a property of the model's caches
    refuses (:data:`CACHE_FEATURES`). Refused by name, never served wrong:
    from a state that was not rolled back or carried, from keys a ring has
    dropped, from pages scaled or framed by heads they do not have."""

    def __init__(self, prop: str, feature: str):
        self.property, self.feature = prop, feature
        words, why = _PROPERTY_WORDS[prop]
        super().__init__(
            f"{_FEATURE_WORDS[feature]} is not supported for {words}: {why}",
            409)


def check_cache_features(cache: CacheSpec, asked) -> dict:
    """Hold the features ``asked`` of an engine to :data:`CACHE_FEATURES`
    for the properties ``cache`` has: raises :class:`CacheFeatureUnsupported`
    for the first pair the table refuses (a refusal wins over an "off"),
    and returns ``{feature: property}`` of those it switches off."""
    pairs = [(prop, feature) for prop in PROPERTIES
             if prop in cache.properties
             for feature in FEATURES if feature in asked]
    for pair in pairs:
        if CACHE_FEATURES.get(pair) == "refuse":
            raise CacheFeatureUnsupported(*pair)
    return {feature: prop for prop, feature in pairs
            if CACHE_FEATURES.get((prop, feature)) == "off"}


# what an expert layer leaves in the cache a decode apply, in the order of
# the step block's last columns (models/experts.py)
_MOE_COUNTS = ("experts_touched", "assignments_held", "assignments_zero")


def _leaves_named(tree, *names) -> list:
    """The leaves of ``tree`` whose last path key is one of ``names``."""
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if getattr(path[-1], "key", None) in names]


def _param_shardings(module, mesh):
    """NamedSharding pytree for a causal-LM module's variables, derived from
    its own ``nn.with_partitioning`` annotations (the same derivation the
    SPMD trainer uses, parallel/trainer.py): abstract-init the module (no
    device work) and read the PartitionSpecs off the boxed params."""
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    dummy = jnp.zeros((1, 2), jnp.int32)
    abstract = jax.eval_shape(
        lambda r: module.init(r, dummy, train=False), jax.random.PRNGKey(0))
    specs = nn.get_partition_spec(abstract)
    shapes = jax.tree.map(lambda a: a.shape, nn.meta.unbox(abstract))

    def fit(spec, shape):
        # an annotated dim falls back to replication FOR THAT AXIS when the
        # mesh lacks the axis (e.g. a dp-only serving mesh) or the dim does
        # not divide it (e.g. a tiny test vocab on lm_head); production
        # meshes name tp and size dims to divide, so this is a no-op there
        axes = tuple(
            ax if (ax is None
                   or (ax in mesh.shape
                       and shape[i] % int(mesh.shape[ax]) == 0)) else None
            for i, ax in enumerate(spec))
        return NamedSharding(mesh, P(*axes))

    return jax.tree.map(fit, specs, shapes,
                        is_leaf=lambda x: isinstance(x, P))


def _quantized_shardings(qtree, dense_shardings, mesh):
    """Map a DENSE NamedSharding tree onto a quantized tree: each
    QuantizedTensor gets its kernel's sharding for ``q`` and the last
    (channel) axis's sharding for its broadcast-shaped per-channel ``s``;
    dense leaves keep their sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .quant import QuantizedTensor, _is_q

    def one(qleaf, sh):
        if not isinstance(qleaf, QuantizedTensor):
            return sh
        axes = tuple(sh.spec)
        ndim = qleaf.q.ndim
        axes = axes + (None,) * (ndim - len(axes))
        s_axes = (None,) * (ndim - 1) + (axes[-1],)
        return QuantizedTensor(q=sh, s=NamedSharding(mesh, P(*s_axes)))

    return jax.tree.map(one, qtree, dense_shardings, is_leaf=_is_q)


def storage_shardings(manifest_leaves, module, mesh):
    """Flat ``path -> NamedSharding`` tree for restoring a QUANTIZED
    (storage-form) sharded checkpoint straight onto a serving mesh: marker
    paths ``.../__q8_q__`` take their kernel's dense sharding, the
    broadcast-shaped ``.../__q8_s__`` scales take their channel axis's,
    and dense paths keep theirs — so a final-int8 restore never
    materializes a dense leaf anywhere."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..storage.sharded_checkpoint import _flatten_any, _unflatten
    from .quant import Q8_Q, Q8_S

    flat_dense = dict(_flatten_any(_param_shardings(module, mesh)))
    out = {}
    for path, spec in manifest_leaves.items():
        if path.endswith("/" + Q8_Q):
            out[path] = flat_dense[path[: -len(Q8_Q) - 1]]
        elif path.endswith("/" + Q8_S):
            sh = flat_dense[path[: -len(Q8_S) - 1]]
            ndim = len(spec["shape"])
            axes = tuple(sh.spec) + (None,) * (ndim - len(tuple(sh.spec)))
            out[path] = NamedSharding(
                mesh, P(*((None,) * (ndim - 1)), axes[-1] if axes else None))
        else:
            out[path] = flat_dense[path]
    return _unflatten(out)


def _sample_rows(logits, keys, temp, topk, active=None):
    """One next-token draw per row with PER-ROW runtime knobs.

    logits [S, V] f32, keys [S, 2] uint32, temp [S] f32 (<=0 = greedy),
    topk [S] int32 (0 = off), active [S] bool (rows whose knobs matter —
    dead slots keep stale knobs). One program serves every knob mix (knobs
    are runtime data), but the sampling branch runs under ``lax.cond`` so a
    step whose ACTIVE rows are all greedy skips the vocab-wide top-k sort +
    categorical draw — on a 32k vocab that work is a real per-step tax the
    argmax path shouldn't pay. The knob-adjusted logits come from the ONE
    shared definition (``models.generation._masked_scaled``) the
    speculative acceptance rule also samples against — the distributions
    must be the same object, not two copies kept in sync."""
    from ..models.generation import _masked_scaled

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(_):
        masked = _masked_scaled(logits, temp, topk, TOP_K_MAX)
        return jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)

    hot = temp > 0.0
    if active is not None:
        hot = hot & active
    sampled = jax.lax.cond(jnp.any(hot), draw, lambda _: greedy, None)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _split_rows(keys):
    """Per-row (use, next) key split. keys [S, 2] uint32."""
    pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # [S, 2, 2]
    return pairs[:, 0], pairs[:, 1]


class _Slab:
    """The device-resident decode state (a plain pytree container)."""

    def __init__(self, cache, tok, pos, live, remaining, keys, temp, topk, eos):
        self.cache = cache          # per-layer KV pytree, [S, ...] leaves
        self.tok = tok              # [S] i32 next token to feed
        self.pos = pos              # [S] i32 cache write position of tok
        self.live = live            # [S] bool
        self.remaining = remaining  # [S] i32 emissions still allowed
        self.keys = keys            # [S, 2] u32 per-slot PRNG state
        self.temp = temp            # [S] f32
        self.topk = topk            # [S] i32, 0 = off
        self.eos = eos              # [S] i32, -1 = off


jax.tree_util.register_pytree_node(
    _Slab,
    lambda s: ((s.cache, s.tok, s.pos, s.live, s.remaining, s.keys, s.temp,
                s.topk, s.eos), None),
    lambda _, c: _Slab(*c),
)


@dataclass
class _Row:
    """One admitted decode row (a request of batch B becomes B rows)."""

    entry: "_Entry"
    index: int
    prompt: np.ndarray  # [plen] int32, dense
    max_new: int
    temp: float
    topk: int   # 0 = off
    eos: int    # -1 = off
    key: np.ndarray  # [2] uint32 (zeros for greedy rows — never used)
    out: List[int] = field(default_factory=list)
    done: bool = False
    canceled: bool = False  # abandoned by its waiter: free the slot ASAP
    # slot pre-freed at dispatch time: every emission this row can produce
    # is already in the dispatch chain, so the slot was handed to the next
    # admission without waiting for the row's results to come back
    drained: bool = False
    # --- paged engine only (PagedBatchingDecoder) ---
    lease: Optional[object] = None  # kvpool.PageLease while pages are held
    prefix_cached: int = 0          # prompt tokens served from the prefix trie
    dispatched: int = 0             # post-admit steps already in the chain
    # host-side UPPER BOUND on the row's device write cursor across the
    # dispatch chain (prompt_len at admission, += chunk size per plain
    # chunk, += k+1 per spec macro-step, clamped at the row's final
    # position): the live-table-width clamp sizes each dispatch's page
    # table from this, so a clamped program can never trash-redirect a
    # write the device actually makes
    pos_cap: int = 0
    # speculative decoding (spec mode): candidate tokens this row sent
    # through batched verification, and drafted tokens accepted
    spec_proposed: int = 0
    spec_accepted: int = 0
    # lifecycle timeline (monotonic; 0 = not reached): slot assignment,
    # first/last token landing on the host — the phase-histogram feeds
    slot_at: float = 0.0
    first_emit_at: float = 0.0
    last_emit_at: float = 0.0
    # latency anatomy (ISSUE 18): host-visible gaps between this row's
    # consecutive emission arrivals (one entry per delta after the first),
    # and wall seconds the row lost stalled behind colocated prefill work
    itl: List[float] = field(default_factory=list)
    hol_stall: float = 0.0
    # chunked prefill (ISSUE 19): True while the row's prompt is mid-way
    # through interleaved prefill chunks — it holds a program row (its
    # pages are reserved and partially written) but is device-dead, takes
    # no decode dispatches, and is excluded from HOL-victim accounting
    # until its final chunk samples the first token
    prefilling: bool = False
    # prefill dispatches a CHUNKED row's prompt took (intermediates + the
    # final admit); stays 0 for a monolithic prefill — short suffix or
    # KUBEML_PREFILL_CHUNK_TOKENS=0
    prefill_chunks: int = 0
    # mid-stream restore (ISSUE 20): a kvsnap.RequestSnapshot whose pages
    # must scatter into fresh arena pages before this row decodes; set on
    # KMS1 admission and on fault-recovery replay, cleared at dispatch.
    # ``out`` already holds the snapshot's emissions, so admission reserves
    # via kvpool.reserve (private pages, no prefix-trie participation — the
    # bytes come from another engine's write history) instead of admit
    snapshot: Optional[object] = None


@dataclass
class _Entry:
    """One submitted request: rows + completion/stream plumbing."""

    rows: List[_Row]
    max_new: int
    stream_q: Optional[queue.Queue] = None
    done_evt: threading.Event = field(default_factory=threading.Event)
    error: Optional[Exception] = None
    submitted_at: float = 0.0   # monotonic; serving telemetry (stats.py)
    first_token_at: float = 0.0  # 0 until the first token lands
    aborted: bool = False        # timeout/cancel already counted
    # absolute request deadline (unix seconds; utils.resilience binding) —
    # a row still QUEUED past it fails fast with 504 instead of taking a slot
    deadline: Optional[float] = None
    # lifecycle attribution: a per-request id (returned in the result so
    # `kubeml trace <request-id>` finds the serving span tree) and the
    # submitter's trace context (the HTTP server span — serving spans
    # parent under it)
    request_id: str = ""
    trace_ctx: Optional[object] = None

    def finished(self) -> bool:
        return all(r.done for r in self.rows)

    def result(self) -> dict:
        tokens = [r.out + [PAD_ID] * (self.max_new - len(r.out))
                  for r in self.rows]
        return {"tokens": tokens, "lengths": [len(r.out) for r in self.rows],
                "request_id": self.request_id,
                # prompt tokens whose KV came from the shared-prefix cache
                # (summed across the request's rows; 0 on the dense engine
                # or with KUBEML_SERVING_PREFIX_CACHE off)
                "prefix_cached_tokens": sum(r.prefix_cached
                                            for r in self.rows),
                # speculative decoding attribution (0 with spec off):
                # candidate tokens verified for this request's rows, and
                # drafted tokens the acceptance rule kept
                "spec_proposed_tokens": sum(r.spec_proposed
                                            for r in self.rows),
                "spec_accepted_tokens": sum(r.spec_accepted
                                            for r in self.rows),
                # stream-smoothness attribution (ISSUE 18): quantiles over
                # the request's host-visible inter-emission gaps (0.0 for
                # single-token / streaming-in-one-delta requests), and the
                # decode-seconds its rows lost behind colocated prefill
                "itl_p99": _itl_quantile(self.rows, 0.99),
                "itl_max": _itl_quantile(self.rows, 1.0),
                "hol_stall_seconds": sum(r.hol_stall for r in self.rows),
                # chunked prefill (ISSUE 19): prefill dispatches this
                # request's prompts took beyond one — 0 means every row
                # prefilled monolithically (short prompt or knob off)
                "prefill_chunks": sum(r.prefill_chunks for r in self.rows)}


def _itl_quantile(rows: List[_Row], q: float) -> float:
    """Quantile over every inter-emission gap a request's rows observed
    (nearest-rank, the DecoderStats ring convention); 0.0 with no gaps —
    a request of n<=1 emissions has no inter-token latency."""
    gaps = sorted(g for r in rows for g in r.itl)
    if not gaps:
        return 0.0
    return gaps[min(len(gaps) - 1, max(0, int(round(q * (len(gaps) - 1)))))]


def _pow2_bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


# floor of the live-table-width pow2 bucket (PagedBatchingDecoder): every
# distinct (chunk size, table width) pair is its own compiled program, and
# widths below 8 pages save almost no bytes while doubling the program set
_MIN_TABLE_BUCKET = 8


def _bucket_width(need: int, cap: int) -> int:
    """THE live-table-width bucket: ``need`` pages rounded up the pow2
    ladder from the ``_MIN_TABLE_BUCKET`` floor, capped at the full table.
    One definition shared by chunk dispatch and admission."""
    need = max(need, min(cap, _MIN_TABLE_BUCKET))
    w = 1
    while w < need:
        w *= 2
    return min(w, cap)


def _chunk_cap(tokens: int, page_tokens: int) -> int:
    """Resolve the ``KUBEML_PREFILL_CHUNK_TOKENS`` knob to the engine's
    prefill-chunk cap: the largest power of two at most ``tokens``, floored
    at one page. A pow2 at or above ``page_tokens`` (itself a pow2) is
    always a whole number of pages, so every chunk boundary is
    page-aligned — which is what keeps int8 KV quantization bit-identical
    under chunking (a page's scale derives from exactly one chunk's
    tokens) and the prefill-program set bounded (chunk programs land on
    the same pow2 suffix-bucket keys the monolithic path compiles).
    Returns 0 (chunking disabled — monolithic prefill, the parity oracle)
    for a knob of 0 or anything below one page."""
    if tokens < page_tokens:
        return 0
    cap = page_tokens
    while cap * 2 <= tokens:
        cap *= 2
    return cap


def service_interval(dispatched: float, done: float,
                     prev_done: float) -> tuple:
    """``(svc_s, wait_s, done)`` of one program, taken where it completes.

    Programs run in dispatch order on one device stream, so a program
    starts when it has been dispatched AND the one before it is done:
    ``start = max(dispatched, prev_done)``. ``svc_s = done - start`` is its
    own time on the device (plus the trip of its result to the host) and
    ``wait_s = start - dispatched`` the time it sat behind programs
    dispatched earlier. The wall of the value fetch covers both, so with
    six programs in flight it reads six steps where this reads one.
    ``done`` comes back as a running maximum: fetcher threads may stamp two
    completions a little out of order, and the later program then reads 0,
    never a negative time. Call in dispatch order, feeding ``done`` back
    as the next ``prev_done``; all three are one clock's readings."""
    done = max(done, prev_done)
    start = max(dispatched, prev_done)
    return done - start, start - dispatched, done


# run_ahead_depth keeps this many turnarounds of the host queued behind the
# running program. 5 also puts a host as slow as its program at 1 + 5 = 6,
# the default ceiling: an engine the host bounds runs as deep as it always has
_RUN_AHEAD_MARGIN = 5


def run_ahead_depth(host_s: Optional[float], svc_s: Optional[float],
                    cap: int) -> int:
    """Programs the paged engine keeps in flight, the running one counted.

    One program queued behind the running one keeps the device fed for as
    long as the host takes to answer a completion with the next dispatch,
    so the depth is 1 + the programs that ``_RUN_AHEAD_MARGIN`` such
    turnarounds (``host_s``) cover at ``svc_s`` a decode program. Every
    further place only stands between a new request's prefill and the
    device: at 6 one-step programs a first token waited five steps. Never
    under 2 (a depth of 1 idles the device for every turnaround), never
    over ``cap`` (``pipeline_depth``: a cap of 1 still gives 1), and the
    cap itself while either estimate has no sample. Pure: the estimates
    are its only inputs."""
    if host_s is None or svc_s is None or svc_s <= 0.0:
        return cap
    need = 1 + math.ceil(_RUN_AHEAD_MARGIN * host_s / svc_s)
    return min(cap, max(2, need))


class _Recent:
    """Running estimate of a duration: the median of its last 8 samples
    (None before the first). One slow turn among them (an admission, a
    collector's pause) moves nothing; a changed regime shows after five."""

    def __init__(self):
        self._samples: deque = deque(maxlen=8)

    def add(self, seconds: float) -> None:
        self._samples.append(seconds)

    def value(self) -> Optional[float]:
        return statistics.median(self._samples) if self._samples else None


class _FetchPool:
    """The result-fetch thread pool both engine loops share: dispatched
    device programs are materialized off-thread (each fetch pays the
    host<->device round trip), the engine consumes them in dispatch order.
    ``stats`` hooks feed the kubeml_serving_fetch* observability. A result
    waits in ``done`` as ``(record, fetch start, fetch end, thread)``,
    clocks monotonic: the end is where the program is known complete."""

    def __init__(self, decoder, n: int):
        self.q: queue.Queue = queue.Queue()
        self.done: Dict[int, tuple] = {}
        self.cv = threading.Condition()
        self._decoder = decoder
        self._threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"decode-fetch-{decoder.name}-{i}")
            for i in range(n)
        ]
        for t in self._threads:
            t.start()

    def _run(self):
        dec = self._decoder
        while True:
            item = self.q.get()
            if item is None:
                return
            seq, rec = item
            dec.stats.fetch_started()
            t0 = time.monotonic()
            try:
                out = dec._materialize(rec)
            except Exception as e:  # surfaces on the engine thread
                out = ("error", e)
            finally:
                t1 = time.monotonic()
                dec.stats.fetch_finished(t1 - t0)
            with self.cv:
                self.done[seq] = (out, t0, t1, threading.get_ident())
                self.cv.notify_all()

    def submit(self, seq: int, rec: tuple) -> None:
        self.q.put((seq, rec))

    def clear(self) -> None:
        with self.cv:
            self.done.clear()
        self._decoder._inflight.clear()

    def stop(self) -> None:
        for _ in self._threads:
            self.q.put(None)


class BatchingDecoder:
    """Slot-based continuous batching over one causal-LM module.

    ``submit`` is thread-safe and returns immediately; ``wait`` blocks for the
    full result; ``stream`` yields per-chunk token deltas as they come off the
    chip. One background thread owns the device loop.
    """

    # the options' defaults are api/config.py Config's; the parameter server
    # reads the process config and hands every one of them over
    # (ps/parameter_server.py _new_decoder)
    def __init__(self, module, variables, *, slots: int = DEFAULT_SLOTS,
                 chunk_steps: int = 8, bucket_min: int = 16,
                 pipeline_depth: int = 6, name: str = "decoder",
                 mesh=None, quantize: str = "", int8_matmul: bool = False,
                 fetchers: int = 6, queue_limit: int = 256,
                 shed_policy: str = "reject",
                 compile_storm_per_min: float = 6.0,
                 cache: Optional[CacheSpec] = None):
        cap = getattr(module, "max_len", None)
        if cap is None:
            raise GenerationInputError(
                "model exposes no max_len attribute; batched decode requires "
                "a declared KV-cache capacity")
        # what the model's caches are, asked once; the paged engine has
        # asked already (it sizes its arenas first) and hands the answer in
        if cache is None:
            cache = cache_spec(module)
            check_cache_features(cache, {"slot_engine"})
        self.cache = cache
        self.module = module
        self.max_len = int(cap)
        self.slots = int(slots)
        self.chunk_steps = int(chunk_steps)
        self.bucket_min = int(bucket_min)
        # serving telemetry: counters/quantiles the PS renders on /metrics
        # (reference gauge discipline, ml/pkg/ps/metrics.go:33-86)
        from .stats import DecoderStats

        self.stats = DecoderStats(slots)
        self.stats.hc_sublayers = cache.residual_sublayers
        self.stats.window_layers = cache.window_layers
        # request-id mint: unique across decoder rebuilds of the same model
        # (the per-boot nonce), monotonic within one decoder — the handle
        # `kubeml trace <request-id>` looks serving span trees up by
        import itertools
        import uuid

        self._req_prefix = f"{name}-{uuid.uuid4().hex[:6]}"
        self._req_seq = itertools.count(1)
        # SHARDED serving (VERDICT r4 next-1): with a mesh, params follow the
        # module's own ``nn.with_partitioning`` annotations (megatron tp) and
        # the KV slab is head-sharded over ``tp`` — the decode step becomes
        # one SPMD program over the serving mesh, so a model too big for one
        # chip serves through the same engine. The sharded-checkpoint store
        # restores straight onto these shardings (no host ever materializes
        # a full leaf), closing the train-big-serve-small gap.
        self.mesh = mesh
        # dispatch pipelining: the device may run up to pipeline_depth
        # programs ahead of the host's processed state, so a value fetch's
        # host round trip never idles it. Deeper delays completion
        # detection, burns dead steps on long requests and stands between
        # a new request's prefill and the device, so the paged engine
        # takes this as the ceiling of run_ahead_depth; the slot engine
        # runs at it.
        self.pipeline_depth = int(pipeline_depth)
        # concurrent result-fetch threads (each fetch pays the host<->device
        # round trip; short-request workloads are fetch-pipeline-bound)
        self.fetchers = int(fetchers)
        self.stats.fetchers_total = self.fetchers
        # compile-storm threshold (compiles/min; 0 disables the warning):
        # sustained compiles in steady state mean shape churn — the PR-15
        # regression this knob exists to surface
        self.stats.compile_storm_per_min = float(compile_storm_per_min)
        # admissions dispatched but not yet processed (engine thread only):
        # nonzero while a chunk dispatch shares the device with prefill
        # work — the chunk's decode steps are tagged cause=prefill_colocated
        self._admits_inflight = 0
        # overload protection: queued rows past queue_limit are refused at
        # admission with 429 + Retry-After (0 = unbounded); shed_policy
        # "oldest" instead sheds the longest-queued request to admit the new
        # one — under sustained overload the queue must bound WAIT, not just
        # depth (an unbounded queue serves nobody within their deadline)
        self.queue_limit = int(queue_limit)
        self.shed_policy = str(shed_policy)
        self.name = name
        # weight-only int8 (serving/quant.py): halves the per-step weight
        # HBM traffic and footprint; the dequantize is traced inside the
        # scan body (_apply_step) so each step reads int8, not a
        # materialized bf16 copy. COMPOSES with the serving mesh: the
        # quantize runs AFTER placement as eager SPMD ops, so q inherits
        # the kernel's tp sharding and the per-channel scales shard with
        # their channel axis.
        if quantize not in ("", "int8"):
            raise ValueError(f"unknown quantize mode {quantize!r} "
                             f"(valid: '', 'int8')")
        from .quant import is_quantized_tree

        pre_quantized = is_quantized_tree(variables)
        if pre_quantized and quantize != "int8":
            raise ValueError(
                "variables carry int8 QuantizedTensor leaves but quantize "
                "is not 'int8' — a dense decode program cannot consume them")
        self.quantize = quantize
        # NATIVE int8 matmuls (quant.quantized_dot): the QuantizedTensor
        # leaves flow INTO module.apply and every dense projection contracts
        # the int8 values directly (models/layers.py QuantizableDense), the
        # per-channel scale folding into the f32 accumulator after — no
        # dense W~ is rebuilt per step. Requires the module's dense layers
        # to be quant-aware: the CausalTransformer family is; MoE expert
        # stacks (3-d einsum params) are not, so they keep the dequantize
        # path.
        self.int8_matmul = (quantize == "int8") and bool(int8_matmul)
        if self.int8_matmul and getattr(module, "moe_every", 0):
            log.warning(
                "%s: KUBEML_INT8_MATMUL does not cover MoE expert params; "
                "falling back to in-program dequantize", name)
            self.int8_matmul = False
        if quantize == "int8" and mesh is None and not pre_quantized:
            from .quant import quantize_tree

            variables = quantize_tree(variables)
        if mesh is not None:
            # params land on the serving mesh under the module's
            # partitioning annotations. A sharded-checkpoint restore already
            # placed every leaf on THIS mesh (the PS derives the same specs
            # before restoring) — skip the re-derivation (a full abstract
            # init trace) and the no-op device_put on that hot path.
            leaves = jax.tree.leaves(variables)
            placed = leaves and all(
                isinstance(l, jax.Array)
                and getattr(l.sharding, "mesh", None) == mesh
                for l in leaves)
            if quantize == "int8":
                from .quant import quantize_tree

                if placed:
                    # already on the mesh. Pre-quantized (a final-int8
                    # checkpoint restored slice-wise): NOTHING dense ever
                    # touched the chip. Dense (a sharded dense restore):
                    # quantize in place — that path already paid the dense
                    # transient when the restore placed it.
                    self._variables = (variables if pre_quantized
                                       else quantize_tree(variables))
                else:
                    # quantize BEFORE placement so per-device HBM peaks at
                    # the int8 tree plus one dense leaf (the quantize's own
                    # working set) — a model sized to int8-per-slice must
                    # not need its full dense shard to fit first
                    qvars = (variables if pre_quantized
                             else quantize_tree(variables))
                    self._variables = jax.device_put(
                        qvars, _quantized_shardings(
                            qvars, _param_shardings(module, mesh), mesh))
            elif placed:
                self._variables = variables
            else:
                self._variables = jax.device_put(
                    variables, _param_shardings(module, mesh))
        else:
            self._variables = jax.device_put(variables)
        # per-step weight HBM bytes (the bandwidth accounting the int8 win
        # is measured against; exported on /metrics)
        from .quant import quantized_bytes

        self.weight_bytes = quantized_bytes(self._variables)
        # KV-read accounting constant (stats.kv_read / the
        # kubeml_serving_kv_read_bytes_total counter): HBM bytes attention
        # reads per cached token per forward pass. The dense slab engine
        # reads its full [S, max_len] stripes every step; the paged engine
        # overrides per dispatch with the table geometry actually shipped.
        self._kv_token_bytes = cache.token_bytes()
        self._pending: deque = deque()
        self._slot_rows: List[Optional[_Row]] = [None] * self.slots
        # rows whose slot was pre-freed but whose results are still in
        # flight (see _free_drained_slots) — tracked so _fail_all reaches
        # their waiters
        self._draining: List[_Row] = []
        self._free = list(range(self.slots))
        self._cond = threading.Condition()
        self._closed = False
        self._retired = False
        # graceful drain (ISSUE 20): while True, submit refuses with 429 +
        # Retry-After (clients back off to another replica / the restart)
        # but live rows keep decoding; the paged engine's drain() snapshots
        # whatever is still running when the grace window closes
        self._drain_mode = False
        self._drain_deadline = 0.0
        self._warmed = False  # flips after the first processed chunk
        self._slab = None
        # steps already in the dispatch chain per slot (gates chunk dispatch)
        self._steps_ahead: List[int] = [0] * self.slots
        self._thread: Optional[threading.Thread] = None
        # --- the dispatch timeline (engine thread only) ---
        # sequence number the next dispatched program gets: one counter for
        # the loop's pipeline arithmetic, the fetch pool's ordering and the
        # engine.* spans
        self._next_seq = 0
        # seq -> (end of its jitted call on the monotonic clock, program
        # kind, ids of the requests it admits, cold) of every program
        # dispatched and not yet consumed; with the completion stamps of
        # the fetch pool, what service_interval needs
        self._inflight: Dict[int, tuple] = {}
        self._prev_done = 0.0   # running maximum of completions consumed
        # --- run-ahead: what run_ahead_depth reads, both from those same
        # clock reads. A warm decode program's service time, and the
        # engine thread's turnaround: from where it could have refilled
        # the device (a result complete and its own last jitted call
        # over) to the end of the jitted call that did
        self._svc_s = _Recent()
        self._host_s = _Recent()
        self._dispatch_end = 0.0   # end of the last jitted call
        # where the turnaround now running began: set by the first result
        # consumed since the last dispatch, 0 when the next dispatch
        # answers no completion (the engine was idle, or a turn had
        # nothing to dispatch)
        self._turn_from = 0.0
        # programs the loop keeps in flight: the paged loop sets it from
        # the estimates once a turn, the slot loop never
        self._depth = self.pipeline_depth
        # tracer clock at which the admission work not yet inside an
        # engine.admit span began (0 = tracing was off then)
        self._admit_from = 0.0
        self._tracer = tracing.get_tracer()
        self._compile_clock = tracing.compile_clock()
        self._startup_logged = False
        # programs are built lazily on the engine thread (first submit);
        # the slab is donated through every link of the dispatch chain
        # (on every backend: the CPU suite runs the chip's buffer lifetimes)
        donate = (1,)
        # two chunk lengths: the big one amortizes per-program overhead, the
        # small one finishes request tails without re-running a full chunk
        # over rows that only need a few more steps (a 64-token request is
        # 63 post-admit steps: 48+16 instead of 48+48)
        import functools

        tail = min(self.chunk_steps,
                   max(8, (self.chunk_steps // 3 + 7) // 8 * 8))
        self._chunk_sizes = sorted({self.chunk_steps, tail})
        if mesh is not None:
            # explicit out_shardings keep the slab sharded through every
            # link of the dispatch chain (and make donation legal: input and
            # output layouts match exactly)
            self._slab_sharding = self._slab_shardings()
            rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            outs = (self._slab_sharding, rep)
        else:
            self._slab_sharding = None
            outs = None
        self._steps = {
            T: jax.jit(functools.partial(self._step_impl, steps=T),
                       donate_argnums=donate, out_shardings=outs)
            for T in self._chunk_sizes
        }
        self._prefill_admit = jax.jit(self._prefill_admit_impl,
                                      donate_argnums=donate,
                                      out_shardings=outs)

    # --- device programs ---

    def _apply_step(self, variables, cache, tok, pos, pages=None, live=None):
        variables = self._dense_vars(variables)
        kw = {} if pages is None else {"pages": pages}
        if self.cache.recurrent or self.cache.expert_layers:
            # a row that is not live (retired, or mid-chunked-prefill) must
            # keep its recurrent state: a step is one position, and a
            # sequence length of 0 leaves state and convolution tail alone
            # (its K/V write goes to the trash page as before); and an
            # expert layer gives a row that is not live to no expert
            kw["seq_lens"] = live.astype(jnp.int32)
        logits, vs = self.module.apply(
            {**variables, "cache": cache}, tok[:, None], decode=True,
            positions=pos, mutable=["cache"], **kw)
        return logits[:, -1].astype(jnp.float32), vs["cache"]

    def _dense_vars(self, variables):
        """The held tree as ``module.apply`` takes it, made INSIDE the
        traced program. A table held with its rows padded to whole lane
        rows (quant.PaddedRows) is sliced to its own width: a bitcast on
        the chip, and the lookup gathers from the held array. int8 weights
        densify (per scan step — the HBM read stays int8 and the
        convert+scale fuses toward the matmul); identity when not
        quantized — and identity in NATIVE int8-matmul mode, where the
        QuantizedTensor leaves flow into ``module.apply`` and the
        quant-aware dense layers contract them without any dense rebuild
        (quant.quantized_dot)."""
        from .quant import dequantize_tree, unpadded

        variables = unpadded(variables)
        if self.quantize != "int8" or self.int8_matmul:
            return variables
        return dequantize_tree(variables, dtype=jnp.float32)

    def _step_impl(self, variables, slab, pages=None, steps=None):
        """Advance every slot ``steps`` tokens (one program per size in
        ``_chunk_sizes``). ``pages`` (paged engine) is the per-row block
        table threading the shared arena; None is the dense cache path.

        Emits ONE packed [T, S] int32 block: the sampled token where the row
        was live that step, -1 otherwise. Packing matters: every fetched
        array pays a host round trip, so the chunk's results
        must come back in a single fetch (token ids are non-negative, so -1
        is unambiguous — PAD_ID 0 is a legal vocab id). A model with expert
        layers adds three columns, [T, S + 3], summed over the layers
        (each layer leaves its counts in the cache, models/experts.py): the
        experts held here that its live rows chose that step, their
        assignments that entered the grouped product, and those to identity
        experts; so the counts come back in the tokens' own fetch."""

        def one(s, _):
            logits, cache = self._apply_step(variables, s.cache, s.tok, s.pos,
                                             pages=pages, live=s.live)
            counts = [sum(leaves) for leaves in (
                _leaves_named(cache, name) for name in _MOE_COUNTS) if leaves]
            use, nxt_keys = _split_rows(s.keys)
            nxt = _sample_rows(logits, use, s.temp, s.topk, active=s.live)
            was_live = s.live
            hit_eos = (s.eos >= 0) & (nxt == s.eos)
            rem = s.remaining - was_live.astype(jnp.int32)
            live = was_live & ~hit_eos & (rem > 0)
            out = jnp.where(was_live, nxt, -1)
            # dead rows freeze: keep feeding their last token at a frozen
            # (in-bounds) position — their writes only touch their own slot,
            # which the next admit overwrites wholesale
            feed = jnp.where(live, nxt, s.tok)
            pos = jnp.where(live, s.pos + 1, s.pos)
            s2 = _Slab(cache, feed, pos, live, rem, nxt_keys, s.temp, s.topk,
                       s.eos)
            if counts:
                out = jnp.concatenate(
                    [out, jnp.stack(counts).astype(out.dtype)])
            return s2, out

        slab, packed = jax.lax.scan(
            one, slab, None, length=steps if steps else self.chunk_steps)
        return slab, packed

    def _prefill_admit_impl(self, variables, slab, prompts, plens, slots,
                            max_news, temps, topks, eoss, keys):
        """ONE program per (row-count, prompt-length) bucket: prefill k
        prompts together (one batched forward — better MXU than k singles),
        insert each row into its slab slot, and sample each first token with
        its own knobs. Batched because an admission WAVE (many slots freeing
        at once) would otherwise pay a host round trip per row;
        returns one packed [k, 2] (first, live0) array = one fetch total.

        Row-count padding is idempotent: callers pad a short group by
        repeating its last row (same slot, same key, same knobs), so the
        duplicate writes are byte-identical and scatter order can't matter."""
        k, Lb = prompts.shape
        variables = self._dense_vars(variables)
        cache_k = init_cache(self.module, variables, k)
        # bucket padding means positions >= plen hold garbage K/V; their
        # validity is trimmed at insert below. Next-token logits come from
        # each row's last REAL prompt token: the module gathers that one
        # position (runtime, at plen-1) before its head
        logits, vs = self.module.apply(
            {**variables, "cache": cache_k}, prompts, decode=True,
            head_positions=plens - 1, mutable=["cache"])
        row_caches = vs["cache"]
        last = logits[:, 0].astype(jnp.float32)

        use, nxt_keys = _split_rows(keys)
        firsts = _sample_rows(last, use, temps, topks)  # [k]
        hit_eos = (eoss >= 0) & (firsts == eoss)
        live0 = (max_news > 1) & ~hit_eos

        Lc = self.max_len
        trim = jnp.arange(Lc)[None, :] < plens[:, None]  # [k, Lc]

        def insert(slab_leaf, rows_leaf):
            if getattr(slab_leaf, "ndim", 0) == 0:
                return slab_leaf  # scalar cursor leaves: unused in slab mode
            if rows_leaf.dtype == jnp.bool_ and rows_leaf.ndim == 2:
                rows_leaf = rows_leaf & trim  # per-layer "valid"

            def body(i, acc):
                row = jax.lax.dynamic_slice_in_dim(rows_leaf, i, 1, 0)
                start = (slots[i],) + (0,) * (row.ndim - 1)
                return jax.lax.dynamic_update_slice(acc, row, start)

            return jax.lax.fori_loop(0, k, body, slab_leaf)

        cache = jax.tree.map(insert, slab.cache, row_caches)

        def put(vec, vals):
            return vec.at[slots].set(vals.astype(vec.dtype))

        slab2 = _Slab(
            cache,
            put(slab.tok, firsts),
            put(slab.pos, plens),
            put(slab.live, live0),
            put(slab.remaining, max_news - 1),
            slab.keys.at[slots].set(nxt_keys),
            put(slab.temp, temps),
            put(slab.topk, topks),
            put(slab.eos, eoss),
        )
        packed = jnp.stack([firsts, live0.astype(jnp.int32)], axis=1)  # [k, 2]
        return slab2, packed

    def _init_slab_impl(self) -> _Slab:
        # shape-only: densify abstractly so quantized trees never
        # materialize a dense copy just to size the cache
        dense_abstract = jax.eval_shape(self._dense_vars, self._variables)
        return self._slab_from_cache(
            init_cache(self.module, dense_abstract, self.slots))

    def _slab_from_cache(self, cache) -> _Slab:
        S = self.slots
        return _Slab(
            cache,
            jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), bool),
            jnp.zeros((S,), jnp.int32),
            jnp.tile(jax.random.PRNGKey(0)[None], (S, 1)),
            jnp.zeros((S,), jnp.float32),  # temp 0: empty slab is all-greedy
            jnp.zeros((S,), jnp.int32),
            jnp.full((S,), -1, jnp.int32),
        )

    def _init_slab(self) -> _Slab:
        if self.mesh is None:
            return self._init_slab_impl()
        # sharded serving: the slab is BORN sharded (jit + out_shardings), so
        # no host or single device ever holds the whole KV cache
        return jax.jit(self._init_slab_impl,
                       out_shardings=self._slab_sharding)()

    def _build_slab(self) -> _Slab:
        """``_init_slab`` on the engine thread, timed: an abstract trace of
        the model for the cache's shapes, then the programs that zero it
        (one jit on a mesh, small eager ones without). Its wall is the
        stats' ``slab`` start-up phase (again after a fault's rebuild) and
        an ``engine.init_slab`` span with the compile clock's bracket; the
        phases stay out of the engine programs' sums."""
        before, t0 = self._compile_clock.read(), time.monotonic()
        slab = jax.block_until_ready(self._init_slab())
        seconds = time.monotonic() - t0
        self.stats.startup("slab", seconds)
        if self._tracer.enabled:
            self._tracer.add_span(
                "engine.init_slab", self._tracer.at(t0), seconds,
                slots=self.slots, bytes=sum(
                    int(l.nbytes) for l in jax.tree.leaves(slab.cache)),
                **self._compile_clock.since(before))
        return slab

    def _slab_shardings(self):
        """NamedSharding pytree for the slab: 4-d ``k``/``v`` cache leaves
        ``[S, max_len, H, D]`` are HEAD-sharded over ``tp`` (axis 2 — heads
        are what the module's column-sharded qkv projections split, so the
        per-shard cache lines up with the per-shard attention compute and no
        collective touches the cache itself); every other leaf (cursors,
        knobs, per-layer valid masks) is replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        has_tp = "tp" in self.mesh.shape
        tp = int(self.mesh.shape["tp"]) if has_tp else 1
        abstract = jax.eval_shape(self._init_slab_impl)

        def leaf_spec(path, s):
            name = getattr(path[-1], "key", None) if path else None
            if (has_tp and name in ("k", "v") and getattr(s, "ndim", 0) == 4
                    and s.shape[2] % tp == 0):
                return NamedSharding(self.mesh, P(None, None, "tp", None))
            return NamedSharding(self.mesh, P())

        return jax.tree_util.tree_map_with_path(leaf_spec, abstract)

    # --- public API ---

    def submit(self, req) -> _Entry:
        """Validate and enqueue a GenerateRequest; returns its entry."""
        try:
            return self._submit(req)
        except KubeMLError as e:
            if e.status_code == 400:
                self.stats.rejected()
            raise

    def _submit(self, req) -> _Entry:
        prompts = np.asarray(req.prompts)
        if prompts.ndim != 2 or not np.issubdtype(prompts.dtype, np.integer):
            raise KubeMLError(
                "prompts must be a [batch, prompt_len] integer token array", 400)
        B, width = prompts.shape
        lens = ([int(v) for v in req.prompt_lengths]
                if req.prompt_lengths is not None else [width] * B)
        if req.top_k is not None and req.top_k > TOP_K_MAX:
            raise KubeMLError(
                f"top_k exceeds the serving bound ({TOP_K_MAX})", 400)
        for plen in lens:
            if plen + req.max_new_tokens - 1 > self.max_len:
                raise KubeMLError(
                    f"prompt ({plen}) + max_new_tokens ({req.max_new_tokens})"
                    f" - 1 exceeds the model's max_len ({self.max_len})", 400)
            self._check_capacity(plen, req.max_new_tokens)
        base_key = (jax.random.PRNGKey(req.seed) if req.seed is not None
                    else None)
        from ..utils import resilience, tracing

        rows = []
        entry = _Entry(rows=rows, max_new=req.max_new_tokens,
                       stream_q=queue.Queue() if req.stream else None,
                       submitted_at=time.monotonic(),
                       deadline=resilience.current_deadline(),
                       request_id=self._next_request_id(),
                       trace_ctx=tracing.current_context())
        for i in range(B):
            key = (np.asarray(jax.random.fold_in(base_key, i))
                   if base_key is not None
                   else np.zeros((2,), np.uint32))
            rows.append(_Row(
                entry=entry, index=i, prompt=prompts[i, :lens[i]].astype(np.int32),
                max_new=req.max_new_tokens,
                temp=float(req.temperature),
                topk=int(req.top_k or 0),
                eos=int(req.eos_id) if req.eos_id is not None else -1,
                key=key,
            ))
        with self._cond:
            if self._closed or self._retired:
                raise DecoderClosed()
            if self._drain_mode:
                from ..api.errors import OverloadedError

                self.stats.overloaded()
                hint = max(1.0, self._drain_deadline - time.monotonic())
                raise OverloadedError(
                    "decoder is draining for shutdown; resubmit to another "
                    "replica or after restart", retry_after=min(hint, 30.0))
            # admission limit gates on QUEUE pressure: a batch wider than the
            # limit still admits into an otherwise-empty queue (it was
            # serviceable before the limit existed and a retry could never
            # succeed), so the bound is limit + one batch, not limit alone
            if (self.queue_limit > 0 and self._pending
                    and len(self._pending) + len(rows) > self.queue_limit):
                if self.shed_policy == "oldest":
                    self._shed_oldest_locked(
                        len(self._pending) + len(rows) - self.queue_limit)
                if (self._pending and len(self._pending) + len(rows)
                        > self.queue_limit):
                    from ..api.errors import OverloadedError

                    self.stats.overloaded()
                    raise OverloadedError(
                        f"decode queue at its admission limit "
                        f"({len(self._pending)}/{self.queue_limit} rows "
                        f"queued; KUBEML_SERVING_QUEUE_LIMIT)",
                        retry_after=self._retry_after_hint())
            self._pending.extend(rows)
            self.stats.submitted(1)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name=f"decode-{self.name}", daemon=True)
                self._thread.start()
            self._cond.notify_all()
        return entry

    def _next_request_id(self) -> str:
        return f"{self._req_prefix}-r{next(self._req_seq)}"

    def _check_capacity(self, plen: int, max_new: int) -> None:
        """Engine-specific admission-capacity validation hook (400s a row no
        amount of queueing could ever admit — the paged engine bounds rows
        by its page arena, the dense engine only by max_len above)."""

    # first-traffic XLA compiles (slab init + prefill/admit + step chunk) can
    # take minutes on chip; client-derived timeouts must not punish them
    COLD_COMPILE_ALLOWANCE = 900.0

    def wait(self, entry: _Entry, timeout: Optional[float] = None) -> dict:
        if timeout is not None and not self._warmed:
            timeout += self.COLD_COMPILE_ALLOWANCE
        if not entry.done_evt.wait(timeout):
            # nobody will read the result: cancel so the rows stop holding
            # decode slots (they would otherwise run to max_new_tokens and
            # starve live traffic behind discarded work)
            if self._record_outcome(entry):
                self.stats.timed_out()
                self._finish_timeline(entry, "timeout")
            self.cancel(entry)
            raise KubeMLError("generation timed out", 504)
        if entry.error is not None:
            raise entry.error
        return entry.result()

    def cancel(self, entry: _Entry) -> None:
        """Abandon a request: queued rows leave the pending queue now;
        admitted rows are evicted from their slots at the next chunk
        boundary."""
        if self._record_outcome(entry):
            self.stats.canceled()
            self._finish_timeline(entry, "canceled")
        with self._cond:
            for row in entry.rows:
                row.canceled = True
            self._pending = deque(r for r in self._pending if not r.canceled)
            self._cond.notify_all()

    def stream(self, entry: _Entry):
        """Yield ``{"row": i, "tokens": [...]}`` deltas, then a final
        ``{"done": true, "lengths": [...]}``; raises the entry's error."""
        while True:
            item = entry.stream_q.get()
            if item is None:
                if entry.error is not None:
                    raise entry.error
                yield {"done": True,
                       "lengths": [len(r.out) for r in entry.rows],
                       "request_id": entry.request_id}
                return
            yield item

    def _record_outcome(self, entry: _Entry) -> bool:
        """Atomically claim an entry's single telemetry outcome: each
        request counts exactly one of completed/timeout/canceled/failed.
        The waiter's timeout and the engine's completion can race on the
        same entry — the flag flips under the engine lock so only one side
        wins (the counters must never sum past requests_submitted)."""
        with self._cond:
            if entry.aborted:
                return False
            entry.aborted = True
            return True

    def _finish_timeline(self, entry: _Entry, outcome: str) -> None:
        """Emit the request's lifecycle span tree (tracing on only): one
        ``serving.request`` span tagged ``job=<request_id>`` — so
        ``kubeml trace <request-id>`` works for serving exactly like it
        does for train tasks — with queue-wait/prefill/decode child spans
        reconstructed from the row timeline. Called exactly once per entry,
        by whichever site claimed the telemetry outcome."""
        tracer = self._tracer
        if not tracer.enabled:
            return
        try:
            now = time.monotonic()
            sub = entry.submitted_at
            # entry-level timeline from the row aggregates (monotonic)
            slot_at = min((r.slot_at for r in entry.rows if r.slot_at),
                          default=0.0)
            first = min((r.first_emit_at for r in entry.rows
                         if r.first_emit_at), default=0.0)
            last = max((r.last_emit_at for r in entry.rows), default=0.0)
            ctx = entry.trace_ctx
            req = tracer.add_span(
                "serving.request", tracer.at(sub), (last or now) - sub,
                trace_id=ctx.trace_id if ctx is not None else None,
                parent_id=ctx.span_id if ctx is not None else None,
                job=entry.request_id, model=self.name,
                rows=len(entry.rows),
                tokens=sum(len(r.out) for r in entry.rows),
                outcome=outcome,
                # latency anatomy (ISSUE 18): stream smoothness + the
                # decode time this request lost behind colocated prefill
                itl_p99=_itl_quantile(entry.rows, 0.99),
                hol_stall_seconds=sum(r.hol_stall for r in entry.rows))
            if req is None:
                return
            kw = dict(trace_id=req.trace_id, parent_id=req.span_id,
                      job=entry.request_id)
            if slot_at:
                tracer.add_span("serving.queue_wait", tracer.at(sub),
                                slot_at - sub, **kw)
                if first:
                    tracer.add_span("serving.prefill", tracer.at(slot_at),
                                    first - slot_at, **kw)
            if first and last > first:
                tracer.add_span("serving.decode", tracer.at(first),
                                last - first, **kw)
        except Exception:  # span emission must never fail the serving path
            log.debug("serving timeline emission failed", exc_info=True)

    def _fail_entry(self, entry: _Entry, error: Exception, counter,
                    outcome: str = "failed") -> None:
        """Fail one entry's waiters (queued-work shed/expiry path): rows are
        marked done, the error set, the single telemetry outcome claimed via
        ``counter``, and both the waiter and any stream consumer released."""
        for row in entry.rows:
            row.done = True
        if entry.error is None:
            entry.error = error
        if self._record_outcome(entry):
            counter()
            self._finish_timeline(entry, outcome)
        entry.done_evt.set()
        if entry.stream_q is not None:
            entry.stream_q.put(None)

    def _shed_oldest_locked(self, need: int) -> int:
        """Shed the longest-queued entries (oldest-first) to free ``need``
        queued rows; caller holds ``_cond``. Only entries ALL of whose rows
        are still queued are sheddable — an entry with rows already in slots
        keeps its queued siblings (failing it would strand device work).
        Returns the number of rows freed."""
        from ..api.errors import OverloadedError

        by_entry: Dict[int, List[_Row]] = {}
        order: List[_Entry] = []
        for r in self._pending:
            if id(r.entry) not in by_entry:
                order.append(r.entry)
            by_entry.setdefault(id(r.entry), []).append(r)
        doomed: List[_Entry] = []
        freed = 0
        for entry in order:
            if freed >= need:
                break
            queued = by_entry[id(entry)]
            if len(queued) != len(entry.rows):
                continue
            doomed.append(entry)
            freed += len(queued)
        if not doomed:
            return 0
        doomed_ids = {id(e) for e in doomed}
        self._pending = deque(r for r in self._pending
                              if id(r.entry) not in doomed_ids)
        hint = self._retry_after_hint()
        for entry in doomed:
            self._fail_entry(
                entry,
                OverloadedError("request shed from the decode queue under "
                                "sustained overload (oldest-first)",
                                retry_after=hint),
                self.stats.shed, outcome="shed")
        return freed

    def _retry_after_hint(self) -> float:
        """Retry-After seconds for a 429: roughly how long the current queue
        takes to drain (depth/slots turns at the recent p50 request
        latency), clamped to [1, 30]."""
        with self._cond:
            depth = len(self._pending)
        p50 = self.stats.snapshot().get("latency_p50_seconds", 1.0)
        turns = depth / max(self.slots, 1)
        return float(min(max(1.0, turns * max(p50, 0.1)), 30.0))

    def _sweep_expired(self) -> None:
        """Fail queued rows whose request deadline already passed: an
        expired request must fail fast (504), not occupy a decode slot
        computing tokens nobody will read. Only entries still fully queued
        are swept (admitted rows run to completion; the waiter's own timeout
        covers them). Cold-start compiles get the same allowance wait()
        grants."""
        now = time.time()
        doomed: List[_Entry] = []
        with self._cond:
            if not self._pending:
                return
            allowance = 0.0 if self._warmed else self.COLD_COMPILE_ALLOWANCE
            by_entry: Dict[int, List[_Row]] = {}
            for r in self._pending:
                by_entry.setdefault(id(r.entry), []).append(r)
            seen = set()
            for r in list(self._pending):
                e = r.entry
                if id(e) in seen:
                    continue
                seen.add(id(e))
                if (e.deadline is not None
                        and now > e.deadline + allowance
                        and len(by_entry[id(e)]) == len(e.rows)):
                    doomed.append(e)
            if doomed:
                doomed_ids = {id(e) for e in doomed}
                self._pending = deque(r for r in self._pending
                                      if id(r.entry) not in doomed_ids)
            for entry in doomed:
                self._fail_entry(
                    entry,
                    KubeMLError("request deadline expired while queued for "
                                "a decode slot", 504),
                    self.stats.deadline_expired, outcome="expired")

    # what the ``ps.serving.decoder`` span says of the K/V memory: physical
    # pages (the slot engine has none) and its bytes by the module's geometry
    arena_pages = 0

    @property
    def arena_bytes(self) -> int:
        return self.slots * self.max_len * self._kv_token_bytes

    def telemetry(self) -> dict:
        """One snapshot of the decoder's serving metrics: the stats counters
        plus the live queue-depth and slot-occupancy gauges (engine state —
        read here so the exposition never touches engine internals)."""
        snap = self.stats.snapshot()
        with self._cond:
            snap["queue_depth"] = float(len(self._pending))
            busy = sum(1 for r in self._slot_rows if r is not None)
        snap["slots_busy"] = float(busy)
        snap["slots_total"] = float(self.slots)
        snap["slot_occupancy"] = busy / max(self.slots, 1)
        snap["weight_bytes"] = float(self.weight_bytes)
        snap["queue_limit"] = float(self.queue_limit)
        # 1 while draining for shutdown (admissions 429; kubeml top DRAIN)
        snap["draining"] = 1.0 if self._drain_mode else 0.0
        return snap

    @property
    def closed(self) -> bool:
        """True once the engine is permanently down (explicit ``close`` or an
        unrecoverable device failure). The PS decoder cache checks this to
        rebuild instead of returning a decoder that 503s everything."""
        return self._closed

    def close(self) -> None:
        """Hard shutdown: fails everything queued or in flight."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._fail_all(DecoderClosed())

    def retire(self) -> None:
        """Graceful shutdown for cache displacement: new submissions are
        rejected, in-flight requests finish normally, then the engine thread
        exits and the slab is freed."""
        with self._cond:
            self._retired = True
            self._cond.notify_all()

    # --- engine loop (one thread owns the device state) ---

    def _busy(self) -> bool:
        return any(r is not None for r in self._slot_rows)

    def _loop(self) -> None:
        """The engine: an event-driven PIPELINED dispatch chain.

        Admissions and chunks are enqueued on the device back-to-back (the
        slab threads through them as a data dependency, so order is total).
        Their results are materialized by a small FETCHER POOL — a value
        fetch blocks its thread until the program has run, so fetches must
        overlap both each other and the device's compute; the engine thread consumes materialized results in dispatch
        order and never blocks on the wire itself. Chunk dispatch is GATED on
        host-known work (each row needs at most max_new-1 steps), so the
        device doesn't burn chunks on rows whose completion the host simply
        hasn't fetched yet. Completions are still detected a bit late; dead
        rows step harmlessly (device-side live flags gate emission), so
        lateness costs idle slot-steps, not correctness."""
        try:
            self._slab = self._build_slab()
        except Exception as e:  # init/compile failure fails all waiters
            log.exception("%s: slab init failed", self.name)
            with self._cond:
                # close BEFORE failing the waiters: with the engine thread
                # gone, later submits would otherwise enqueue into a loop
                # nobody runs and block the full timeout each. Closed, they
                # get a fast DecoderClosed 503 and the PS decoder cache
                # rebuilds a fresh decoder (it skips closed entries).
                self._closed = True
            self._fail_all(e)
            return

        pool = _FetchPool(self, self.fetchers)
        process_seq = 0    # next result to consume (in dispatch order)
        self._steps_ahead = [0] * self.slots

        while True:
            # deadline hygiene before admission: expired queued work fails
            # fast instead of winning a slot
            self._sweep_expired()
            with self._cond:
                while (not self._closed and not self._pending
                       and not self._busy()
                       and process_seq == self._next_seq):
                    if self._retired:
                        self._slab = None  # free the KV slab's HBM
                        pool.stop()
                        return
                    self._wait_work()
                if self._closed:
                    pool.stop()
                    return
                admits = []
                self._admit_from = self._span_clock()
                if self._next_seq - process_seq < self.pipeline_depth:
                    while self._free and self._pending:
                        admits.append((self._free.pop(0),
                                       self._pending.popleft()))
            try:
                dispatched = False
                live_admits = []
                for slot, row in admits:
                    if row.canceled:
                        with self._cond:
                            self._free.append(slot)
                        continue
                    live_admits.append((slot, row))
                groups = self._group_admits(live_admits)
                for gi, group in enumerate(groups):
                    if self._next_seq - process_seq >= self.pipeline_depth:
                        # backpressure mid-wave (multi-bucket admissions):
                        # requeue the untouched remainder
                        rest = [p for g in groups[gi:] for p in g]
                        with self._cond:
                            for slot, row in reversed(rest):
                                self._free.insert(0, slot)
                                self._pending.appendleft(row)
                        break
                    self._submit_program(pool, self._dispatch_admits(group))
                    dispatched = True
                self._evict_canceled()
                self._free_drained_slots()
                if (self._next_seq - process_seq < self.pipeline_depth
                        and (needed := self._chunk_wanted()) > 0):
                    self._submit_program(pool, self._dispatch_chunk(needed))
                    dispatched = True
                # consume materialized results in order; block only when the
                # pipe is full or nothing else can make progress
                must_wait = (
                    self._next_seq - process_seq >= self.pipeline_depth
                    or (not dispatched and process_seq < self._next_seq))
                process_seq = self._consume_ready(pool, process_seq,
                                                  must_wait)
            except Exception as e:
                log.exception("%s: decode loop failed", self.name)
                # drain whatever the fetchers still owe so seqs stay aligned
                pool.clear()
                process_seq = self._next_seq
                self._fail_all(e, wrap=True)
                with self._cond:
                    if self._closed:
                        pool.stop()
                        return
                    # reset device state so later traffic gets a clean slab
                    self._slot_rows = [None] * self.slots
                    self._free = list(range(self.slots))
                    self._steps_ahead = [0] * self.slots
                    self._admits_inflight = 0
                try:
                    self._reset_engine_state()
                    self._slab = self._build_slab()
                except Exception:
                    with self._cond:
                        self._closed = True
                    pool.stop()
                    return

    def _reset_engine_state(self) -> None:
        """Fault-recovery hook: extra engine state to rebuild before a fresh
        slab is initialized (the paged engine rebuilds its page pool here —
        a zeroed arena invalidates every cached page)."""

    # --- the dispatch timeline: engine.* spans (utils.tracing) ---
    #
    # Every site reads ``self._tracer.enabled`` and does nothing more when
    # it is off. What is always on is what feeds the stats: the clock read
    # at the end of each jitted call and the fetcher's two, through
    # service_interval.

    def _span_clock(self) -> float:
        """The tracer's clock when it is on, else 0."""
        return self._tracer.now() if self._tracer.enabled else 0.0

    def _wait_work(self) -> None:
        """``self._cond.wait()`` of a loop with nothing pending, no live
        row and nothing in flight, as an ``engine.wait_work`` span: the
        device's idle time inside one is nobody's fault."""
        t0 = self._span_clock()
        self._turn_from = 0.0
        self._cond.wait()
        # a wait that outlives the tracing it began under (close() wakes
        # the thread after the tracer went off) records nothing
        if t0 and self._tracer.enabled:
            self._tracer.add_span("engine.wait_work", t0,
                                  self._tracer.now() - t0)

    def _engine_span(self, name: str, start: float, duration: float,
                     requests: Optional[str], **attrs: Any) -> None:
        """One engine.* span; ``requests`` (ids, comma-separated) rides on
        the spans of an admitting program so that ``kubeml trace <id>``
        finds them."""
        if requests:
            attrs["requests"] = requests
        self._tracer.add_span(name, start, duration, **attrs)

    def _submit_program(self, pool: _FetchPool, rec: tuple) -> None:
        """Hand the record of the program just dispatched to the fetchers
        under the sequence number ``_run_program`` gave it."""
        pool.submit(self._next_seq, rec)
        self._next_seq += 1

    def _consume_ready(self, pool: _FetchPool, process_seq: int,
                       must_wait: bool) -> int:
        """Consume materialized results in dispatch order; blocks only while
        ``must_wait`` (pipe full, or nothing else can make progress) and
        returns the advanced ``process_seq``. A fetch error re-raises on the
        engine thread. Each result's completion stamp becomes its program's
        service time here, where the one before it is known."""
        tracer = self._tracer
        waiting = 0.0   # tracer clock since which the engine has blocked
        self._turn_from = 0.0   # the turn just over dispatched nothing
        while process_seq < self._next_seq:
            with pool.cv:
                if process_seq not in pool.done:
                    if not must_wait:
                        break
                    waiting = waiting or self._span_clock()
                    pool.cv.wait(timeout=1.0)
                    continue
                rec, t0, t1, thread = pool.done.pop(process_seq)
            dispatched, kind, requests, cold = self._inflight.pop(
                process_seq, (t0, rec[0], None, True))
            svc_s, wait_s, self._prev_done = service_interval(
                dispatched, t1, self._prev_done)
            if kind == "step" and not cold:
                self._svc_s.add(svc_s)
            self._turn_from = self._turn_from or max(t1, self._dispatch_end)
            if tracer.enabled:
                if waiting:
                    tracer.add_span("engine.wait_result", waiting,
                                    tracer.now() - waiting, seq=process_seq)
                    waiting = 0.0
                self._engine_span("engine.fetch", tracer.at(t0), t1 - t0,
                                  requests, thread=thread, seq=process_seq,
                                  program=kind, svc_s=svc_s, wait_s=wait_s)
            if rec[0] == "error":
                raise rec[1]
            began = self._span_clock()
            tokens = self._process_record(rec, svc_s)
            if began:
                self._engine_span("engine.process", began,
                                  tracer.now() - began,
                                  requests, seq=process_seq, program=kind,
                                  tokens=tokens)
            process_seq += 1
            must_wait = False  # one result is progress enough
        return process_seq

    def _remaining_steps(self) -> List[int]:
        """Per-active-row steps still needed beyond the dispatch chain (one
        value per live slot row) — the ONE step-accounting expression both
        chunk sizing and pressure sizing read."""
        return [
            row.max_new - 1 - self._steps_ahead[slot]
            for slot, row in enumerate(self._slot_rows)
            if row is not None and not row.done and not row.canceled
        ]

    def _chunk_wanted(self) -> int:
        """Steps some occupied slot still needs beyond what's already in the
        dispatch chain (0 = no chunk wanted): each row needs at most
        max_new-1 post-admit steps, so chunks past that bound would compute
        nothing the host can use. The caller sizes the next chunk program to
        this — the MAX across rows, so the longest row is never starved."""
        if not self._busy():
            return 0
        return max(self._remaining_steps(), default=0)

    def _run_program(self, program: str, sig: tuple, fn, *args, kind: str,
                     steps: int = 0, width: int = 0, group=None,
                     state_rows: int = 0, head_positions: int = 0):
        """Dispatch one jitted program through the compile tracker: the
        first call per (program, shape signature) traces + XLA-compiles
        synchronously before the async dispatch, so its wall here IS the
        compile wall — measured into kubeml_serving_compile_seconds and
        flagged cold so the dispatch record's service time lands in the
        cold-start series, never the steady-state decode_step/first_token
        histograms. Returns ``(fn(*args), cold)``.

        The end of the call is where the program's wait for the device
        begins (service_interval), so it is stamped every time. With the
        tracer on the call is an ``engine.dispatch`` span — ``kind`` is the
        record's (admit, step, spec, pchunk), ``steps`` the decode steps in
        the program, ``width`` its page-table width, ``state_rows`` the rows
        whose recurrent state it writes (0 for a model without one)
        (``moe_layers``: its routed-expert layers, 0 likewise),
        ``head_positions`` the positions a prefill program's output head
        takes (the one a row it samples from; 0 on any other program)
        — under a profiler
        annotation of the same name, and an admitting program (``group``
        set) closes the ``engine.admit`` span that began where its rows
        were taken from the queue. A first call's span also carries
        ``sig`` (the program and its shape signature) and the compile
        clock's bracket of the call: ``trace_s``, ``lower_s``,
        ``backend_s``, ``cache_hits``, ``cache_misses``; the same five add
        into the stats whether the tracer is on or not."""
        cold = self.stats.compile_begin(program, sig)
        tracer, seq, requests = self._tracer, self._next_seq, None
        traced, annotation = tracer.enabled, _NO_ANNOTATION
        # a first call's tracing, lowering and compile (or cache read) all
        # happen inside it, on this thread: the compile clock's bracket
        before = self._compile_clock.read() if cold else None
        t0 = time.monotonic()
        if traced:
            if group is not None:
                requests = ",".join(dict.fromkeys(
                    row.entry.request_id for _, row in group))
                began = self._admit_from or tracer.at(t0)
                tracer.add_span("engine.admit", began, tracer.at(t0) - began,
                                rows=len(group), requests=requests,
                                state_rows=state_rows,
                                moe_layers=self.cache.expert_layers)
            annotation = jax.profiler.TraceAnnotation("engine.dispatch",
                                                      seq=seq)
        # ONE call site, traced or not: a program's source locations are
        # part of what its compile-cache entry is found by, and a second
        # site compiled every program again when tracing came on (+49 s of
        # set-up on the chip, PR 24)
        with annotation:
            out = fn(*args)
        t1 = time.monotonic()
        phases = self._compile_clock.since(before) if cold else None
        if traced:
            first = ({"sig": f"{program}{sig}",
                      **{k: phases[k] for k in COMPILE_PHASES}}
                     if cold else {})
            self._engine_span(
                "engine.dispatch", tracer.at(t0), t1 - t0, requests, seq=seq,
                program=kind, steps=steps, width=width, cold=cold,
                state_rows=state_rows, moe_layers=self.cache.expert_layers,
                head_positions=head_positions,
                rows_live=sum(r is not None for r in self._slot_rows),
                depth=self._depth, ahead=len(self._inflight), **first)
            # a later group of the same wave is prepared from here on
            self._admit_from = tracer.at(t1)
        if cold:
            self.stats.compiled(program, t1 - t0, phases)
        elif self._turn_from:
            self._host_s.add(t1 - self._turn_from)
        self._turn_from = 0.0
        self._dispatch_end = t1
        self._inflight[seq] = (t1, kind, requests, cold)
        return out, cold

    def _stalled_rows(self) -> List[_Row]:
        """Live decoding rows with host-known work NOT yet in the dispatch
        chain — the rows a colocated prefill dispatch actually delays. A
        row whose every remaining emission is already dispatched (the
        pre-freed/drained case, including rows that retire mid-chunk)
        rides the ordered chain regardless and is NOT stalled."""
        return [row for slot, row in enumerate(self._slot_rows)
                if row is not None and not row.done and not row.canceled
                and row.max_new - 1 - self._steps_ahead[slot] > 0]

    def _materialize(self, rec: tuple) -> tuple:
        """Runs on a fetcher thread: the value fetch (the host needs the
        tokens, and fetching them waits for the program), returning a
        host-data record. Its return is the program's execution barrier:
        the pool stamps it, and _consume_ready turns the stamp into the
        program's service time."""
        if rec[0] == "admit":
            return ("admit", rec[1], np.asarray(rec[2])) + rec[3:]
        return ("chunk", np.asarray(rec[1])) + rec[2:]

    def _group_admits(self, admits: List[tuple]) -> List[List[tuple]]:
        """Split an admission wave into same-prompt-bucket groups (each group
        becomes ONE batched prefill+admit dispatch)."""
        by_bucket: Dict[int, List[tuple]] = {}
        for slot, row in admits:
            b = _pow2_bucket(max(len(row.prompt), 1), self.bucket_min,
                             self.max_len)
            by_bucket.setdefault(b, []).append((slot, row))
        return list(by_bucket.values())

    def _dispatch_admits(self, group: List[tuple]) -> tuple:
        """Enqueue one batched prefill+admit for same-bucket rows; short
        groups pad by repeating their last row (idempotent — same slot, same
        bytes). The row count is ALWAYS padded to ``slots``: one program
        shape per prompt bucket, so no admission wave can hit a fresh XLA
        compile mid-traffic (chip-measured: per-k program variants put
        30-60s compiles on the serving path — a 14s p95 on an otherwise
        600ms-p50 load test). The padded rows' prefill compute is one
        batched forward — noise. Returns the in-flight record."""
        n = len(group)
        k = self.slots
        bucket = _pow2_bucket(
            max(max(len(r.prompt) for _, r in group), 1), self.bucket_min,
            self.max_len)
        # HOL attribution snapshot BEFORE the new rows take slots: the live
        # decoding rows with undispatched work are exactly the rows this
        # prefill dispatch delays (its wall charges to them at processing)
        stalled = self._stalled_rows()
        padded_group = group + [group[-1]] * (k - n)
        prompts = np.zeros((k, bucket), np.int32)
        plens = np.zeros((k,), np.int32)
        slots = np.zeros((k,), np.int32)
        max_news = np.zeros((k,), np.int32)
        temps = np.zeros((k,), np.float32)
        topks = np.zeros((k,), np.int32)
        eoss = np.zeros((k,), np.int32)
        keys = np.zeros((k, 2), np.uint32)
        for i, (slot, row) in enumerate(padded_group):
            plen = len(row.prompt)
            prompts[i, :plen] = row.prompt
            plens[i] = plen
            slots[i] = slot
            max_news[i] = row.max_new
            temps[i] = row.temp
            topks[i] = row.topk
            eoss[i] = row.eos
            keys[i] = row.key
        (self._slab, packed), cold = self._run_program(
            "prefill", (bucket,), self._prefill_admit,
            self._variables, self._slab, jnp.asarray(prompts),
            jnp.asarray(plens), jnp.asarray(slots), jnp.asarray(max_news),
            jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(eoss),
            jnp.asarray(keys), kind="admit", group=group, head_positions=k)
        now = time.monotonic()
        real_tokens = 0
        for slot, row in group:
            self._slot_rows[slot] = row
            self._steps_ahead[slot] = 0
            # lifecycle: queued -> slot-assigned
            row.slot_at = now
            self.stats.phase("queue_wait", now - row.entry.submitted_at)
            real_tokens += len(row.prompt)
        self.stats.admitted_wave()
        # prefill padding accounting: the program computes k x bucket token
        # positions; everything beyond the real prompts (bucket padding +
        # the rows repeated to pad the group to S) is padding compute; the
        # head takes one position a program row
        self.stats.admit_tokens(real_tokens, k * bucket - real_tokens,
                                head_positions=k)
        self._admits_inflight += 1
        # one prefill forward attends over the fresh [k, max_len] caches
        return ("admit", group, packed,
                k * self.max_len * self._kv_token_bytes, cold, stalled)

    def _dispatch_chunk(self, needed: int) -> tuple:
        """Enqueue one multi-token step program sized to the work: the
        largest chunk that fits ``needed`` steps, else the smallest (tails
        pay the small program instead of a full re-run).

        Under QUEUE PRESSURE (rows waiting for a slot) the sizing flips to
        the earliest completion instead: the smallest chunk covering the
        least-remaining active row, so its slot frees at the next boundary
        and an admission replaces it — short-request workloads (the
        chat-shaped 64-token case, VERDICT r4 weak-1) otherwise spend most
        of each oversubscribed chunk stepping rows that finished early,
        while admitted work waits a full big-chunk turnaround."""
        size = self._chunk_sizes[0]
        for t in self._chunk_sizes:
            if t <= needed:
                size = t
        with self._cond:
            pressure = bool(self._pending)
        if pressure and len(self._chunk_sizes) > 1:
            soonest = min((n for n in self._remaining_steps() if n > 0),
                          default=needed)
            for t in self._chunk_sizes:  # smallest size covering `soonest`
                if t >= soonest:
                    size = min(size, t)
                    break
        # a chunk dispatched while admissions sit unprocessed in the chain
        # shared the device with prefill work: its steps are attributed
        # cause=prefill_colocated in the decode-step histogram
        coloc = self._admits_inflight > 0
        (self._slab, packed), cold = self._run_program(
            "step", (size,), self._steps[size], self._variables, self._slab,
            kind="step", steps=size)
        for slot in range(self.slots):
            self._steps_ahead[slot] += size
        self.stats.chunk()
        # every step re-reads the whole [S, max_len] K and V stripes
        return ("chunk", packed, list(self._slot_rows),
                size * self.slots * self.max_len * self._kv_token_bytes,
                cold, coloc)

    def _charge_stall(self, stalled: List[_Row], svc_s: float) -> None:
        """Head-of-line attribution: a prefill-carrying program's service
        time was decode time every stalled row lost; charge it to each."""
        if svc_s > 0 and stalled:
            self.stats.hol_stall(svc_s, len(stalled))
            for r in stalled:
                r.hol_stall += svc_s

    def _process_record(self, rec: tuple, svc_s: float) -> int:
        """Route one materialized program's results to their rows.
        ``svc_s`` is the program's own time (service_interval): it feeds the
        decode-step, KV-bandwidth, head-of-line and cold-start series.
        Returns the tokens routed."""
        if rec[0] == "admit":
            _, group, packed, kv_bytes, cold, stalled = rec
            self._admits_inflight = max(0, self._admits_inflight - 1)
            # prefill KV reads count toward the byte total; the per-chunk
            # bandwidth observation stays a DECODE-path signal
            self.stats.kv_read(kv_bytes)
            self._charge_stall(stalled, svc_s)
            if cold:
                # first execution of a fresh program: quarantined
                self.stats.cold_start(svc_s)
            # first processed result of EITHER kind flips the cold-start
            # allowance off: admit-only traffic (max_new_tokens=1) must not
            # keep inflating client timeouts forever; a later first chunk
            # compile fits inside the normal request-scaled timeout
            self._warmed = True
            now = time.monotonic()
            tokens = 0
            for i, (slot, row) in enumerate(group):
                if row.canceled:
                    continue  # _evict_canceled owns the slot bookkeeping
                # lifecycle: slot-assigned -> prefilled (first token on host)
                if row.slot_at:
                    self.stats.phase("prefill", now - row.slot_at)
                first = int(packed[i, 0])
                row.out.append(first)
                self._emit_delta(row, [first], cold=cold)
                tokens += 1
                if not bool(packed[i, 1]):
                    self._complete_row(slot, row)
            return tokens
        _, packed, snapshot, kv_bytes, cold, coloc = rec
        if self.cache.expert_layers:
            # the block's last columns are each step's counts (_step_impl):
            # the experts its live rows chose, their assignments to held and
            # to identity experts; all assignments follow from the rows that
            # emitted, and the rest lie on other chips
            cols = len(_MOE_COUNTS)
            packed, counts = packed[:, :-cols], packed[:, -cols:].sum(axis=0)
            touched, held, zero = (int(n) for n in counts)
            made = (int((packed >= 0).sum()) * self.cache.experts_per_token
                    * self.cache.expert_layers)
            self.stats.moe_steps(held, touched, zero, made - held - zero)
        # decode-step histogram feed: the chunk's service time over its
        # steps is the per-step decode latency, and kv_bytes over it the
        # achieved KV bandwidth. Cold first executions quarantine to the
        # cold-start series; steps colocated with in-flight prefill split
        # to cause=prefill_colocated
        self.stats.chunk_fetched(svc_s, packed.shape[0],
                                 colocated=coloc, cold=cold)
        self.stats.kv_read(kv_bytes, svc_s)
        self._warmed = True
        # batch-occupancy truth, per device step: live = the device emitted
        # a token (its live flag was up), dead = a row was resident in this
        # chunk's snapshot but emitted nothing (finished/eos'd rows still
        # stepping — the exact waste SERVING_R5 had to reason about blind),
        # idle = no resident row (free capacity / drain lag)
        emitted_mask = packed >= 0  # [T, S]
        live_steps = int(emitted_mask.sum())
        resident = [s for s, r in enumerate(snapshot) if r is not None]
        dead_steps = int((~emitted_mask[:, resident]).sum()) if resident else 0
        T, S = packed.shape
        # capacity travels per chunk (the paged engine's program width is
        # decoupled from the dense engine's slot count): the partition
        # identity live + dead + idle == steps x capacity holds either way
        self.stats.chunk_occupancy(
            T, live_steps, dead_steps, T * S - live_steps - dead_steps,
            capacity=S)
        return self._route_chunk_tokens(packed, snapshot, cold=cold)

    def _route_chunk_tokens(self, packed, snapshot, cold: bool = False) -> int:
        """Route one packed [T, S] emission block to its rows (shared by
        the plain chunk path and the paged engine's spec records): fresh
        tokens append in order, -1 ends a row's block, eos/max_new close
        the row, and tokens for an already-done row count as waste so
        goodput + wasted stays the exact partition of every emitted
        token. Returns the tokens that reached a row."""
        routed = 0
        for slot, row in enumerate(snapshot):
            if row is None:
                continue
            if row.done:
                # the device computed tokens for a row whose waiter is
                # already gone (canceled/evicted after this chunk was
                # dispatched): they route nowhere, but they're real device
                # work — account them as wasted so goodput + wasted stays
                # the exact partition of every emitted token
                n = 0
                for t in range(packed.shape[0]):
                    if packed[t, slot] < 0:
                        break
                    n += 1
                if n:
                    self.stats.emitted(n, wasted=True)
                continue
            fresh: List[int] = []
            for t in range(packed.shape[0]):
                tok = int(packed[t, slot])
                if tok < 0:
                    break
                fresh.append(tok)
                row.out.append(tok)
                if ((row.eos >= 0 and tok == row.eos)
                        or len(row.out) >= row.max_new):
                    break
            if fresh:
                self._emit_delta(row, fresh, cold=cold)
                routed += len(fresh)
            if ((row.eos >= 0 and row.out and row.out[-1] == row.eos)
                    or len(row.out) >= row.max_new):
                self._complete_row(slot, row)
        return routed

    def _evict_canceled(self) -> None:
        """Free slots whose rows were abandoned (wait() timeout / cancel):
        the device-side live flag drops so the slot stops burning steps."""
        for slot, row in enumerate(self._slot_rows):
            if row is not None and row.canceled:
                self._slab.live = self._slab.live.at[slot].set(False)
                row.done = True
                self._slot_rows[slot] = None
                with self._cond:
                    self._free.append(slot)

    def _free_drained_slots(self) -> None:
        """Pre-free slots whose rows' every possible emission is ALREADY in
        the dispatch chain (``steps_ahead >= max_new - 1``): the device has
        stopped advancing them (``remaining`` hits 0 and the live flag
        drops inside the step scan), their tokens come back with the
        in-flight results regardless, and token routing uses per-dispatch
        snapshots — so the next admission may overwrite the slot wholesale
        and the handoff is race-free. Without this, a finished request's
        slot sat dead for up to ``depth x chunk`` steps (the fetch lag)
        before its completion was processed and the slot re-admitted —
        the diagnosed cost of the 256-token workload's 0.44-0.53 fraction
        (VERDICT r5 weak-1)."""
        for slot, row in enumerate(self._slot_rows):
            if row is None or row.done or row.canceled:
                continue
            if self._steps_ahead[slot] >= row.max_new - 1:
                row.drained = True
                self._slot_rows[slot] = None
                with self._cond:
                    self._draining.append(row)
                    self._free.append(slot)

    def _complete_row(self, slot: int, row: _Row) -> None:
        row.done = True
        self._observe_completion_phases(row)
        self._release_row_slot(slot, row)
        self._finish_entry(row.entry)

    def _observe_completion_phases(self, row: _Row) -> None:
        now = time.monotonic()
        if row.first_emit_at:
            # lifecycle: first token -> the row's last emitted token
            self.stats.phase("decode_active",
                             row.last_emit_at - row.first_emit_at)
        # slot-idle: how long the slot stayed held past the row's last
        # useful token. A pre-freed (drained) slot was re-admitted at
        # dispatch time — its idle lag is 0 by construction, and observing
        # the 0 keeps the histogram honest about the pre-free win.
        self.stats.phase("slot_idle",
                         0.0 if row.drained or not row.last_emit_at
                         else now - row.last_emit_at)

    def _release_row_slot(self, slot: int, row: _Row) -> None:
        if row.drained:
            # the slot was pre-freed at dispatch time and may already hold
            # a newly admitted row — only retire the drain bookkeeping.
            # Removal is BY IDENTITY: _Row/_Entry are dataclasses whose
            # structural __eq__ recurses through the row<->entry cycle, so
            # `in`/`.remove` against a list holding any OTHER row would
            # blow the stack
            with self._cond:
                self._draining = [r for r in self._draining if r is not row]
        else:
            self._slot_rows[slot] = None
            with self._cond:
                self._free.append(slot)

    def _finish_entry(self, entry: _Entry) -> None:
        if entry.finished():
            if self._record_outcome(entry):
                self.stats.completed(time.monotonic() - entry.submitted_at)
                self._finish_timeline(entry, "completed")
            entry.done_evt.set()
            if entry.stream_q is not None:
                entry.stream_q.put(None)

    def _emit_delta(self, row: _Row, tokens: List[int],
                    cold: bool = False) -> None:
        entry = row.entry
        now = time.monotonic()
        if entry.first_token_at == 0.0:
            entry.first_token_at = now
            # a first token off a freshly compiled program carries the
            # compile wall — it lands in cold_start, not the TTFT series
            self.stats.first_token(entry.first_token_at - entry.submitted_at,
                                   cold=cold)
            if not self._startup_logged:
                # the operator's reading of a replica's start, once
                self._startup_logged = True
                log.info("%s: first token %.2f s after its request; "
                         "start-up in s: %s", self.name,
                         entry.first_token_at - entry.submitted_at,
                         self.stats.startup_report())
        if row.first_emit_at == 0.0:
            row.first_emit_at = now
        else:
            # inter-token latency: the host-visible gap since this row's
            # previous emission arrival (n emissions -> n-1 gaps; a
            # multi-token delta is ONE arrival — in-chunk spacing is not
            # host-visible and would fabricate smoothness)
            gap = now - row.last_emit_at
            row.itl.append(gap)
            self.stats.inter_token(gap)
        row.last_emit_at = now
        # goodput truth: tokens routed to a waiter that already gave up
        # (timeout/cancel claimed the outcome) are computed waste
        self.stats.emitted(len(tokens), wasted=entry.aborted)
        q = entry.stream_q
        if q is not None:
            q.put({"row": row.index, "tokens": tokens})

    def _fail_all(self, error: Exception, wrap: bool = False) -> None:
        with self._cond:
            rows = (list(self._pending) + [r for r in self._slot_rows if r]
                    + list(self._draining))
            self._pending.clear()
            self._slot_rows = [None] * self.slots
            self._draining = []
            self._free = list(range(self.slots))
        failed_entries = set()
        for row in rows:
            row.done = True
            entry = row.entry
            if entry.error is None:
                # wrap=True (a LOOP fault — the engine rebuilds and keeps
                # serving): an in-flight request gets a DETERMINISTIC
                # retryable envelope — 503 + the tokens each stream emitted
                # before the fault — never the raw backend exception (whose
                # 500 a client must treat as fatal) and never a hang on
                # done_evt (ISSUE 20 regression seam). Init failures and
                # close() keep the raw error: the decoder is CLOSED, so
                # "retry the same endpoint" would be a lie.
                if not wrap or isinstance(error, KubeMLError):
                    entry.error = error
                else:
                    from ..api.errors import EngineFaultError

                    entry.error = EngineFaultError(
                        f"decode engine fault: {error}",
                        partial_tokens=[list(r.out) for r in entry.rows])
            if id(entry) not in failed_entries:
                failed_entries.add(id(entry))
                if self._record_outcome(entry):
                    self.stats.failed()
                    self._finish_timeline(entry, "failed")
            entry.done_evt.set()
            if entry.stream_q is not None:
                entry.stream_q.put(None)


class _DrainReq:
    """Rendezvous between ``drain()`` (a server thread) and the engine loop:
    the engine quiesces its dispatch chain, snapshots stragglers into KMS1
    frames, and posts them back through ``frames`` before setting ``evt``."""

    def __init__(self):
        self.evt = threading.Event()
        self.frames: List[bytes] = []


class PagedBatchingDecoder(BatchingDecoder):
    """The paged KV-cache serving engine: continuous batching with a block
    allocator, per-token admission, and shared-prefix reuse.

    Where :class:`BatchingDecoder` gives every row a full ``[max_len, H, D]``
    cache stripe, this engine carves the device KV arena into fixed-size
    pages (``KUBEML_SERVING_PAGE_TOKENS``) addressed through per-row page
    tables (serving/kvpool.py), so a row holds memory proportional to what
    it actually decodes and the admission test is a PAGE BUDGET, not a slot
    count. ``slots`` here is only the step program's static row width (the
    compile shape); rows of any length share the one jitted step program
    via gather/scatter page indexing in the model's paged attention path.

    Three structural differences from the slot engine:

    * **Per-token admission** — chunks are sized down a pow2 ladder to end
      exactly at the earliest row completion, the finished row's program
      row and pages free AT DISPATCH TIME (its remaining emissions are all
      in the ordered dispatch chain, so reuse is race-free — what the slot
      engine bolted on as the pre-free hack is the admission design here,
      with exact per-row ``dispatched`` accounting replacing the
      ``_steps_ahead`` compensation), and the next queued request admits at
      the very next chunk edge. On a no-EOS workload dead slot-steps are
      ZERO by construction — the regression test holds the engine to it.
    * **Shared-prefix reuse** — full prompt-token blocks are cached in a
      refcounted prefix trie; an identical system prompt / few-shot header
      maps to the same physical pages, prefill runs ONLY on the unshared
      suffix, and the request payload reports ``prefix_cached_tokens``.
    * **Page-budget overload truth** — a request that could never fit the
      arena 400s at submit; one that merely can't fit NOW queues at the
      head of the line until pages free (or its deadline expires).
    * **One real row per admission program** — the suffix-prefill program
      is handed the one request it admits (``_run_prefill``); a wave of n
      rows is n programs, and the row count is no axis of the program's
      key. The slot engine pads a wave to ``slots`` rows instead.

    Quantized weights (int8 / native int8 matmul) compose unchanged — the
    arena is cache state, not weights. A mesh does not: sharded serving
    stays on the dense engine until the arena learns a head-sharded layout.

    **Recurrent state beside the pages** — a model with a Mamba-2 mixer
    beside attention, or a Gated DeltaNet mixer in its place in some layers
    (``CacheSpec.state_layers`` of them), keeps, in each such layer, one
    fixed-size state per program row (``ssm_state`` ``[slots, H, N, P]`` or
    ``gdn_state`` ``[slots, H / p, d_k, p d_v]``, float32, and
    ``conv_tail``; ``CacheSpec.state_row_bytes`` a row) in the same
    ``cache`` tree as the arena: donated, rebuilt and freed with it. A layer
    whose mixer is the state alone has no arena, so the pool's pages are the
    other layers'. An admission names its row's place
    (``rows``) and the model starts it from zeros (a reused slot), or from
    the row's own state where a chunked prefill continues, and writes the
    state at the prompt's true length; the decode step advances the state
    of live rows only, in place (ops/ssm.py ``ssm_update``, ops/
    gated_delta.py ``gdn_update``: the kernel reads and writes every slab
    row, ``state_rows_moved``, of which ``state_rows_live`` advance).

    **Two kinds of lease** — a model that mixes window layers with full
    ones (``CacheSpec.window_layers``) has two arenas a kind: the
    full layers' pages, addressed through ``_table`` as above, and the
    window layers' RINGS, ``window / page_tokens + 2`` pages a row whatever
    its depth (``_wtable`` ``[slots, ring]``; serving/kvpool.py says why a
    ring). Every program takes both tables (``pages = (table, rings)``,
    models/gpt.py), an admit writes only the tail of its bucket into the
    ring, and the window arenas are ``slots`` rings whatever ``max_len``.

    What a model's caches refuse or switch off of the options below is one
    table, :data:`CACHE_FEATURES`, read once here (and at a snapshot).
    """

    # the options' defaults are api/config.py Config's, as the slot engine's
    def __init__(self, module, variables, *, page_tokens: int = 16,
                 pages: int = 0, prefix_cache: bool = True, mesh=None,
                 spec: str = "", spec_k: int = 4, spec_adaptive: bool = True,
                 draft_module=None, draft_variables=None,
                 spec_exit_layer: Optional[int] = None,
                 paged_attn: str = "auto", kv_quant: str = "off",
                 spec_min_accept: float = 0.10,
                 prefill_chunk_tokens: int = 0,
                 pool_audit_interval: float = 0.0, **kw):
        if mesh is not None:
            raise ValueError(
                "paged serving does not run on a mesh yet; use the dense "
                "BatchingDecoder for sharded serving")
        from ..models.generation import supports_paged_decode

        if not supports_paged_decode(module):
            raise GenerationInputError(
                "module has no paged decode path (pages/seq_lens decode "
                "kwargs + page_tokens/kv_pages fields); serve it through "
                "the dense BatchingDecoder")
        cap = getattr(module, "max_len", None)
        if cap is None:
            raise GenerationInputError(
                "model exposes no max_len attribute; batched decode requires "
                "a declared KV-cache capacity")
        from ..ops.paged_attention import resolve_kv_quant, resolve_paged_attn
        from .kvpool import KVPool

        # --- speculative decoding (KUBEML_SERVING_SPEC=draft|self|off) ---
        if spec in ("off", None):
            spec = ""
        if spec not in ("", "draft", "self"):
            raise ValueError(f"unknown spec mode {spec!r} "
                             f"(valid: 'off', 'draft', 'self')")
        self.spec = spec
        pt = int(page_tokens)
        slots = int(kw.get("slots", DEFAULT_SLOTS))
        self.page_tokens = pt
        kvq = resolve_kv_quant(kv_quant)
        self.kv_quant = kvq
        # --- chunked prefill (KUBEML_PREFILL_CHUNK_TOKENS, ISSUE 19):
        # a cold prompt whose unshared suffix exceeds the cap advances one
        # page-aligned chunk per engine-loop iteration through the same
        # suffix-prefill program, interleaved with decode chunks, instead
        # of one monolithic prefill stalling every decoding row. 0 = off.
        self.prefill_chunk = _chunk_cap(int(prefill_chunk_tokens), pt)
        # what the model's caches are, asked once, and what they make of
        # the options handed in: a refusal by name, or a feature off
        cache = cache_spec(module)
        asked = {feature for feature, on in (
            ("prefix_sharing", prefix_cache), ("int8_pages", kvq == "int8"),
            ("chunked_prefill", self.prefill_chunk),
            ("spec_self", spec == "self"), ("spec_draft", spec == "draft"),
        ) if on}
        self._features_off = check_cache_features(cache, asked)
        for feature, prop in self._features_off.items():
            # served without it: said once here, flagged in telemetry()
            log.warning("%s: %s is off for %s: %s",
                        kw.get("name", "decoder"), _FEATURE_WORDS[feature],
                        *_PROPERTY_WORDS[prop])
        use_trie = bool(prefix_cache) and (
            "prefix_sharing" not in self._features_off)
        # per-row logical table width: enough pages to address max_len
        self.table_pages = -(-int(cap) // pt)
        npages = int(pages)
        if npages <= 0:
            # default arena matches the slot engine's worst case (every
            # program row at full depth) plus the reserved trash page —
            # never admission-regresses vs slot mode; size it DOWN via
            # KUBEML_SERVING_PAGES for the memory win
            npages = slots * self.table_pages + 1
        # --- KV-cache storage quantization (KUBEML_KV_QUANT=off|int8,
        # ops/paged_attention.resolve_kv_quant): arena sizing derives the
        # page count FROM THE BYTE BUDGET the unquantized arena would
        # occupy, so int8 mode yields ~2x (bf16) / ~4x (f32) the pages at
        # the same HBM spend — capacity, not memory, is the win surfaced.
        if kvq == "int8":
            bytes_off = cache.page_bytes(pt, "off")
            bytes_q = cache.page_bytes(pt, "int8")
            if bytes_off and bytes_q:
                budget = (npages - 1) * bytes_off
                npages = max(npages, budget // bytes_q + 1)
        # the second kind of lease: a ring a program row in the window
        # layers' arenas, whatever max_len (0 / 0 without window layers)
        self.window_ring = cache.ring_pages(pt)
        self.window_arena_pages = (slots * self.window_ring + 1
                                   if self.window_ring else 0)
        self._pool = KVPool(npages, pt, prefix_cache=use_trie,
                            window_pages=self.window_arena_pages,
                            window_ring=self.window_ring)
        self.arena_pages = npages
        # --- paged-attention read path (KUBEML_PAGED_ATTN=auto|pallas|
        # gather, ops/paged_attention.py): resolved HERE and cloned onto
        # the module, so the impl is part of the module identity every jit
        # trace sees — toggling the knob builds a fresh decoder with fresh
        # programs, never a stale one.
        impl = resolve_paged_attn(paged_attn)
        self.paged_attn = impl
        k_cap = int(spec_k)
        self.spec_exit_layer = 0
        self.draft_module = None
        self._draft_variables = None
        self._draft_cache = None
        if spec == "draft":
            if draft_module is None or draft_variables is None:
                raise GenerationInputError(
                    "spec='draft' needs a draft module + variables "
                    "(KUBEML_SPEC_DRAFT_MODEL names the checkpointed job)")
            if not supports_paged_decode(draft_module):
                raise GenerationInputError(
                    "draft module has no paged decode path")
            if getattr(draft_module, "vocab_size", None) != \
                    getattr(module, "vocab_size", None):
                raise GenerationInputError(
                    "draft and target models must share one vocabulary")
            if int(getattr(draft_module, "max_len", cap)) < int(cap):
                raise GenerationInputError(
                    f"draft model max_len "
                    f"({getattr(draft_module, 'max_len', None)}) must cover "
                    f"the target's ({cap})")
            # the drafter addresses THE SAME page ids through its own
            # arena, so shared-prefix pages carry valid draft K/V too
            # (and reads it through the same attention impl + storage mode
            # — the doubled page count must not double the draft arena's
            # bytes)
            self.draft_module = draft_module.clone(
                page_tokens=pt, kv_pages=npages, paged_attn=impl,
                kv_quant=kvq)
        elif spec == "self":
            depth = getattr(module, "depth", None)
            e = int(spec_exit_layer if spec_exit_layer
                    else max(1, (depth or 2) // 2))
            if depth is not None and not (1 <= e <= depth):
                raise GenerationInputError(
                    f"spec_exit_layer must be in [1, depth={depth}], got {e}")
            self.spec_exit_layer = e
        from .spec import AdaptiveK

        # the draft backend never suspends (its KV cache is only coherent
        # while the drafter sees every decoded token); self-drafting may
        # retreat to plain decode and re-probe. A DRAFT backend whose
        # sustained acceptance sits below KUBEML_SPEC_MIN_ACCEPT instead
        # disables permanently (spec.py) — a mismatched draft checkpoint
        # degrades to plain decode, not a latent throughput regression.
        self._spec_ctl = (AdaptiveK(
            k_cap, adaptive=bool(spec_adaptive), allow_off=(spec == "self"),
            min_accept=(float(spec_min_accept) if spec == "draft" else 0.0))
            if spec else None)
        self._spec_disabled_logged = False
        # worst-case page reservation must cover the verify lookahead: a
        # spec step writes up to k positions past the row's final token
        # before the host learns they were rejected (admission math below)
        self._spec_lookahead = k_cap if spec else 0
        # the arena dims ride the module as clone fields so the flax cache
        # variables know their shapes (params are untouched by the clone)
        clone_kw = dict(page_tokens=pt, kv_pages=npages, paged_attn=impl,
                        kv_quant=kvq)
        if cache.recurrent:
            # one recurrent state per program row, beside the arena
            clone_kw["state_rows"] = slots
        if self.window_ring:
            clone_kw["window_pages"] = self.window_arena_pages
        module = module.clone(**clone_kw)
        super().__init__(module, variables, mesh=None, cache=cache, **kw)
        self._kv_token_bytes = cache.token_bytes(kvq)
        self._window_token_bytes = cache.window_token_bytes()
        # a decode step's K/V page walk takes the kernel's decode body (one
        # query a row, the arena in the compute type): its grid is counted
        # at each chunk dispatch (_walk_chunks), the tile body's at each
        # prefill dispatch (_run_prefill)
        self.stats.walks_kv_chunks = (
            impl == "pallas" and kvq == "off" and cache.latent is None)
        # a latent arena's steps take the latent walk's loop; its trips are
        # counted at each chunk dispatch too (_latent_walk_trips)
        self.stats.walks_latents = (
            impl == "pallas" and cache.latent is not None)
        # drafter KV-read constant for the spec accounting: the early-exit
        # self-drafter reads only its truncated stack's layers; a separate
        # draft model reads its own geometry
        if spec == "self":
            self._kv_draft_token_bytes = cache.token_bytes(
                kvq, first=self.spec_exit_layer)
        elif spec == "draft":
            self._kv_draft_token_bytes = cache_spec(
                self.draft_module).token_bytes(kvq)
        else:
            self._kv_draft_token_bytes = 0
        if spec == "draft":
            from .quant import is_quantized_tree, quantize_tree

            # the drafter rides the SAME int8 path as the target: a
            # pre-quantized tree (the quantized-checkpoint store) loads
            # as-is, a dense one quantizes here
            if is_quantized_tree(draft_variables):
                if self.quantize != "int8":
                    raise ValueError(
                        "draft variables carry int8 QuantizedTensor leaves "
                        "but quantize is not 'int8'")
            elif self.quantize == "int8":
                draft_variables = quantize_tree(draft_variables)
            self._draft_variables = jax.device_put(draft_variables)
        # pow2 chunk ladder: any remaining-step count decomposes into
        # ladder chunks, so chunks end EXACTLY at the earliest completion
        # (the per-token admission edge) with a bounded program set —
        # log2(chunk_steps) compiles, not one per request length
        import functools

        ladder = {self.chunk_steps}
        t = 1
        while t < self.chunk_steps:
            ladder.add(t)
            t *= 2
        self._chunk_sizes = sorted(ladder)
        self._steps = {
            T: jax.jit(functools.partial(self._step_impl, steps=T),
                       donate_argnums=(1,))
            for T in self._chunk_sizes
        }
        if self.spec:
            # one spec-step program per adaptive-k ladder rung (bounded
            # compile set, like the chunk ladder); the slab and the draft
            # cache are donated through the chain
            self._spec_steps = {
                kk: jax.jit(functools.partial(self._spec_step_impl, k=kk),
                            donate_argnums=(1, 4))
                for kk in self._spec_ctl.ladder
            }
            if self.spec == "draft":
                # admission must also prefill the drafter's arena: swap in
                # the draft-aware prefill program
                self._prefill_admit = jax.jit(
                    self._prefill_admit_spec_impl,
                    donate_argnums=(3, 2))
        # host page-table mirror handed to every dispatch ([slots, P] i32);
        # zeroed rows point at the trash page, so a retired/canceled row's
        # stale device writes can never reach a reallocated page
        self._table = np.zeros((self.slots, self.table_pages), np.int32)
        # the window layers' rings, a row each (zeroed like _table's rows)
        self._wtable = np.zeros((self.slots, self.window_ring), np.int32)
        # rows mid-prefill: (slot, row) pairs holding program rows + leases
        # whose prompts still have undispatched chunks; the turn flag
        # alternates the last pipeline slot between a prefill chunk and a
        # decode chunk when both contend for it
        self._prefill_pending: List[tuple] = []
        self._prefill_turn = True
        # --- KVPool invariant watchdog (KUBEML_POOL_AUDIT_INTERVAL,
        # ISSUE 20): the engine loop runs kvpool.check() every interval
        # seconds under the engine lock; a tripped invariant fires the
        # errorhook and routes through fault recovery (snapshot-and-replay)
        # instead of decoding through silent accounting corruption. 0 = off
        self.pool_audit_interval = float(pool_audit_interval)
        self._next_audit = 0.0
        # graceful-drain rendezvous: drain() posts a _DrainReq; the engine
        # thread quiesces the dispatch chain, snapshots stragglers, and
        # hands the KMS1 frames back through it
        self._drain_req: Optional[_DrainReq] = None
        self._param_bytes = sum(
            int(l.size) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(self._variables) if hasattr(l, "dtype"))
        self._expert_param_bytes = sum(
            int(l.size) * np.dtype(l.dtype).itemsize for l in _leaves_named(
                self._variables, "w_gate", "w_up", "w_down"))

    # --- capacity & programs ---

    def _check_capacity(self, plen: int, max_new: int) -> None:
        if not self._pool.can_admit(plen, max_new,
                                    lookahead=self._spec_lookahead,
                                    max_positions=self.max_len):
            need = self._pool.pages_for(self._pool.total_positions(
                plen, max_new, self._spec_lookahead, self.max_len))
            raise KubeMLError(
                f"request needs {need} "
                f"KV pages but the arena holds {self._pool.capacity} "
                f"(KUBEML_SERVING_PAGES x KUBEML_SERVING_PAGE_TOKENS)", 400)

    def _init_slab_impl(self) -> _Slab:
        from ..models.generation import init_paged_cache

        dense_abstract = jax.eval_shape(self._dense_vars, self._variables)
        return self._slab_from_cache(init_paged_cache(
            self.module, dense_abstract, self.slots, self.table_pages,
            cache=self.cache))

    def _init_slab(self) -> _Slab:
        slab = super()._init_slab()
        if self.spec == "draft":
            # the drafter's own paged arena (same page ids, its own
            # head/depth dims) — rebuilt with the slab on fault recovery,
            # so a zeroed target arena never pairs with stale draft K/V
            from ..models.generation import init_paged_cache

            dense_abstract = jax.eval_shape(self._dense_draft_vars,
                                            self._draft_variables)
            self._draft_cache = init_paged_cache(
                self.draft_module, dense_abstract, self.slots,
                self.table_pages)
        return slab

    def _prefill_admit_impl(self, variables, slab, ptbl, suffix, base, slens,
                            rowids, max_news, temps, topks, eoss, keys):
        """ONE program per (suffix-length bucket, table width): prefill
        the UNSHARED suffix of each row it is handed straight into the
        paged arena (a prefix hit's cached pages are already there — only
        the suffix runs, the FLOP saving behind
        kubeml_serving_prefix_tokens_saved_total), scatter the row's
        cursors/knobs into its program row, and sample its first token.
        The engine hands it exactly ONE row, the request it admits
        (``_run_prefill``): the row count is a constant of the program,
        not an axis of its key, and no row is a copy of another."""
        variables = self._dense_vars(variables)
        # a recurrent model scatters the row's state into its program row
        # (zeros first where base is 0: a reused slot; the row's own state
        # where a chunked prefill goes on)
        kw = {"rows": rowids} if self.cache.recurrent else {}
        # the one position sampled from, the suffix's last real token, is
        # all the head is given: logits [1, 1, vocab], not the bucket's
        logits, vs = self.module.apply(
            {**variables, "cache": slab.cache}, suffix, decode=True,
            positions=base, pages=ptbl, seq_lens=slens,
            head_positions=slens - 1, mutable=["cache"], **kw)
        cache = vs["cache"]
        last = logits[:, 0].astype(jnp.float32)
        use, nxt_keys = _split_rows(keys)
        firsts = _sample_rows(last, use, temps, topks)
        hit_eos = (eoss >= 0) & (firsts == eoss)
        live0 = (max_news > 1) & ~hit_eos

        def put(vec, vals):
            return vec.at[rowids].set(vals.astype(vec.dtype))

        slab2 = _Slab(
            cache,
            put(slab.tok, firsts),
            put(slab.pos, base + slens),
            put(slab.live, live0),
            put(slab.remaining, max_news - 1),
            slab.keys.at[rowids].set(nxt_keys),
            put(slab.temp, temps),
            put(slab.topk, topks),
            put(slab.eos, eoss),
        )
        packed = jnp.stack([firsts, live0.astype(jnp.int32)], axis=1)
        return slab2, packed

    # --- speculative decoding (KUBEML_SERVING_SPEC=draft|self) ---

    def _dense_draft_vars(self, dvars):
        """The drafter's twin of ``_dense_vars``: int8 draft weights
        densify inside the traced program (or flow natively in int8-matmul
        mode); identity otherwise."""
        if self.quantize != "int8" or self.int8_matmul:
            return dvars
        from .quant import dequantize_tree

        return dequantize_tree(dvars, dtype=jnp.float32)

    def _prefill_admit_spec_impl(self, variables, draft_variables,
                                 draft_cache, slab, ptbl, suffix, base,
                                 slens, rowids, max_news, temps, topks,
                                 eoss, keys):
        """Draft-backend admission: the target prefill+admit PLUS the
        drafter's prefill of the same (unshared) suffix into its own
        arena through the same page tables — a prefix hit skips both
        prefills (the trie guarantees the cached pages were written from
        identical prompt blocks, so the incumbent's draft K/V is equally
        valid)."""
        slab2, packed = self._prefill_admit_impl(
            variables, slab, ptbl, suffix, base, slens, rowids, max_news,
            temps, topks, eoss, keys)
        dvars = self._dense_draft_vars(draft_variables)
        # the drafter's logits are dropped: only its cache is wanted, and
        # one row keeps its head's product over the bucket out of the trace
        _, dvs = self.draft_module.apply(
            {**dvars, "cache": draft_cache}, suffix, decode=True,
            positions=base, pages=ptbl, seq_lens=slens,
            head_positions=slens - 1, mutable=["cache"])
        return slab2, dvs["cache"], packed

    def _spec_step_impl(self, variables, slab, pages, draft_variables,
                        draft_cache, *, k):
        """ONE speculative macro-step over every program row: the drafter
        proposes k tokens per live row, the target verifies all k+1
        positions in a single batched forward (the same L>1 paged suffix
        path admission uses), and the canonical acceptance rule emits
        1..k+1 tokens per row. Rollback is purely positional: a rejected
        suffix's K/V entries are dead-by-position and the next step's
        k+1-wide write window overwrites them — no copy, no page churn.

        Emits a packed [k+1, S] block (-1 past each row's clip — host
        routing is byte-compatible with the chunk path) plus a [2, S]
        device-truth stats block (drafted, accepted per row)."""
        variables = self._dense_vars(variables)
        S = self.slots
        from ..models.generation import (draft_sample, spec_accept,
                                         spec_mask_emissions)

        use, nxt_keys = _split_rows(slab.keys)
        live = slab.live
        if self.spec == "self":
            dvars, dc0, dmod = variables, slab.cache, self.module
            dkw = {"exit_layer": self.spec_exit_layer}
        else:
            dvars = self._dense_draft_vars(draft_variables)
            dc0, dmod, dkw = draft_cache, self.draft_module, {}

        def dr(carry, i):
            dc, t, p = carry
            lg, vs = dmod.apply(
                {**dvars, "cache": dc}, t[:, None], decode=True,
                positions=p, pages=pages,
                seq_lens=jnp.where(live, 1, 0), mutable=["cache"], **dkw)
            dk = jax.vmap(jax.random.fold_in)(use, jnp.full((S,), i))
            d_i, q_i = draft_sample(lg[:, -1].astype(jnp.float32),
                                    slab.temp, slab.topk, dk,
                                    topk_cap=TOP_K_MAX)
            return (vs["cache"], d_i, p + 1), (d_i, q_i)

        # the draft backend runs one extra WRITE-ONLY iteration: the k-th
        # draft's K/V must land in the drafter's own cache too, or a fully
        # accepted step leaves a permanent zero-KV gap at that position
        # (self-drafting skips it — the verify re-writes the shared arena)
        iters = k + 1 if self.spec == "draft" else k
        (dc_out, _, _), (d, q_probs) = jax.lax.scan(
            dr, (dc0, slab.tok, slab.pos), jnp.arange(iters))
        drafts = d.T[:, :k]                            # [S, k]
        q_probs = jnp.moveaxis(q_probs, 0, 1)[:, :k]   # [S, k, V]
        vcache = dc_out if self.spec == "self" else slab.cache
        vt = jnp.concatenate([slab.tok[:, None], drafts], axis=1)
        vlg, vs = self.module.apply(
            {**variables, "cache": vcache}, vt, decode=True,
            positions=slab.pos, pages=pages,
            seq_lens=jnp.where(live, k + 1, 0), mutable=["cache"])
        emit, n_acc = spec_accept(vlg.astype(jnp.float32), drafts, q_probs,
                                  slab.temp, slab.topk, use,
                                  topk_cap=TOP_K_MAX)
        out, n_take, live2, rem2, feed = spec_mask_emissions(
            emit, n_acc, live, slab.remaining, slab.eos, slab.tok)
        pos2 = jnp.where(live, slab.pos + n_take, slab.pos)
        slab2 = _Slab(vs["cache"], feed, pos2, live2, rem2, nxt_keys,
                      slab.temp, slab.topk, slab.eos)
        stats = jnp.stack([jnp.where(live, k, 0),
                           jnp.where(live, n_acc, 0)]).astype(jnp.int32)
        dc_ret = dc_out if self.spec == "draft" else None
        return slab2, dc_ret, out.T, stats

    def _dispatch_spec_chunk(self, k: int) -> tuple:
        # a verify window reads/writes up to k+1 positions past each row's
        # cursor; the table ships clamped to the live width and as a copy
        # for the same aliasing reason as _dispatch_chunk_paged
        w = self._live_table_width(k + 1)
        (self._slab, dc, packed, stats), cold = self._run_program(
            "spec_step", (k, w), self._spec_steps[k],
            self._variables, self._slab,
            jnp.asarray(self._table[:, :w].copy()),
            self._draft_variables, self._draft_cache,
            kind="spec", steps=k + 1, width=w)
        if self.spec == "draft":
            self._draft_cache = dc
        # KV model: drafter iteration i reads i positions past the cursor
        # (k iterations, +1 write-only in draft mode), the verify forward
        # reads the whole k+1-deep window once
        iters = k + 1 if self.spec == "draft" else k
        kv_bytes = (self._chunk_kv_tokens(w, k + 1) * self._kv_token_bytes
                    + sum(self._chunk_kv_tokens(w, i)
                          for i in range(1, iters + 1))
                    * self._kv_draft_token_bytes)
        self._bump_pos_caps(k + 1)
        for row in self._slot_rows:
            if (row is not None and not row.done and not row.canceled
                    and not row.prefilling):
                # a live row emits AT LEAST one token per macro-step, so
                # counting 1 keeps the dispatch gate conservative (the
                # actual count lands with the results)
                row.dispatched += 1
        self.stats.chunk()
        return ("spec", packed, stats, list(self._slot_rows), k, kv_bytes,
                cold)

    def _materialize(self, rec: tuple) -> tuple:
        if rec[0] == "spec":
            return ("spec", np.asarray(rec[1]), np.asarray(rec[2])) + rec[3:]
        if rec[0] == "pchunk":
            # the fetch is the dispatch's execution barrier, same as an
            # admit record
            return ("pchunk", rec[1], np.asarray(rec[2])) + rec[3:]
        return super()._materialize(rec)

    def _process_record(self, rec: tuple, svc_s: float) -> int:
        if rec[0] == "pchunk":
            # an intermediate prefill chunk emits nothing and routes
            # nothing; its accounting mirrors the admit branch (KV reads,
            # HOL charge to the snapshot's stalled rows, cold-start
            # quarantine) minus the token lifecycle
            _, batch, _packed, kv_bytes, cold, stalled = rec
            self._admits_inflight = max(0, self._admits_inflight - 1)
            self.stats.kv_read(kv_bytes)
            self._charge_stall(stalled, svc_s)
            if cold:
                self.stats.cold_start(svc_s)
            self._warmed = True
            return 0
        if rec[0] != "spec":
            return super()._process_record(rec, svc_s)
        _, packed, stats_arr, snapshot, k, kv_bytes, cold = rec
        self._warmed = True
        if cold:
            # a spec macro-step never feeds decode_step, but its first
            # execution still belongs in the cold-start series
            self.stats.cold_start(svc_s)
        # no decode-step observation (a macro-step is k+1 tokens wide, not
        # a per-token step) — but the KV reads and their bandwidth are real
        self.stats.kv_read(kv_bytes, svc_s)
        emitted_mask = packed >= 0  # [k+1, S]
        live_steps = int(emitted_mask.sum())
        resident = [s for s, r in enumerate(snapshot) if r is not None]
        dead = int((~emitted_mask[:, resident]).sum()) if resident else 0
        T, S = packed.shape
        # token-truth occupancy: ONE device step whose capacity is the
        # verify window's S x (k+1) token slots. live = emitted, dead =
        # a resident row's unemitted slots (rejected speculation — the
        # measured cost of a wrong drafter), idle = no resident row. The
        # partition identity live + dead + idle == steps x capacity holds,
        # and tokens-per-step reads tokens_emitted / device_steps.
        self.stats.chunk_occupancy(1, live_steps, dead,
                                   T * S - live_steps - dead,
                                   capacity=T * S)
        drafted, accepted = stats_arr[0], stats_arr[1]
        d_sum = int(drafted.sum())
        a_sum = int(accepted.sum())
        live_rows = int((drafted > 0).sum())
        self.stats.spec_step(d_sum, a_sum, d_sum + live_rows)
        if self._spec_ctl is not None:
            self._spec_ctl.on_step(d_sum, a_sum)
            if self._spec_ctl.disabled and not self._spec_disabled_logged:
                self._spec_disabled_logged = True
                log.warning(
                    "%s: draft speculation disabled — sustained acceptance "
                    "%.3f below KUBEML_SPEC_MIN_ACCEPT=%.3f; decoding "
                    "continues plain (kubeml_serving_spec_disabled=1)",
                    self.name, self._spec_ctl.ratio,
                    self._spec_ctl.min_accept)
        for slot, row in enumerate(snapshot):
            if row is None or drafted[slot] <= 0:
                continue
            row.spec_proposed += int(drafted[slot]) + 1
            row.spec_accepted += int(accepted[slot])
        return self._route_chunk_tokens(packed, snapshot, cold=cold)

    # --- admission (engine thread; caller holds self._cond) ---

    def _take_admissions_locked(self, max_n: int) -> List[tuple]:
        """Admit queued rows in FIFO order while a program row is free AND
        the page budget covers them (worst-case reservation: prompt +
        max_new-1 positions, minus whatever the prefix trie already
        caches). The head of the line blocks the tail — admission stays
        fair, and a starved head admits the moment pages free at a chunk
        edge. ``max_n`` bounds the dispatches one iteration may create so
        the pipeline gate never has to un-admit a leased row."""
        admits = []
        while len(admits) < max_n and self._pending and self._free:
            row = self._pending[0]
            if row.canceled:
                self._pending.popleft()
                continue
            if row.snapshot is not None:
                # restore admission: fresh PRIVATE pages for the snapshot
                # scatter (no trie — the bytes come from another engine's
                # write history, so sharing them would poison the prefix
                # cache); budget-refused restores stay queued at the head
                # exactly like plain rows until pages free
                lease = self._pool.reserve(self._pool.total_positions(
                    len(row.prompt), row.max_new,
                    lookahead=self._spec_lookahead,
                    max_positions=self.max_len))
            else:
                lease = self._pool.admit(row.prompt, row.max_new,
                                         lookahead=self._spec_lookahead,
                                         max_positions=self.max_len)
            if lease is None:
                break
            self._pending.popleft()
            slot = self._free.pop(0)
            row.lease = lease
            row.prefix_cached = lease.prefix_tokens
            if lease.shared:
                self.stats.prefix_hit(lease.prefix_tokens)
            admits.append((slot, row))
        return admits

    def _stalled_rows(self) -> List[_Row]:
        """Paged flavor: undispatched work reads from the per-row
        ``dispatched`` accounting (a row `_retire_dispatched` already
        drained mid-chunk left ``_slot_rows`` and is never charged).
        Rows mid-chunked-prefill are NOT victims: they are not decoding
        yet, so a colocated dispatch costs them nothing the chunking
        didn't already choose (their own prefill latency is TTFT, tracked
        separately)."""
        return [row for row in self._slot_rows
                if row is not None and not row.done and not row.canceled
                and not row.prefilling
                and row.max_new - 1 - row.dispatched > 0]

    def _run_prefill(self, slot: int, row: _Row, take: int, kind: str
                     ) -> tuple:
        """Dispatch the suffix-prefill program for ONE row: ``take`` prompt
        tokens from the row's prefill cursor, through the row's own pages.
        Every argument has one row — ``(1, bucket)`` tokens under a ``(1,
        table width)`` page table — whoever calls: an admission, an
        intermediate chunk, with or without a draft arena. So the key
        ``("prefill", (bucket, wa))`` names ONE compiled program whatever
        the waves that came before, and the program computes nothing but
        the request it was handed: a wave of n rows is n of these, one
        after another under the run-ahead gate. (Until PR 31 the row count
        was padded to ``slots`` with copies of the last row: 82-98% of the
        positions a cell prefilled.) ``kind`` is the record's: an ``admit``
        scatters the row's own sampling knobs, a ``pchunk`` a dead
        placeholder. Returns the record's tail ``(packed, kv_bytes, cold,
        stalled)``."""
        pre = row.lease.prefill_pos
        pt = self.page_tokens
        bucket = _pow2_bucket(max(take, 1), self.bucket_min, self.max_len)
        # HOL snapshot before the row takes its program row (base class
        # comment applies: these are the rows this prefill delays)
        stalled = self._stalled_rows()
        # prefill touches only positions < pre + take: the page table ships
        # clamped to that width (the shared pow2-with-floor bucket), not
        # the full worst-case reservation
        depth = -(-(pre + take) // pt)
        wa = _bucket_width(depth, self.table_pages)
        suffix = np.zeros((bucket,), np.int32)
        suffix[:take] = row.prompt[pre:pre + take]
        ptbl = np.zeros((wa,), np.int32)
        pgs = row.lease.pages[:wa]
        ptbl[:len(pgs)] = pgs
        if self.window_ring:
            # both tables: the full layers' pages and the row's ring
            ptbl = (ptbl, np.asarray(row.lease.window, np.int32))
        if kind == "admit":
            max_new, temp, topk, eos, key = (row.max_new, row.temp, row.topk,
                                             row.eos, row.key)
        else:   # max_new 1 => dead scatter, no emission
            max_new, temp, topk, eos, key = 1, 0.0, 0, -1, (0, 0)
        # ptbl, suffix, base, slens, rowids, max_news, temps, topks, eoss,
        # keys: each gets its one row here
        i32 = np.int32
        one = lambda value, dtype: jnp.asarray(np.asarray(value, dtype)[None])
        args = (tuple(one(t, i32) for t in ptbl) if self.window_ring
                else one(ptbl, i32),) + tuple(
            one(value, dtype) for value, dtype in (
                (suffix, i32), (pre, i32), (take, i32), (slot, i32),
                (max_new, i32), (temp, np.float32), (topk, i32), (eos, i32),
                (key, np.uint32)))
        span = dict(kind=kind, width=wa,
                    group=[(slot, row)] if kind == "admit" else None,
                    state_rows=1 if self.cache.recurrent else 0,
                    head_positions=1)
        # the prefill program is keyed (suffix bucket, table width) — both
        # are compile shapes on the paged engine. A draft backend's program
        # prefills the drafter's arena through the same one-row arguments
        if self.spec == "draft":
            (self._slab, self._draft_cache, packed), cold = self._run_program(
                "prefill", (bucket, wa), self._prefill_admit,
                self._variables, self._draft_variables, self._draft_cache,
                self._slab, *args, **span)
        else:
            (self._slab, packed), cold = self._run_program(
                "prefill", (bucket, wa), self._prefill_admit,
                self._variables, self._slab, *args, **span)
        # prefill accounting: only the unshared suffix is computed —
        # prefix-cached tokens are the measured FLOP saving, padding is
        # what the bucket adds to the row's own tokens, and the head takes
        # the row's one sampled position
        self.stats.admit_tokens(take, bucket - take)
        if self.stats.walks_kv_chunks:
            # the page walk's tile body: the bucket's queries from the
            # row's cursor, every attention layer
            from ..ops.paged_attention import tile_chunks

            if self.window_ring:
                # a window layer walks the bucket's own pages, and a layer
                # of either kind a K/V head a program where the heads have
                # a grid axis
                live, grid, windowed = self._tile_chunks_by_kind(
                    pre, bucket, wa)
                self.stats.tile_chunks(live + windowed[0],
                                       grid + windowed[1], windowed)
            else:
                live, grid = tile_chunks(pre, bucket, wa, pt,
                                         self.cache.itemsize)
                layers = self.cache.sublayers
                self.stats.tile_chunks(live * layers, grid * layers)
        # KV model for the prefill forward(s): gather reads the row's
        # clamped table, the kernel stops at the depth the row has reached;
        # a draft backend prefills the drafter's arena too
        span_tokens = (min(depth, wa) if self.paged_attn == "pallas"
                       else wa) * pt
        kv_bytes = span_tokens * self._kv_token_bytes
        if self.spec == "draft":
            kv_bytes += span_tokens * self._kv_draft_token_bytes
        # a window layer attends over the bucket's own keys
        kv_bytes += bucket * self._window_token_bytes
        self._admits_inflight += 1
        return (packed, kv_bytes, cold, stalled)

    def _dispatch_admit(self, slot: int, row: _Row) -> tuple:
        """Admit ONE row: prefill what is left of its prompt (all of the
        unshared suffix, or the last chunk of a chunked prefill), sample
        its first token, and give it its program row. One admission
        program per admitted request (``_run_prefill``); the loop takes no
        more rows from the queue than it has room to dispatch, so nothing
        is ever un-admitted. Returns the in-flight record."""
        tail = self._run_prefill(
            slot, row, len(row.prompt) - row.lease.prefill_pos, "admit")
        self._slot_rows[slot] = row
        self._table[slot, :] = 0
        self._table[slot, :len(row.lease.pages)] = row.lease.pages
        self._wtable[slot, :] = row.lease.window
        row.dispatched = 0
        row.pos_cap = len(row.prompt)  # device cursor lands at plen
        if not row.slot_at:
            # a chunked row took its slot (and paid queue_wait) at
            # _begin_chunked_prefill; only monolithic admits land here
            row.slot_at = time.monotonic()
            self.stats.phase("queue_wait",
                             row.slot_at - row.entry.submitted_at)
        # cache the FULL prompt blocks for future sharers. At dispatch
        # time, not admission: device programs run in dispatch order,
        # so a later match is guaranteed to read pages already written
        self._pool.register_prefix(row.prompt, row.lease)
        self.stats.admitted_wave()
        return ("admit", [(slot, row)]) + tail

    # --- chunked prefill (Sarathi-style, interleaved with decode) ---

    def _begin_chunked_prefill(self, slot: int, row: _Row) -> None:
        """Divert an admitted long-prompt row into the chunked-prefill
        ledger: it takes its program row and pages NOW (admission
        invariants unchanged — the lease was reserved worst-case), but
        its ``_table`` row stays ZEROED until the final chunk, so the
        frozen dead slab row's decode-step writes trash-redirect while
        each prefill dispatch ships the real pages in its own clamped
        table. The row keeps ``_busy()`` true via ``_slot_rows``."""
        now = time.monotonic()
        row.prefilling = True
        row.dispatched = 0
        row.pos_cap = row.lease.prefill_pos
        row.slot_at = now
        self.stats.phase("queue_wait", now - row.entry.submitted_at)
        self._slot_rows[slot] = row
        self._prefill_pending.append((slot, row))

    def _advance_prefills(self, pool, process_seq: int) -> bool:
        """One engine-loop turn of the chunked-prefill schedule: every
        pending row, oldest first, advances AT MOST one chunk per iteration
        while the run-ahead has room for its program — a row whose
        remaining suffix fits a chunk runs REAL admission (first token,
        sampling state, prefix registration: byte-identical to a
        monolithic admit at that cursor), the rest advance one intermediate
        chunk, one program a row. Decode chunks dispatch in the same
        iteration, which is the whole point: a long prompt no longer
        monopolizes the device for its full length. Returns whether
        anything was dispatched."""
        if not self._prefill_pending:
            return False
        keep: List[tuple] = []
        dispatched = False
        for slot, row in self._prefill_pending:
            if row.done or row.canceled:
                continue  # _evict_canceled owned the slot + lease
            left = len(row.prompt) - row.lease.prefill_pos
            if self._next_seq - process_seq >= self._depth:
                keep.append((slot, row))
            elif left <= self.prefill_chunk:
                rec = self._dispatch_admit(slot, row)
                # clear ``prefilling`` only AFTER the dispatch: its
                # _stalled_rows snapshot must not count a final-chunk row
                # as its own head-of-line victim
                row.prefilling = False
                row.prefill_chunks += 1
                self.stats.prefill_chunk(1, left)
                self._submit_program(pool, rec)
                dispatched = True
            else:
                self._submit_program(
                    pool, self._dispatch_prefill_chunk(slot, row))
                keep.append((slot, row))
                dispatched = True
        self._prefill_pending = keep
        return dispatched

    def _dispatch_prefill_chunk(self, slot: int, row: _Row) -> tuple:
        """One page-aligned intermediate chunk of one mid-prefill row: the
        SAME one-row program as admission (``_run_prefill``, keyed
        ("prefill", (bucket, wa))), so chunking adds no XLA program beyond
        the widths it exercises. ``max_new=1`` turns the program's
        admission scatter into a frozen dead row (live0 False, remaining
        0): the chunk writes its cap tokens of K/V into the row's own
        pages and parks; the FINAL chunk re-runs real admission with the
        row's own key/temp/topk/eos, overwriting every placeholder — which
        is why the PRNG chain and sampled tokens are bit-identical to
        monolithic prefill. Chunks are whole pages (``_chunk_cap`` floors
        at page_tokens), so each arena page — and each int8 page's
        scatter-max scale — derives from exactly one dispatch's tokens,
        monolithic or chunked. No admitted_wave / register_prefix: those
        belong to the final chunk's real admission."""
        cap = self.prefill_chunk
        tail = self._run_prefill(slot, row, cap, "pchunk")
        row.lease.prefill_pos += cap
        row.pos_cap = row.lease.prefill_pos
        row.prefill_chunks += 1
        self.stats.prefill_chunk(1, cap)
        return ("pchunk", [(slot, row)]) + tail

    # --- the decode chunk (pow2 ladder to the earliest completion) ---

    def _paged_chunk_size(self) -> int:
        # rows mid-chunked-prefill hold a program row but have no decode
        # work yet — they neither demand a chunk nor bound its size
        rem = [row.max_new - 1 - row.dispatched
               for row in self._slot_rows
               if row is not None and not row.done and not row.canceled
               and not row.prefilling
               and row.max_new - 1 - row.dispatched > 0]
        if not rem:
            return 0
        soonest = min(rem)
        size = self._chunk_sizes[0]
        for t in self._chunk_sizes:
            if t <= soonest:
                size = t
        return size

    def _live_table_width(self, extra: int) -> int:
        """Pow2-bucketed page-table width covering every resident row's
        reads AND writes for a dispatch that advances each row at most
        ``extra`` positions past its ``pos_cap`` (the host-side cursor
        upper bound). Shipping only the live width — instead of the full
        reserved ``table_pages`` — is the fallback path's cheap win (the
        gather shrinks from the worst-case reservation to what the batch
        actually occupies) and bounds the kernel's grid the same way; the
        pow2 bucket keeps the compiled-program set at log2(table_pages)
        widths. Capped per row at its lease width: positions beyond the
        reservation were trash-bound in the full-width program too (zero
        table entries), so the clamp is behavior-preserving. The bucket
        FLOORS at 8 pages (or the whole table when smaller): sub-8 widths
        barely cut bytes but each is another (chunk, width) XLA compile —
        the clamp's win lives in the deep-reservation regime (a 2048-token
        max_len is 128 pages at pt=16; a 256-token chat row stays in a
        16-32 page bucket)."""
        pt = self.page_tokens
        need = 1
        for row in self._slot_rows:
            if row is None or row.lease is None or row.prefilling:
                # a prefilling row's table row is still zeroed (its pages
                # ship per prefill dispatch) — its dead slab cursor walks
                # the trash page and must not widen the decode table
                continue
            need = max(need, min(-(-(row.pos_cap + extra) // pt),
                                 len(row.lease.pages)))
        return _bucket_width(need, self.table_pages)

    def _bump_pos_caps(self, adv: int) -> None:
        """Advance every resident row's host-side cursor upper bound after
        a dispatch: a plain chunk moves a row at most its step count, a
        spec macro-step at most k+1, and no row ever writes past its final
        position (the device clamps via remaining/live)."""
        for row in self._slot_rows:
            if (row is not None and not row.done and not row.canceled
                    and not row.prefilling):
                row.pos_cap = min(row.pos_cap + adv,
                                  len(row.prompt) + row.max_new - 1)

    def _chunk_kv_tokens(self, w: int, adv: int) -> int:
        """Host-modeled cached tokens ONE forward pass reads through a
        ``w``-page table when each row sits ``adv`` positions past its
        pre-dispatch ``pos_cap`` (the forward's deepest query): the gather
        path materializes every program row's full ``w`` pages regardless;
        the Pallas kernel stops at each resident row's live depth,
        ``ceil((pos_cap+adv)/pt)`` pages (empty program rows repeat one
        clamped page — noise the model ignores). Callers sum one span per
        forward (each chunk step / drafter iteration deepens ``adv``)."""
        pt = self.page_tokens
        if self.paged_attn != "pallas":
            return self.slots * w * pt
        total = 0
        for row in self._slot_rows:
            if row is None or row.lease is None or row.prefilling:
                continue
            total += min(-(-(row.pos_cap + adv) // pt), w) * pt
        return total

    def _walk_chunks(self, w: int, size: int) -> tuple:
        """``(live, grid)`` programs of the page walk's decode body in one
        chunk of ``size`` steps over a ``w``-page table, all attention
        layers: the kernel's grid is every program row by ``w / C`` chunks
        of ``C`` pages, and a resident row's chunks up to its depth are the
        ones with pages to read (step ``s``'s query sits ``s`` positions
        past ``pos_cap``, as in :meth:`_chunk_kv_tokens`)."""
        from ..ops.paged_attention import walk_chunk_pages

        pages = walk_chunk_pages(w)
        span = pages * self.page_tokens
        live = sum(min(-(-(row.pos_cap + s) // span), w // pages)
                   for row in self._slot_rows
                   if row is not None and row.lease is not None
                   and not row.prefilling
                   for s in range(1, size + 1))
        layers = self.cache.full_layers
        return live * layers, size * self.slots * (w // pages) * layers

    def _latent_walk_trips(self, w: int, size: int) -> tuple:
        """``(live, run, pages)`` of the latent walk's loop in one chunk of
        ``size`` steps over a ``w``-page table, all latent layers: trips a
        resident row made, trips every program row made, and the pages those
        copied (ops/mla_attention.py walk_trips, the kernel's own rules).
        Step ``s``'s query sits at ``pos_cap + s - 1``; a program row that
        is not resident has a zeroed table row, one trip over the trash
        page a step."""
        from ..ops.mla_attention import walk_trips

        at = [row.pos_cap + s for row in self._slot_rows
              if row is not None and row.lease is not None
              and not row.prefilling for s in range(size)]
        dead = size * self.slots - len(at)
        run, pages = walk_trips(w, self.page_tokens, at, dead_rows=dead)
        layers = self.cache.full_layers
        return (run - dead) * layers, run * layers, pages * layers

    def _ring_chunks(self, size: int) -> tuple:
        """The window layers' part of a chunk of ``size`` steps:
        ``((live, grid) programs of the decode body over the rows' rings,
        (live, held) ring pages)``, all window layers. A row's ring is
        ``window_ring / C`` programs (one where the ring is at most 16
        pages); those up to the pages the row has written have pages to
        read, and of the pages it holds a step's query could read the ones
        its window of keys lies in (the kernel's own clamp and mask,
        ops/paged_attention.py _decode_kernel)."""
        from ..ops.paged_attention import walk_chunk_pages

        pt, ring = self.page_tokens, self.window_ring
        n, window = self.cache.window_layers, self.cache.window
        pages = walk_chunk_pages(ring, ring=True)
        live = held = read = 0
        for row in self._slot_rows:
            if row is None or row.lease is None or row.prefilling:
                continue
            for s in range(1, size + 1):
                pos = row.pos_cap + s - 1          # the step's query
                written = min(pos // pt + 1, ring)
                live += -(-written // pages)
                held += ring
                read += pos // pt - max(pos - window + 1, 0) // pt + 1
        return ((live * n, size * self.slots * (ring // pages) * n),
                (read * n, held * n))

    def _tile_chunks_by_kind(self, pre: int, bucket: int, wa: int) -> tuple:
        """``(live, grid, (window live, window grid))`` programs of the
        tile body in one admission of a model whose attention differs by
        layer: each full layer over the row's table, each window layer over
        the bucket's own pages under its window, a K/V head a program
        wherever the kernel gives the heads a grid axis (the same rule,
        read off the same shapes: ops/paged_attention.py paged_attention)."""
        from ..ops.paged_attention import tile_chunks, tile_head_groups

        pt, itemsize = self.page_tokens, self.cache.itemsize
        heads = int(self.module.num_heads)
        out = [0, 0, 0, 0]
        for layer in self.cache.layers:
            groups = tile_head_groups(heads, layer.kv_heads, layer.k_dim,
                                      layer.v_dim, bucket, itemsize)
            if layer.window:
                live, grid = tile_chunks(
                    0, bucket, -(-bucket // pt), pt, itemsize,
                    window=layer.window, groups=groups)
                out[2] += live
                out[3] += grid
            else:
                live, grid = tile_chunks(pre, bucket, wa, pt, itemsize,
                                         groups=groups)
                out[0] += live
                out[1] += grid
        return out[0], out[1], (out[2], out[3])

    def _dispatch_chunk_paged(self, size: int) -> tuple:
        # the table ships CLAMPED to the batch's live width (see
        # _live_table_width) and as a COPY: jnp.asarray of a numpy array
        # can be zero-copy on CPU, and the host mutates self._table in
        # place the moment a row retires (often right after dispatching
        # its dying chunk) — an aliased buffer would hand the
        # still-executing program a zeroed table row and trash-redirect
        # the row's final tokens
        w = self._live_table_width(size)
        coloc = self._admits_inflight > 0
        tables = jnp.asarray(self._table[:, :w].copy())
        if self.window_ring:
            # both tables: the full layers' pages and the rows' rings
            tables = (tables, jnp.asarray(self._wtable.copy()))
        state_rows = (sum(r is not None and not r.prefilling
                          for r in self._slot_rows)
                      if self.cache.recurrent else 0)
        (self._slab, packed), cold = self._run_program(
            "step", (size, w), self._steps[size],
            self._variables, self._slab, tables,
            kind="step", steps=size, width=w, state_rows=state_rows)
        # one span per step: step s's query sits s positions past pos_cap
        kv_bytes = sum(self._chunk_kv_tokens(w, s)
                       for s in range(1, size + 1)) * self._kv_token_bytes
        if self.window_ring:
            # a window layer reads a row's window, whatever its depth
            window = self.cache.window
            kv_bytes += self._window_token_bytes * sum(
                min(row.pos_cap + s, window)
                for row in self._slot_rows
                if row is not None and row.lease is not None
                and not row.prefilling for s in range(1, size + 1))
        if self.stats.walks_kv_chunks:
            live, grid = self._walk_chunks(w, size)
            ring, ring_pages = (self._ring_chunks(size) if self.window_ring
                                else ((0, 0), (0, 0)))
            self.stats.walk_chunks(live + ring[0], grid + ring[1], ring,
                                   ring_pages)
        if self.stats.walks_latents:
            self.stats.latent_walk(*self._latent_walk_trips(w, size))
        self._bump_pos_caps(size)
        for row in self._slot_rows:
            if (row is not None and not row.done and not row.canceled
                    and not row.prefilling):
                row.dispatched += size
        # a recurrent model's state kernel reads and writes every slab row
        self.stats.chunk(state_rows=(
            size * self.slots if self.cache.recurrent else 0,
            size * state_rows))
        return ("chunk", packed, list(self._slot_rows), kv_bytes, cold,
                coloc)

    def _retire_dispatched(self) -> None:
        """Per-token admission's other half: a row whose every remaining
        emission is already in the ordered dispatch chain releases its
        program row AND its pages NOW — any reuse is dispatched after, so
        the device-order dependency makes the handoff race-free. Tokens
        still in flight route through per-dispatch snapshots; the row waits
        in ``_draining`` only for its waiter bookkeeping."""
        for slot, row in enumerate(self._slot_rows):
            if row is None or row.done or row.canceled or row.prefilling:
                # a mid-prefill row with max_new == 1 reads as fully
                # dispatched (0 >= 0) but hasn't emitted its first token —
                # its final chunk clears ``prefilling`` and retires it then
                continue
            if row.dispatched >= row.max_new - 1:
                row.drained = True
                self._slot_rows[slot] = None
                self._table[slot, :] = 0
                self._wtable[slot, :] = 0
                self._pool.release(row.lease)
                with self._cond:
                    self._draining.append(row)
                    self._free.append(slot)

    def _evict_canceled(self) -> None:
        for slot, row in enumerate(self._slot_rows):
            if row is not None and row.canceled:
                self._slab.live = self._slab.live.at[slot].set(False)
                row.done = True
                self._slot_rows[slot] = None
                self._table[slot, :] = 0
                self._wtable[slot, :] = 0
                self._pool.release(row.lease)
                with self._cond:
                    self._free.append(slot)

    def _release_row_slot(self, slot: int, row: _Row) -> None:
        if row.lease is not None:
            self._pool.release(row.lease)  # idempotent per lease
        if row.drained:
            with self._cond:
                self._draining = [r for r in self._draining if r is not row]
        else:
            self._slot_rows[slot] = None
            self._table[slot, :] = 0
            self._wtable[slot, :] = 0
            with self._cond:
                self._free.append(slot)

    def _reset_engine_state(self) -> None:
        """Fault recovery: a rebuilt slab means a ZEROED arena, so every
        cached page (and the trie over them) is invalid — fresh pool."""
        from .kvpool import KVPool

        self._pool = KVPool(self._pool.num_pages, self.page_tokens,
                            prefix_cache=self._pool.trie is not None,
                            window_pages=self.window_arena_pages,
                            window_ring=self.window_ring)
        self._table[:] = 0
        self._wtable[:] = 0

    # --- mid-stream snapshot / restore / drain (ISSUE 20) ---

    def submit_snapshot(self, frame, stream: bool = False) -> _Entry:
        """Admit a KMS1 snapshot (bytes, or a decoded
        :class:`kvsnap.RequestSnapshot`) as a first-class request: the row
        re-enters the queue carrying its emitted tokens and — once the page
        budget covers it — its pages scatter into fresh arena pages and it
        continues decoding from its saved position (greedy continuation is
        bit-identical to the uninterrupted run). A snapshot with zero
        emissions simply re-prefills from its prompt. Geometry or storage
        mismatches 409; a snapshot no arena this size could ever hold 400s;
        a snapshot that is already complete resolves immediately."""
        from . import kvsnap

        snap = (frame if isinstance(frame, kvsnap.RequestSnapshot)
                else kvsnap.decode_snapshot(frame))
        if snap.model and snap.model != self.name:
            raise KubeMLError(
                f"snapshot was taken from model {snap.model!r}, this "
                f"decoder serves {self.name!r}", 409)
        if not snap.prompt:
            raise KubeMLError("snapshot carries an empty prompt", 400)
        plen = len(snap.prompt)
        if plen + snap.max_new - 1 > self.max_len:
            raise KubeMLError(
                f"snapshot prompt ({plen}) + max_new ({snap.max_new}) - 1 "
                f"exceeds the model's max_len ({self.max_len})", 400)
        self._check_capacity(plen, snap.max_new)
        done = bool(snap.out) and (
            len(snap.out) >= snap.max_new
            or (snap.eos >= 0 and snap.out[-1] == snap.eos))
        if snap.out and not done:
            check_cache_features(self.cache, {"snapshot"})
            # mid-stream state only restores into a byte-compatible arena
            if int(snap.page_tokens) != self.page_tokens:
                raise KubeMLError(
                    f"snapshot page_tokens ({snap.page_tokens}) != engine "
                    f"page_tokens ({self.page_tokens})", 409)
            mine = "int8" if self.kv_quant == "int8" else "none"
            theirs = "int8" if snap.kv_quant == "int8" else "none"
            if mine != theirs:
                raise KubeMLError(
                    f"snapshot arena storage is {theirs!r}, engine stores "
                    f"{mine!r} (KUBEML_KV_QUANT mismatch)", 409)
            if self.spec == "draft":
                raise KubeMLError(
                    "mid-stream restore is unsupported under spec='draft' "
                    "(the drafter's separate arena is not captured); "
                    "resubmit the prompt", 409)
            mine = self.cache.layers
            if mine and len(snap.layers) != len(mine):
                raise KubeMLError(
                    f"snapshot has {len(snap.layers)} layers, model has "
                    f"{len(mine)}", 409)
            for layer, held in zip(snap.layers, mine):
                want = (self.page_tokens, held.kv_heads, held.k_dim)
                got = tuple(int(x) for x in layer.k.shape[1:])
                if got != want:
                    raise KubeMLError(
                        f"snapshot layer {layer.name!r} page shape {got} "
                        f"!= engine page shape {want}", 409)
        from ..utils import resilience, tracing

        rows: List[_Row] = []
        entry = _Entry(rows=rows, max_new=int(snap.max_new),
                       stream_q=queue.Queue() if stream else None,
                       submitted_at=time.monotonic(),
                       deadline=resilience.current_deadline(),
                       request_id=snap.request_id or self._next_request_id(),
                       trace_ctx=tracing.current_context())
        row = _Row(entry=entry, index=0,
                   prompt=np.asarray(snap.prompt, np.int32),
                   max_new=int(snap.max_new), temp=float(snap.temp),
                   topk=int(snap.topk), eos=int(snap.eos),
                   key=np.asarray(snap.key, np.uint32),
                   out=list(snap.out),
                   snapshot=snap if snap.out and not done else None)
        rows.append(row)
        with self._cond:
            if self._closed or self._retired:
                raise DecoderClosed()
            if self._drain_mode and not done:
                from ..api.errors import OverloadedError

                self.stats.overloaded()
                raise OverloadedError(
                    "decoder is draining for shutdown; replay the snapshot "
                    "elsewhere", retry_after=max(
                        1.0, self._drain_deadline - time.monotonic()))
            self.stats.submitted(1)
            if done:
                row.done = True
            else:
                # restores bypass the queue-limit shed gate: they ARE the
                # replay of work this server already accepted once
                self._pending.append(row)
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._loop, name=f"decode-{self.name}",
                        daemon=True)
                    self._thread.start()
                self._cond.notify_all()
        if stream and snap.out:
            # the consumer sees the pre-snapshot emissions as one delta so
            # the concatenated stream equals the uninterrupted stream
            entry.stream_q.put({"row": 0, "tokens": list(snap.out)})
        if done:
            if self._record_outcome(entry):
                self.stats.completed(0.0)
                self._finish_timeline(entry, "completed")
            entry.done_evt.set()
            if entry.stream_q is not None:
                entry.stream_q.put(None)
        return entry

    def _dispatch_restore(self, slot: int, row: _Row) -> None:
        """Rebuild a snapshot row in its slot: scatter the saved pages into
        the fresh lease's physical pages, then write the row's cursors —
        ``tok=out[-1]``, ``pos=plen+m-1`` (the next write position),
        ``remaining=max_new-m``, sampler key replayed m splits from the
        root — exactly the state ``_prefill_admit_impl`` + m-1 steps would
        have left. No program dispatch: the functional ``.at[].set`` updates
        thread into the slab's value-dependency chain, so ordering against
        in-flight dispatches is free."""
        from . import kvsnap

        snap = row.snapshot
        t0 = time.monotonic()
        m = len(row.out)
        plen = len(row.prompt)
        try:
            npg = snap.npages
            pages = list(row.lease.pages[:npg])
            if len(pages) < npg:
                raise kvsnap.SnapshotError(
                    f"lease holds {len(pages)} pages, snapshot needs {npg}")
            pos = plen + m - 1
            keys = (kvsnap.replay_keys(snap.key, m) if row.temp > 0
                    else np.zeros((2,), np.uint32))
            s = self._slab
            s.cache = kvsnap.scatter_pages(s.cache, pages, snap.layers)
            s.tok = s.tok.at[slot].set(int(row.out[-1]))
            s.pos = s.pos.at[slot].set(pos)
            s.live = s.live.at[slot].set(True)
            s.remaining = s.remaining.at[slot].set(row.max_new - m)
            s.keys = s.keys.at[slot].set(jnp.asarray(keys))
            s.temp = s.temp.at[slot].set(row.temp)
            s.topk = s.topk.at[slot].set(row.topk)
            s.eos = s.eos.at[slot].set(row.eos)
        except Exception as e:
            log.exception("%s: snapshot restore failed (slot %d)",
                          self.name, slot)
            self.stats.snapshot_fail()
            self._pool.release(row.lease)
            row.lease = None
            row.snapshot = None
            with self._cond:
                self._free.append(slot)
            from ..api.errors import EngineFaultError

            self._fail_entry(row.entry, EngineFaultError(
                f"snapshot restore failed: {e}",
                partial_tokens=[list(row.out)]), self.stats.failed)
            return
        self._slot_rows[slot] = row
        self._table[slot, :] = 0
        self._table[slot, :len(row.lease.pages)] = row.lease.pages
        # m-1 post-admit steps are "already dispatched" (their emissions
        # ride in out); the chunk sizer sees exactly max_new - m to go
        row.dispatched = m - 1
        row.pos_cap = pos
        row.prefilling = False
        row.snapshot = None
        now = time.monotonic()
        if not row.slot_at:
            row.slot_at = now
            self.stats.phase("queue_wait", now - row.entry.submitted_at)
        self.stats.snapshot_restore(kvsnap.snapshot_nbytes(snap), now - t0)
        tracer = self._tracer
        if tracer.enabled:
            # no program of the chain, so no seq: the scatter and the
            # cursor writes thread into the slab as they stand
            tracer.add_span("engine.dispatch", tracer.at(t0), now - t0,
                            program="restore", seq=None, steps=0,
                            width=len(row.lease.pages), cold=False,
                            rows_live=sum(r is not None
                                          for r in self._slot_rows),
                            requests=row.entry.request_id)

    def _snapshot_row(self, row: _Row) -> Optional[object]:
        """Capture one resident row's portable state (host side of KMS1):
        tokens + knobs + the written arena pages gathered through its page
        table. Returns None — after counting a snapshot failure — when the
        state cannot be captured: draft-mode rows (the drafter's separate
        arena isn't covered) and rows whose device state is poisoned by
        the fault being recovered from."""
        from . import kvsnap

        if self.spec == "draft":
            self.stats.snapshot_fail()
            return None
        try:
            check_cache_features(self.cache, {"snapshot"})
        except CacheFeatureUnsupported as refusal:
            log.warning("%s: %s (request %s)", self.name, refusal,
                        row.entry.request_id)
            self.stats.snapshot_fail()
            return None
        t0 = time.monotonic()
        try:
            m = len(row.out)
            npg = kvsnap.snapshot_pages_needed(len(row.prompt), m,
                                               self.page_tokens)
            if row.lease is None or len(row.lease.pages) < npg:
                raise kvsnap.SnapshotError("row holds no page lease")
            held = self.cache.layers[0]
            layers = (kvsnap.gather_pages(self._slab.cache,
                                          list(row.lease.pages[:npg]),
                                          held.kv_heads, held.k_dim)
                      if npg else [])
            snap = kvsnap.RequestSnapshot(
                model=self.name, request_id=row.entry.request_id,
                page_tokens=self.page_tokens,
                kv_quant="int8" if self.kv_quant == "int8" else "none",
                spec=self.spec or "off",
                prompt=[int(t) for t in row.prompt], out=list(row.out),
                max_new=row.max_new, temp=row.temp, topk=row.topk,
                eos=row.eos, key=(int(row.key[0]), int(row.key[1])),
                layers=layers)
            self.stats.snapshot_save(kvsnap.snapshot_nbytes(snap),
                                     time.monotonic() - t0)
            return snap
        except Exception:
            log.exception("%s: row snapshot failed (request %s)",
                          self.name, row.entry.request_id)
            self.stats.snapshot_fail()
            return None

    def _recover_rows(self, error: Exception) -> List[_Row]:
        """Fault recovery's salvage half: called from the engine loop's
        except seam BEFORE the arena is reinitialized, while resident rows'
        pages still hold their written history. Rows with consumed
        emissions snapshot (the restore replays them bit-exactly for
        greedy/plain-mode sampling); rows still prefilling reset to plain
        re-prefill. Whatever cannot cross the rebuild — ``_draining`` rows
        (pages already released at retire time), draft-mode rows, rows
        whose gather hits poisoned device state — fails NOW with a
        retryable 503 carrying partial tokens. Queued rows of healthy
        entries stay queued. Returns salvageable rows in admission order,
        snapshots attached."""
        from ..api.errors import EngineFaultError

        with self._cond:
            resident = [r for r in self._slot_rows if r is not None]
            draining = list(self._draining)
            self._draining = []
        doomed: Dict[int, _Entry] = {}
        for row in draining:
            if not row.done and not row.canceled:
                doomed.setdefault(id(row.entry), row.entry)
        salvaged: List[_Row] = []
        for row in resident:
            if row.done or row.canceled or id(row.entry) in doomed:
                continue
            snap = None
            if row.out:
                snap = self._snapshot_row(row)
                if snap is None:
                    doomed.setdefault(id(row.entry), row.entry)
                    continue
            row.snapshot = snap
            row.lease = None  # the pool is rebuilt; old leases are void
            row.dispatched = 0
            row.pos_cap = 0
            row.prefilling = False
            row.drained = False
            row.prefix_cached = 0
            salvaged.append(row)
        # one unsalvageable row dooms its whole entry (result() needs all
        # rows) — drop doomed entries' siblings everywhere
        salvaged = [r for r in salvaged if id(r.entry) not in doomed]
        if doomed:
            with self._cond:
                self._pending = deque(r for r in self._pending
                                      if id(r.entry) not in doomed)
            for entry in doomed.values():
                self._fail_entry(entry, EngineFaultError(
                    f"decode engine fault: {error}; request state could "
                    "not be snapshotted across the rebuild — retry",
                    partial_tokens=[list(r.out) for r in entry.rows]),
                    self.stats.failed)
        return salvaged

    def _audit_pool(self) -> None:
        """KVPool invariant watchdog tick (KUBEML_POOL_AUDIT_INTERVAL): a
        tripped ``check()`` fires the errorhook and re-raises into the
        fault-recovery seam — corrupted page accounting must trigger a
        rebuild, not decode garbage through aliased pages."""
        try:
            with self._cond:
                self._pool.check()
        except Exception as e:
            self.stats.pool_audit(False)
            log.error("%s: KVPool invariant audit FAILED: %s",
                      self.name, e)
            try:
                from ..utils.errorhook import report_error

                report_error("serving.pool_audit", f"{self.name}: {e}")
            except Exception:
                log.debug("pool-audit errorhook emission failed",
                          exc_info=True)
            raise
        else:
            self.stats.pool_audit(True)

    def drain(self, grace: float) -> List[bytes]:
        """Graceful shutdown (checkpoint-and-yield for serving): stop
        admitting (submit 429s with Retry-After), give live rows up to
        ``grace`` seconds (KUBEML_DRAIN_GRACE) to run out, then snapshot
        every straggler into a portable KMS1 frame — its waiter fails with
        a retryable 503 carrying partial tokens — and return the frames.
        The PS writes them under KUBEML_SNAP_DIR and replays them through
        :meth:`submit_snapshot` on next boot. Returns [] when everything
        finished inside the grace window."""
        deadline = time.monotonic() + max(0.0, grace)
        with self._cond:
            self._drain_mode = True
            self._drain_deadline = deadline
            active = self._thread is not None and not self._closed
            self._cond.notify_all()
        if not active:
            return []
        while time.monotonic() < deadline:
            with self._cond:
                idle = (not self._pending and not self._busy()
                        and not self._draining)
            if idle:
                return []
            time.sleep(0.05)
        req = _DrainReq()
        with self._cond:
            if self._closed:
                return []
            self._drain_req = req
            self._cond.notify_all()
        if not req.evt.wait(timeout=max(30.0, grace) + 120.0):
            log.warning("%s: drain quiesce timed out", self.name)
            return []
        return list(req.frames)

    def _drain_quiesce(self, pool, req: _DrainReq, process_seq: int) -> int:
        """Engine-thread half of :meth:`drain`: settle the dispatch chain
        (host row state must equal device truth before gathering), encode
        one KMS1 frame per straggler single-row request (zero emissions →
        a stateless frame that re-prefills on replay), fail the drained
        waiters retryably, release every lease — ``check()`` must come
        back clean — and hand the frames to the drain() caller."""
        from . import kvsnap
        from ..api.errors import EngineFaultError

        try:
            while process_seq < self._next_seq:
                process_seq = self._consume_ready(pool, process_seq, True)
        except Exception:
            log.exception("%s: drain could not settle the dispatch chain",
                          self.name)
            pool.clear()
            process_seq = self._next_seq
        with self._cond:
            resident = [r for r in self._slot_rows if r is not None]
            queued = list(self._pending)
            self._pending.clear()
            draining = list(self._draining)
            self._draining = []
        entries: Dict[int, _Entry] = {}
        for r in resident + queued + draining:
            if not r.done and not r.canceled:
                entries.setdefault(id(r.entry), r.entry)
        frames: List[bytes] = []
        for entry in entries.values():
            snap = None
            if len(entry.rows) == 1 and not entry.rows[0].drained:
                r = entry.rows[0]
                if r.out:
                    snap = self._snapshot_row(r)
                else:
                    # queued / mid-prefill: no arena state worth shipping —
                    # a stateless frame replays as a plain prefill
                    t0 = time.monotonic()
                    snap = kvsnap.RequestSnapshot(
                        model=self.name, request_id=entry.request_id,
                        page_tokens=self.page_tokens,
                        kv_quant="int8" if self.kv_quant == "int8"
                        else "none",
                        spec=self.spec or "off",
                        prompt=[int(t) for t in r.prompt], out=[],
                        max_new=r.max_new, temp=r.temp, topk=r.topk,
                        eos=r.eos, key=(int(r.key[0]), int(r.key[1])),
                        layers=[])
                    self.stats.snapshot_save(
                        kvsnap.snapshot_nbytes(snap),
                        time.monotonic() - t0)
            if snap is not None:
                try:
                    frames.append(kvsnap.encode_snapshot(snap))
                except Exception:
                    log.exception("%s: drain frame encode failed (%s)",
                                  self.name, entry.request_id)
                    self.stats.snapshot_fail()
            self._fail_entry(entry, EngineFaultError(
                "decoder drained for shutdown"
                + ("; request snapshotted for replay" if snap is not None
                   else ""),
                partial_tokens=[list(r.out) for r in entry.rows]),
                self.stats.failed)
        with self._cond:
            for r in resident:
                if r.lease is not None:
                    self._pool.release(r.lease)
                    r.lease = None
            self._slot_rows = [None] * self.slots
            self._free = list(range(self.slots))
            self._table[:] = 0
            self._prefill_pending = []
            self._admits_inflight = 0
            self._prefill_turn = True
            self._drain_req = None
            self._cond.notify_all()
        req.frames = frames
        req.evt.set()
        return process_seq

    @property
    def arena_bytes(self) -> int:
        return (self.arena_pages * self.cache.page_bytes(
            self.page_tokens, self.kv_quant)
            + self.window_arena_pages * self.cache.ring_page_bytes(
                self.page_tokens))

    def telemetry(self) -> dict:
        snap = super().telemetry()
        snap.update(self._pool.telemetry())
        # which arena read path this engine compiled (1 = Pallas kernel,
        # 0 = gather fallback) — the bench scrape's ground truth
        snap["paged_attn_kernel"] = (1.0 if self.paged_attn == "pallas"
                                     else 0.0)
        # arena storage mode (1 = int8-quantized pages, 0 = compute dtype)
        # — pairs with pages_total so the capacity doubling is chartable
        snap["kv_quant"] = 1.0 if self.kv_quant == "int8" else 0.0
        # rows currently mid-chunked-prefill (holding a slot + pages but
        # not yet decoding) — the engine-thread snapshot is racy by a loop
        # iteration, which is fine for a gauge
        snap["prefills_in_progress"] = float(len(self._prefill_pending))
        # programs the loop keeps in flight now (run_ahead_depth): under
        # pipeline_depth wherever the host answers well inside a step
        snap["run_ahead_depth"] = float(self._depth)
        # bytes of parameters resident for this model (every leaf, in the
        # type it is held in: Config.serving_param_dtype)
        snap["param_bytes"] = float(self._param_bytes)
        # traces of the model's block in this process (models/gpt.py
        # _decode_block; engines share them): about one per compiled
        # program, not one per layer of each
        snap["block_traces"] = float(block_traces())
        # sub-layers that hold a paged cache (the depth; twice that where
        # a layer is a double layer of two attentions)
        snap["cache_sublayers"] = float(self.cache.sublayers)
        # of those, the layers whose row holds a ring of pages (a window
        # layer) and the ones whose row holds a page for every position
        snap["window_layers"] = float(self.cache.window_layers)
        snap["full_layers"] = float(self.cache.full_layers)
        # streams of the model's residual path (1; hyper-connections: n)
        snap["residual_streams"] = float(self.cache.residual_streams)
        # recurrent state beside the pages: layers that keep one, its bytes
        # over all program rows, and whether it switched prefix sharing off
        snap["recurrent_layers"] = float(self.cache.state_layers)
        snap["recurrent_state_bytes"] = float(
            self.cache.state_bytes(self.slots))
        if "state_rows_moved" in snap:
            # what a row's gate carries a step and head, beside the counts
            # of the kernel that applies it: 1 (a decay a head) or the keys'
            # width (one a key channel): which delta rule the states follow
            snap["state_gate_width"] = float(self.cache.state_gate_width)
        snap["prefix_cache_off_recurrent"] = (
            1.0 if "prefix_sharing" in self._features_off else 0.0)
        # a latent arena: values one token holds in one layer, once, and
        # the lanes its row is stored in (both 0 for a model that pages K
        # and V heads); routed-expert layers and the bytes of their stacked
        # expert weights (a decode step reads the share of them its rows
        # chose: moe_experts_touched)
        latent = self.cache.latent
        snap["kv_latent_width"] = float(latent.latent_width if latent else 0)
        snap["kv_latent_row_width"] = float(
            latent.latent_row_width if latent else 0)
        snap["moe_layers"] = float(self.cache.expert_layers)
        # experts of a layer whose weights are here (all, or this chip's
        # share of them)
        snap["moe_experts_held"] = float(self.cache.experts_held)
        snap["expert_param_bytes"] = float(self._expert_param_bytes)
        if self._spec_ctl is not None:
            # current adaptive speculation depth (0 = retreated to plain
            # decode) + the controller's EWMA acceptance estimate
            snap["spec_k"] = float(self._spec_ctl.current())
            if self._spec_ctl.ratio >= 0:
                snap["spec_accept_ewma"] = float(self._spec_ctl.ratio)
            # 1 = the draft-mode acceptance floor tripped and drafting is
            # permanently off for this model (KUBEML_SPEC_MIN_ACCEPT)
            snap["spec_disabled"] = 1.0 if self._spec_ctl.disabled else 0.0
        return snap

    # --- the engine loop (paged flavor) ---

    def _loop(self) -> None:
        try:
            self._slab = self._build_slab()
        except Exception as e:
            log.exception("%s: paged slab init failed", self.name)
            with self._cond:
                self._closed = True
            self._fail_all(e)
            return
        pool = _FetchPool(self, self.fetchers)
        process_seq = 0
        while True:
            self._sweep_expired()
            # every gate of this turn reads the one depth; when it falls
            # below what is in flight the turn dispatches nothing and
            # consumes, and nothing is ever un-admitted
            self._depth = run_ahead_depth(
                self._host_s.value(), self._svc_s.value(),
                self.pipeline_depth)
            with self._cond:
                while (not self._closed and not self._pending
                       and not self._busy()
                       and process_seq == self._next_seq
                       and self._drain_req is None):
                    if self._retired:
                        self._slab = None  # free the arena's HBM
                        pool.stop()
                        return
                    self._wait_work()
                if self._closed:
                    pool.stop()
                    return
                room = self._depth - (self._next_seq - process_seq)
                self._admit_from = self._span_clock()
                admits = (self._take_admissions_locked(room)
                          if room > 0 and self._drain_req is None else [])
            try:
                req = self._drain_req
                if req is not None and not admits:
                    # graceful drain: quiesce, snapshot stragglers, hand
                    # the KMS1 frames back to the drain() caller
                    process_seq = self._drain_quiesce(pool, req, process_seq)
                    continue
                if (self.pool_audit_interval > 0
                        and time.monotonic() >= self._next_audit):
                    self._next_audit = (time.monotonic()
                                        + self.pool_audit_interval)
                    self._audit_pool()
                dispatched = False
                for slot, row in admits:
                    if row.canceled:  # canceled between admit and dispatch
                        self._pool.release(row.lease)
                        with self._cond:
                            self._free.append(slot)
                        continue
                    if row.snapshot is not None:
                        # KMS1 restore: scatter saved pages + cursors into
                        # the slab directly — no prefill program runs
                        self._dispatch_restore(slot, row)
                        dispatched = True
                        continue
                    if (self.prefill_chunk and len(row.prompt)
                            - row.lease.prefill_pos > self.prefill_chunk):
                        # long cold suffix: prefill in page-aligned chunks
                        # interleaved with decode instead of one program
                        self._begin_chunked_prefill(slot, row)
                        continue
                    # one admission program a row: ``room`` bounded the
                    # rows taken by the dispatches there is room for
                    self._submit_program(pool, self._dispatch_admit(slot, row))
                    dispatched = True
                self._evict_canceled()
                # fair interleave (ISSUE 19): when the pipeline has room
                # for only ONE dispatch and both a prefill chunk and a
                # decode chunk want it, alternate the grant — prefill-
                # first would re-create the monopoly chunking exists to
                # break (live rows starve for the whole prompt, just in
                # slices), decode-first would starve TTFT instead
                prefill_now = True
                if (self._prefill_pending
                        and self._depth
                        - (self._next_seq - process_seq) == 1
                        and self._paged_chunk_size() > 0):
                    prefill_now = self._prefill_turn
                    self._prefill_turn = not self._prefill_turn
                if prefill_now:
                    adv = self._advance_prefills(pool, process_seq)
                    dispatched = dispatched or adv
                self._retire_dispatched()
                if (self._next_seq - process_seq < self._depth
                        and (size := self._paged_chunk_size()) > 0):
                    # spec mode verifies k drafts per dispatch instead of
                    # stepping one token; the adaptive controller may have
                    # retreated (current() == 0), in which case plain
                    # chunks run and count toward the re-probe
                    spec_k_now = (self._spec_ctl.current()
                                  if self._spec_ctl is not None else 0)
                    if spec_k_now > 0:
                        self._submit_program(
                            pool, self._dispatch_spec_chunk(spec_k_now))
                    else:
                        self._submit_program(
                            pool, self._dispatch_chunk_paged(size))
                        if self._spec_ctl is not None:
                            self._spec_ctl.on_plain_chunk()
                    dispatched = True
                    # the chunk may have fully dispatched rows: free their
                    # program rows + pages for the NEXT chunk edge
                    self._retire_dispatched()
                must_wait = (
                    self._next_seq - process_seq >= self._depth
                    or (not dispatched and process_seq < self._next_seq))
                process_seq = self._consume_ready(pool, process_seq,
                                                  must_wait)
            except Exception as e:
                log.exception("%s: paged decode loop failed", self.name)
                pool.clear()
                process_seq = self._next_seq
                # snapshot-what-you-can BEFORE the arena reinitializes —
                # resident rows' pages still hold their written history;
                # unsalvageable entries fail retryably inside (ISSUE 20).
                # Queued rows of healthy entries stay queued.
                salvaged = self._recover_rows(e)
                with self._cond:
                    if self._closed:
                        pool.stop()
                        return
                    self._slot_rows = [None] * self.slots
                    self._free = list(range(self.slots))
                    self._admits_inflight = 0
                    self._prefill_pending = []
                    self._prefill_turn = True
                try:
                    self._reset_engine_state()
                    self._slab = self._build_slab()
                except Exception:
                    # rebuild failed: the engine is permanently down — the
                    # salvaged rows live nowhere _fail_all can see, so
                    # fail their entries here first
                    with self._cond:
                        self._closed = True
                    from ..api.errors import EngineFaultError

                    for entry in {id(r.entry): r.entry
                                  for r in salvaged}.values():
                        self._fail_entry(entry, EngineFaultError(
                            f"decode engine fault: {e}; rebuild failed",
                            partial_tokens=[list(r.out)
                                            for r in entry.rows]),
                            self.stats.failed)
                    self._fail_all(e, wrap=True)
                    pool.stop()
                    return
                if salvaged:
                    # replay: snapshot rows re-enter at the head of the
                    # queue (they were admitted before anything queued now)
                    with self._cond:
                        for row in reversed(salvaged):
                            self._pending.appendleft(row)
                        self._cond.notify_all()
                    self.stats.snapshot_replay(len(salvaged))
