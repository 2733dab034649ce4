"""Serving-side runtime: continuous batching for autoregressive decode.

The reference serves classifier forward passes one request at a time
(/root/reference/ml/pkg/scheduler/api.go:119-162); LM decode has no
counterpart there. On TPU a decode step streams the weights whatever the
batch, so serving one request per program execution leaves most of the chip
idle. :class:`BatchingDecoder` coalesces
concurrent requests into one slot-based batched decode loop;
:class:`PagedBatchingDecoder` (the default for capable models) replaces the
per-row ``[max_len, H, D]`` cache stripes with a paged KV arena + block
allocator (serving/kvpool.py): page-budget admission at every chunk edge
and shared-prefix reuse across requests. Speculative decoding
(KUBEML_SERVING_SPEC, serving/spec.py + the acceptance math in
models/generation.py) rides the paged engine: a drafter proposes k
tokens, the target verifies them in one forward, and rollback is a
positional paged-cache operation.
"""

from .batcher import BatchingDecoder, DecoderClosed, PagedBatchingDecoder
from .kvpool import KVPool, PageLease, PrefixTrie
from .spec import AdaptiveK

__all__ = ["BatchingDecoder", "PagedBatchingDecoder", "DecoderClosed",
           "KVPool", "PageLease", "PrefixTrie", "AdaptiveK"]
