"""Autoregressive generation for the causal-LM family (KV-cache decode).

The reference platform serves classifier inference only (`/infer` returns one
forward pass — /root/reference/ml/pkg/scheduler/api.go:119-162); sampling from
a language model has no counterpart there. This is the TPU-native serving path
for the ``CausalTransformer`` family (incl. imported HF GPT-2 checkpoints,
kubeml_tpu.interop): per-layer K/V caches live in a flax ``cache`` collection
with STATIC shapes ``[B, max_len, H, D]``, writes go through
``dynamic_update_slice`` at a runtime cursor, and the whole
prefill-then-sample loop is ONE jitted program — the per-token loop is a
``lax.scan``, so XLA compiles exactly two executables (prefill + step chain)
regardless of how many tokens are generated.

Design notes (why it looks this way on TPU):
- Static shapes everywhere: ``max_new_tokens`` is a trace-time constant and
  rows that hit EOS keep "generating" pad tokens under a done-mask instead of
  exiting the loop — data-dependent loop exits would force a recompile per
  length (or a ``while_loop`` that defeats scan pipelining).
- The cache cursor is a runtime scalar, so serving many prompts of different
  lengths reuses one executable per (batch, prompt_len, max_new_tokens) shape
  bucket.
- Sampling (greedy / temperature / top-k) happens on-device inside the scan;
  the host sees only the final ``[B, max_new_tokens]`` array.

Usage::

    from kubeml_tpu.models import GPTSmall
    from kubeml_tpu.models.generation import generate

    module = GPTSmall()
    variables = module.init(jax.random.PRNGKey(0), prompt)  # or a checkpoint
    out = generate(module, variables, prompt, max_new_tokens=64,
                   temperature=0.8, top_k=40, eos_id=2,
                   rng=jax.random.PRNGKey(7))
    out.tokens   # [B, max_new_tokens] int32, pad after EOS
    out.lengths  # [B] generated length incl. the EOS token
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .cache_spec import CacheSpec, cache_spec
from .gpt import PAD_ID


class GenerationInputError(ValueError):
    """A USER-input problem in a generation request (bad shapes, capacity
    overflow, missing rng for sampling). The wire layer maps exactly this
    type to HTTP 400 — any other ValueError out of the pipeline is a genuine
    server fault and stays a 500."""


class GenerateResult(NamedTuple):
    tokens: jnp.ndarray   # [B, max_new_tokens] int32; PAD_ID after a row's EOS
    lengths: jnp.ndarray  # [B] int32 — tokens generated incl. EOS (or the cap)


class SpecGenerateResult(NamedTuple):
    """A speculative run's result + its acceptance accounting."""

    tokens: jnp.ndarray    # [B, max_new_tokens] int32; PAD_ID padded
    lengths: jnp.ndarray   # [B] int32
    proposed: int          # candidate tokens verified (k + 1 per live row/step)
    accepted: int          # drafted tokens that passed acceptance
    drafted: int           # tokens the drafter sampled (k per live row/step)
    steps: int             # verify macro-steps executed


def init_cache(module, variables, batch: int) -> dict:
    """A zeroed KV-cache pytree for ``batch`` rows (cursor at 0).

    Shapes come from ``jax.eval_shape`` over a one-token decode apply, so no
    device work happens and the dummy token is never written anywhere."""
    dummy = jnp.zeros((batch, 1), jnp.int32)

    def shape_fn(vs):
        return module.apply(vs, dummy, decode=True, mutable=["cache"])

    # variables go through eval_shape AS AN ARGUMENT (not a closure) so
    # callers may pass an abstract ShapeDtypeStruct tree — the quantized
    # decode path sizes its cache without materializing dense weights
    _, vars_out = jax.eval_shape(shape_fn, variables)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        vars_out["cache"])


def init_paged_cache(module, variables, batch: int, table_pages: int,
                     cache: Optional[CacheSpec] = None) -> dict:
    """A zeroed PAGED KV-cache pytree: per-layer physical page arenas of
    token rows ``[kv_pages, page_tokens, W]`` (K‖V; a latent model's
    ``latent_pages``; the module carries ``kv_pages`` /
    ``page_tokens`` — the serving layer clones them in) addressed through
    per-row page tables. Shapes come from ``jax.eval_shape`` over a
    one-token paged decode apply, so no device work happens; like
    :func:`init_cache`, ``variables`` may be an abstract tree (the
    quantized path sizes the arena without materializing dense weights).
    The arena shape is independent of ``batch`` — prefill programs of any
    row count share the same cache tree. ``cache``: the module's
    :func:`~kubeml_tpu.models.cache_spec.cache_spec`, from a caller that
    holds it already."""
    dummy = jnp.zeros((batch, 1), jnp.int32)
    pos = jnp.zeros((batch,), jnp.int32)
    pages = jnp.zeros((batch, table_pages), jnp.int32)
    ring = (cache or cache_spec(module)).ring_pages(module.page_tokens)
    if ring:
        # window layers' arenas (``window_pages`` pages) are addressed
        # through a ring a row beside the full layers' table
        pages = (pages, jnp.zeros((batch, ring), jnp.int32))

    def shape_fn(vs):
        return module.apply(vs, dummy, decode=True, positions=pos,
                            pages=pages, mutable=["cache"])

    _, vars_out = jax.eval_shape(shape_fn, variables)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        vars_out["cache"])


def supports_paged_decode(module) -> bool:
    """Whether ``module`` can serve through the paged KV-cache engine:
    it must expose the ``pages``/``seq_lens`` decode kwargs and an
    admission's ``head_positions``, plus the clonable
    ``page_tokens``/``kv_pages`` arena fields, and not interleave
    ``moe_every`` blocks (parallel/moe.py's training-side block: its
    attention has no paged path). A block's own kinds all serve: latent
    attention (``mla``) and routed experts behind dense layers
    (``mlp="experts"``) among them."""
    import inspect

    if getattr(module, "moe_every", 0):
        return False
    if not (hasattr(module, "page_tokens") and hasattr(module, "kv_pages")):
        return False
    try:
        params = inspect.signature(module.__call__).parameters
    except (TypeError, ValueError):
        return False
    return all(name in params for name in (
        "pages", "seq_lens", "positions", "head_positions"))


def _sample(logits, rng, temperature: float, top_k: Optional[int]):
    """One next-token draw per row from [B, V] logits (f32)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.float32(temperature)
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]  # [B, 1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Speculative decoding (Leviathan et al. 2023; Chen et al. 2023): a cheap
# drafter proposes k tokens, the target verifies all k+1 positions in ONE
# forward, and the canonical rejection-sampling rule keeps the emitted
# stream EXACTLY target-distributed (greedy: bit-identical to the baseline
# argmax chain). The traced helpers below are shared by the one-shot
# ``make_speculative_generate_fn`` and the serving engine's spec mode
# (serving/batcher.py) so the acceptance math exists exactly once.
# ---------------------------------------------------------------------------

# static width of the on-device top-k scratch for runtime per-row knobs —
# mirrors serving.batcher.TOP_K_MAX (the wire cap); kept here so the
# acceptance math has no serving-layer import
SPEC_TOP_K_CAP = 128

_SPEC_NEG_INF = jnp.finfo(jnp.float32).min

# fold_in indices the acceptance draws consume — far outside the
# small-integer per-draft-position folds callers use on the same keys
_ACCEPT_FOLD = 7919
_CORRECTION_FOLD = 7927


def _masked_scaled(logits, temp, topk, topk_cap: int = SPEC_TOP_K_CAP):
    """Per-row knob-adjusted logits: temperature scaling + top-k truncation
    with RUNTIME knobs. logits [S, V] f32, temp [S] (<=0 rows produce junk
    the greedy branch discards), topk [S] i32 (0 = off)."""
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    kwide = min(topk_cap, V)
    vals = jax.lax.top_k(scaled, kwide)[0]  # [S, kwide] sorted desc
    kth = jnp.take_along_axis(
        vals, jnp.clip(topk - 1, 0, kwide - 1)[:, None], axis=1)  # [S, 1]
    return jnp.where((topk > 0)[:, None] & (scaled < kth),
                     _SPEC_NEG_INF, scaled)


def _knob_probs(logits, temp, topk, topk_cap: int = SPEC_TOP_K_CAP):
    """The actual per-row SAMPLING DISTRIBUTION under runtime knobs —
    softmax over the temperature-scaled, top-k-truncated logits. This is
    the p (target) and q (drafter) the acceptance rule compares, so it must
    match what a categorical draw over ``_masked_scaled`` samples from
    (it does: softmax is shift-invariant, categorical is softmax-implicit)."""
    return jax.nn.softmax(_masked_scaled(logits, temp, topk, topk_cap),
                          axis=-1)


def draft_sample(logits, temp, topk, keys, topk_cap: int = SPEC_TOP_K_CAP):
    """One drafter draw per row with runtime knobs: greedy rows take the
    argmax, sampled rows draw categorically. Returns ``(tokens [S],
    probs [S, V])`` — probs is the drafter's knob-adjusted distribution q,
    recorded for the acceptance test (greedy rows' probs are unused: their
    acceptance is exact argmax equality)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _masked_scaled(logits, temp, topk, topk_cap)
    drawn = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    toks = jnp.where(temp <= 0.0, greedy, drawn)
    return toks, _knob_probs(logits, temp, topk, topk_cap)


def spec_accept(tgt_logits, draft_tokens, draft_probs, temp, topk, keys,
                topk_cap: int = SPEC_TOP_K_CAP):
    """The distribution-preserving acceptance rule, vectorized per row.

    ``tgt_logits`` [S, k+1, V] f32 — the verify forward's logits at the
    k+1 positions (position i is the distribution AFTER feeding draft i-1;
    position 0 follows the row's current token). ``draft_tokens`` [S, k],
    ``draft_probs`` [S, k, V] (the drafter's q at each position), ``temp``
    [S], ``topk`` [S], ``keys`` [S, 2] — fresh per-row use-keys; draws
    consume ``fold_in(key, _ACCEPT_FOLD)`` (uniforms) and
    ``fold_in(key, _CORRECTION_FOLD)`` (the correction categorical) —
    indices far outside the small-integer range callers use for their
    per-draft-position folds, so no stream is ever reused.

    Greedy rows (temp <= 0): draft i accepted iff it IS the target argmax
    at position i — the emitted stream is bit-identical to the baseline
    argmax chain. Sampled rows: accept draft d_i with prob
    min(1, p_i(d_i) / q_i(d_i)); at the first rejection resample from the
    normalized residual max(p - q, 0) (the exact Leviathan correction);
    if all k drafts survive, the bonus token samples from p_k. Returns
    ``(emit [S, k+1] — accepted drafts then the correction/bonus, -1
    past it; n_acc [S] — accepted draft count in [0, k])``."""
    S, k1, V = tgt_logits.shape
    k = k1 - 1
    greedy_row = temp <= 0.0
    tgt_arg = jnp.argmax(tgt_logits, axis=-1).astype(jnp.int32)  # [S, k+1]
    p = jax.vmap(lambda lg: _knob_probs(lg, temp, topk, topk_cap),
                 in_axes=1, out_axes=1)(tgt_logits)  # [S, k+1, V]
    if k > 0:
        p_d = jnp.take_along_axis(
            p[:, :k], draft_tokens[..., None], axis=-1)[..., 0]  # [S, k]
        q_d = jnp.take_along_axis(
            draft_probs, draft_tokens[..., None], axis=-1)[..., 0]
        u = jax.vmap(lambda kk: jax.random.uniform(
            jax.random.fold_in(kk, _ACCEPT_FOLD), (k,)))(keys)  # [S, k]
        # u < min(1, p/q)  <=>  u * q < p  (u < 1, so p >= q always accepts)
        acc = jnp.where(greedy_row[:, None],
                        tgt_arg[:, :k] == draft_tokens,
                        u * q_d < p_d)
        # leading-run length: drafts past the first rejection never count
        n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
    else:
        n_acc = jnp.zeros((S,), jnp.int32)
    # the correction/bonus position: first rejected draft index, or k
    j = n_acc[:, None, None]
    p_j = jnp.take_along_axis(p, jnp.broadcast_to(j, (S, 1, V)),
                              axis=1)[:, 0]  # [S, V]
    if k > 0:
        jq = jnp.minimum(n_acc, k - 1)[:, None, None]
        q_j = jnp.take_along_axis(draft_probs,
                                  jnp.broadcast_to(jq, (S, 1, V)),
                                  axis=1)[:, 0]
        resid = jnp.maximum(p_j - q_j, 0.0)
        rs = resid.sum(-1, keepdims=True)
        # a rejection with an (numerically) empty residual means p ~= q —
        # the acceptance probability was ~1, so sampling p is the limit
        resid = jnp.where(rs > 1e-9, resid / jnp.maximum(rs, 1e-30), p_j)
        corr_dist = jnp.where((n_acc < k)[:, None], resid, p_j)
    else:
        corr_dist = p_j
    corr_keys = jax.vmap(
        lambda kk: jax.random.fold_in(kk, _CORRECTION_FOLD))(keys)
    drawn = jax.vmap(jax.random.categorical)(
        corr_keys, jnp.log(jnp.maximum(corr_dist, 1e-30))).astype(jnp.int32)
    corr_greedy = jnp.take_along_axis(tgt_arg, n_acc[:, None],
                                      axis=1)[:, 0]
    correction = jnp.where(greedy_row, corr_greedy, drawn)
    idx = jnp.arange(k + 1)[None, :]
    if k > 0:
        drafts_wide = jnp.pad(draft_tokens, ((0, 0), (0, 1)))  # [S, k+1]
    else:
        drafts_wide = jnp.zeros((S, 1), jnp.int32)
    emit = jnp.where(idx < n_acc[:, None], drafts_wide, -1)
    emit = jnp.where(idx == n_acc[:, None], correction[:, None], emit)
    return emit, n_acc


def spec_mask_emissions(emit, n_acc, live, remaining, eos, tok):
    """Clip one macro-step's raw emissions to what the row may actually
    emit — the device-side mirror of the host routing rules, so packed
    blocks never carry a token the host would have to un-route:

    * only live rows emit; a row emits at most ``remaining`` tokens;
    * emissions stop AFTER the first ``eos`` (the eos itself counts,
      matching the baseline step loop and the engine's routing).

    Returns ``(out [S, k+1] with -1 past the clip, n_take [S], live2 [S],
    rem2 [S], feed [S] — the next token to feed, frozen for dead rows)``."""
    S, k1 = emit.shape
    idx = jnp.arange(k1)[None, :]
    valid = (idx <= n_acc[:, None]) & (idx < remaining[:, None]) \
        & live[:, None]
    is_eos = (eos >= 0)[:, None] & (emit == eos[:, None]) & valid
    eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) \
        - is_eos.astype(jnp.int32)
    valid = valid & (eos_before == 0)
    n_take = valid.sum(axis=1).astype(jnp.int32)
    out = jnp.where(valid, emit, -1)
    hit_eos = (is_eos & valid).any(axis=1)
    rem2 = remaining - n_take
    live2 = live & ~hit_eos & (rem2 > 0)
    last = jnp.take_along_axis(
        out, jnp.clip(n_take - 1, 0, k1 - 1)[:, None], axis=1)[:, 0]
    feed = jnp.where(live & (n_take > 0), last, tok)
    return out, n_take, live2, rem2, feed


def make_generate_fn(module, *, max_new_tokens: int, temperature: float = 0.0,
                     top_k: Optional[int] = None, eos_id: Optional[int] = None):
    """The jitted ``(variables, prompt_ids, rng) -> GenerateResult`` callable
    behind ``generate``. Build once and reuse across calls — the sampling
    knobs are trace-time constants, so each knob combination is its own
    program (``generate`` keeps a cache of these keyed by knobs)."""

    @jax.jit
    def run(variables, prompt_ids, rng):
        B, Lp = prompt_ids.shape
        cap = getattr(module, "max_len", None)
        if cap is None:
            # without a declared capacity the overflow guard below can't run,
            # and dynamic_update_slice would clamp writes at the cache end and
            # silently corrupt every token past it — refuse instead
            raise GenerationInputError(
                "model exposes no max_len attribute; generation requires a "
                "declared KV-cache capacity (CausalTransformer sets it)")
        # the LAST sampled token is returned but never written back, so the
        # cache needs Lp + max_new_tokens - 1 slots
        if Lp + max_new_tokens - 1 > cap:
            # shapes are trace-time constants, so this is a clean Python error
            # instead of dynamic_update_slice silently clamping at the cache
            # end and corrupting every token past capacity
            raise GenerationInputError(
                f"prompt ({Lp}) + max_new_tokens ({max_new_tokens}) - 1 "
                f"exceeds the model's max_len ({cap})")
        cache = init_cache(module, variables, B)

        # prefill: the whole prompt in one decode call (cursor 0 -> Lp)
        logits, vs = module.apply({**variables, "cache": cache}, prompt_ids,
                                  decode=True, mutable=["cache"])
        cache = vs["cache"]
        rng, r0 = jax.random.split(rng)
        first = _sample(logits[:, -1], r0, temperature, top_k)  # [B]
        done0 = jnp.zeros((B,), bool) if eos_id is None else first == eos_id

        def step(carry, r):
            cache, tok, done = carry
            logits, vs = module.apply(
                {**variables, "cache": cache}, tok[:, None],
                decode=True, mutable=["cache"])
            nxt = _sample(logits[:, -1], r, temperature, top_k)
            was_live = ~done
            if eos_id is not None:
                done = done | (was_live & (nxt == eos_id))
            # dead rows keep feeding their last token (any real id keeps the
            # cache well-formed); their OUTPUT slot is PAD below. Live rows
            # may legitimately emit id 0 — that's a vocab token, which is why
            # lengths come from the live mask, not from comparing against PAD
            feed = jnp.where(was_live, nxt, tok)
            out = jnp.where(was_live, nxt, PAD_ID)
            return (vs["cache"], feed, done), (out, was_live)

        if max_new_tokens > 1:
            _, (rest, live) = jax.lax.scan(
                step, (cache, first, done0),
                jax.random.split(rng, max_new_tokens - 1))
        else:
            rest = jnp.zeros((0, B), jnp.int32)
            live = jnp.zeros((0, B), bool)
        tokens = jnp.concatenate([first[None], rest], axis=0).T  # [B, N]
        # the first token is always live; each later slot counts if its row
        # was still generating when it was produced
        lengths = 1 + live.sum(axis=0).astype(jnp.int32)
        return GenerateResult(tokens, lengths)

    return run


def make_speculative_generate_fn(module, *, max_new_tokens: int,
                                 spec: str = "self", spec_k: int = 4,
                                 draft_module=None,
                                 exit_layer: Optional[int] = None,
                                 temperature: float = 0.0,
                                 top_k: Optional[int] = None,
                                 eos_id: Optional[int] = None,
                                 page_tokens: int = 16):
    """Speculative decoding for the one-shot path: a ``(variables,
    prompt_ids, rng, draft_variables=None) -> SpecGenerateResult`` callable.

    Two drafter backends:

    * ``spec="draft"`` — a separate small causal LM (``draft_module`` +
      the call-time ``draft_variables``, e.g. loaded from its own
      checkpoint) proposes ``spec_k`` tokens per step through its own
      paged KV cache;
    * ``spec="self"`` — self-drafting: logits from a TRUNCATED layer stack
      of the target (``exit_layer`` blocks + ln_f + lm_head — no second
      model). The drafter shares the target's paged arena: it writes
      layers < exit_layer, and the verify forward re-writes those
      positions with identical bytes while filling the rest.

    Per step the target verifies all k+1 positions in ONE forward (the
    paged L>1 suffix path), ``spec_accept`` applies the canonical
    rejection rule, and rollback is positional: a rejected suffix is
    simply overwritten by the next step's k+1-wide write window. Greedy
    (``temperature == 0``) emits BIT-IDENTICAL tokens to the baseline
    ``generate``; sampled decode preserves the target distribution exactly
    (accept min(1, p/q), resample the residual).

    Unlike ``make_generate_fn`` this is a host loop over one jitted
    macro-step (the step count is data-dependent — that is the point:
    fewer weight streams per emitted token), so each call syncs once per
    macro-step. Serving traffic goes through the engine's spec mode
    instead (``KUBEML_SERVING_SPEC``)."""
    if spec not in ("self", "draft"):
        raise ValueError(f"unknown spec backend {spec!r} "
                         f"(valid: 'self', 'draft')")
    if spec_k < 1:
        raise ValueError("spec_k must be >= 1")
    if not supports_paged_decode(module):
        raise GenerationInputError(
            "speculative decoding runs on the paged decode path; the module "
            "has none (pages/seq_lens kwargs + page_tokens/kv_pages fields)")
    if spec == "draft":
        if draft_module is None:
            raise ValueError("spec='draft' needs a draft_module")
        if not supports_paged_decode(draft_module):
            raise GenerationInputError("draft module has no paged decode path")
        if getattr(draft_module, "vocab_size", None) != \
                getattr(module, "vocab_size", None):
            raise GenerationInputError(
                "draft and target models must share one vocabulary")
    depth = getattr(module, "depth", None)
    if spec == "self":
        exit_layer = int(exit_layer) if exit_layer else max(1, (depth or 2) // 2)
        if depth is not None and not (1 <= exit_layer <= depth):
            raise ValueError(
                f"exit_layer must be in [1, depth={depth}], got {exit_layer}")
    cap = getattr(module, "max_len", None)
    if cap is None:
        raise GenerationInputError(
            "model exposes no max_len attribute; generation requires a "
            "declared KV-cache capacity")
    pt = int(page_tokens)
    k = int(spec_k)
    if temperature <= 0.0:
        top_k = None  # greedy ignores top_k (normalized like generate)
    # per-(B, Lp) compiled pieces: the cloned modules depend on the page
    # table geometry, which depends on the call shapes
    programs: dict = {}

    def build(B: int, Lp: int):
        total = min(Lp + max_new_tokens - 1 + k, int(cap))
        tp = -(-total // pt)
        npages = B * tp + 1  # page 0 reserved as trash
        cloned = module.clone(page_tokens=pt, kv_pages=npages)
        dcloned = (draft_module.clone(page_tokens=pt, kv_pages=npages)
                   if spec == "draft" else None)
        table = jnp.asarray(
            [[1 + r * tp + j for j in range(tp)] for r in range(B)],
            jnp.int32)

        def drafter_apply(dvars, dcache, tok, pos, live):
            kw = {"exit_layer": exit_layer} if spec == "self" else {}
            mod = cloned if spec == "self" else dcloned
            lg, vs = mod.apply(
                {**dvars, "cache": dcache}, tok[:, None], decode=True,
                positions=pos, pages=table,
                seq_lens=jnp.where(live, 1, 0), mutable=["cache"], **kw)
            return lg[:, -1].astype(jnp.float32), vs["cache"]

        @jax.jit
        def prefill(variables, draft_variables, prompt_ids, rng):
            cache = init_paged_cache(cloned, variables, B, tp)
            zeros = jnp.zeros((B,), jnp.int32)
            plens = jnp.full((B,), Lp, jnp.int32)
            logits, vs = cloned.apply(
                {**variables, "cache": cache}, prompt_ids, decode=True,
                positions=zeros, pages=table, seq_lens=plens,
                mutable=["cache"])
            cache = vs["cache"]
            if spec == "draft":
                dcache = init_paged_cache(dcloned, draft_variables, B, tp)
                _, dvs = dcloned.apply(
                    {**draft_variables, "cache": dcache}, prompt_ids,
                    decode=True, positions=zeros, pages=table,
                    seq_lens=plens, mutable=["cache"])
                dcache = dvs["cache"]
            else:
                dcache = None
            rng, r0 = jax.random.split(rng)
            first = _sample(logits[:, -1], r0, temperature, top_k)
            done0 = (jnp.zeros((B,), bool) if eos_id is None
                     else first == eos_id)
            live = jnp.full((B,), max_new_tokens > 1) & ~done0
            rem = jnp.full((B,), max_new_tokens - 1, jnp.int32)
            return (cache, dcache, first, plens, live, rem, rng)

        @jax.jit
        def step(variables, draft_variables, carry):
            cache, dcache, tok, pos, live, rem, rng = carry
            rng, use = jax.random.split(rng)
            row_keys = jax.vmap(
                lambda b: jax.random.fold_in(use, b))(jnp.arange(B))
            temps = jnp.full((B,), float(temperature), jnp.float32)
            topks = jnp.full((B,), int(top_k or 0), jnp.int32)
            eoss = jnp.full((B,), eos_id if eos_id is not None else -1,
                            jnp.int32)
            dvars = draft_variables if spec == "draft" else variables
            dc0 = dcache if spec == "draft" else cache

            def dr(c2, i):
                dc, t, p = c2
                lg, dc = drafter_apply(dvars, dc, t, p, live)
                dk = jax.vmap(jax.random.fold_in)(
                    row_keys, jnp.full((B,), i))
                d_i, q_i = draft_sample(lg, temps, topks, dk)
                return (dc, d_i, p + 1), (d_i, q_i)

            # draft mode runs ONE extra write-only iteration: the k-th
            # draft is fed to the verify pass but the drafter's own cache
            # must also hold its K/V, or a fully-accepted step leaves a
            # permanent zero-KV gap at that position and every later draft
            # distribution degrades. Self mode skips it — the verify
            # forward re-writes the shared arena wholesale.
            iters = k + 1 if spec == "draft" else k
            (dc_out, _, _), (d, q_probs) = jax.lax.scan(
                dr, (dc0, tok, pos), jnp.arange(iters))
            drafts = d.T[:, :k]  # [B, k]
            q_probs = jnp.moveaxis(q_probs, 0, 1)[:, :k]  # [B, k, V]
            vcache = dc_out if spec == "self" else cache
            vt = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B, k+1]
            vlg, vs = cloned.apply(
                {**variables, "cache": vcache}, vt, decode=True,
                positions=pos, pages=table,
                seq_lens=jnp.where(live, k + 1, 0), mutable=["cache"])
            cache2 = vs["cache"]
            dcache2 = dc_out if spec == "draft" else None
            emit, n_acc = spec_accept(vlg.astype(jnp.float32), drafts,
                                      q_probs, temps, topks, row_keys)
            out, n_take, live2, rem2, feed = spec_mask_emissions(
                emit, n_acc, live, rem, eoss, tok)
            pos2 = jnp.where(live, pos + n_take, pos)
            stats = jnp.stack([
                jnp.where(live, k, 0).sum(),
                jnp.where(live, n_acc, 0).sum(),
            ])
            return (cache2, dcache2, feed, pos2, live2, rem2, rng), out, stats

        return prefill, step

    def run(variables, prompt_ids, rng=None,
            draft_variables=None) -> SpecGenerateResult:
        import numpy as np

        if temperature > 0.0 and rng is None:
            raise GenerationInputError(
                "temperature > 0 requires an explicit rng (PRNGKey)")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        if spec == "draft" and draft_variables is None:
            raise GenerationInputError("spec='draft' needs draft_variables")
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        B, Lp = prompt_ids.shape
        if Lp + max_new_tokens - 1 > cap:
            raise GenerationInputError(
                f"prompt ({Lp}) + max_new_tokens ({max_new_tokens}) - 1 "
                f"exceeds the model's max_len ({cap})")
        dcap = (getattr(draft_module, "max_len", None)
                if spec == "draft" else cap)
        if dcap is not None and Lp + max_new_tokens - 1 > dcap:
            raise GenerationInputError(
                f"draft model's max_len ({dcap}) cannot cover the request")
        key = (B, Lp)
        if key not in programs:
            programs[key] = build(B, Lp)
        prefill, step = programs[key]
        carry = prefill(variables, draft_variables, prompt_ids, rng)
        outs = [[int(np.asarray(carry[2])[b])] for b in range(B)]
        proposed = accepted = drafted = steps = 0
        live = np.asarray(carry[4])
        while live.any() and steps < max_new_tokens:
            carry, packed, stats = step(variables, draft_variables, carry)
            packed = np.asarray(packed)  # [B, k+1]; -1 past the clip
            n_live = int(live.sum())
            for b in range(B):
                for t in packed[b]:
                    if t < 0:
                        break
                    outs[b].append(int(t))
            d, a = (int(v) for v in np.asarray(stats))
            drafted += d
            accepted += a
            proposed += d + n_live  # + the bonus position per live row
            steps += 1
            live = np.asarray(carry[4])
        lengths = jnp.asarray([len(o) for o in outs], jnp.int32)
        tokens = jnp.asarray(
            [o + [PAD_ID] * (max_new_tokens - len(o)) for o in outs],
            jnp.int32)
        return SpecGenerateResult(tokens, lengths, proposed, accepted,
                                  drafted, steps)

    return run


# LRU of (module, knobs) -> jitted fn. Keyed by the module itself when
# hashable (flax modules are frozen dataclasses, so equal configs share one
# program even across fresh instances); falls back to id() for modules with
# unhashable fields, holding the module ref so the id can't be recycled.
# Lock-guarded: the PS serves /generate from a threaded HTTP server, and a
# hit must never mutate the dict in a way that makes a concurrent identical
# request miss (a miss costs a ~20-27s jit compile on chip).
_GENERATE_CACHE: OrderedDict = OrderedDict()
_GENERATE_CACHE_MAX = 16
_GENERATE_CACHE_LOCK = threading.Lock()


def _cache_key(module, knobs):
    try:
        hash(module)
        return (module, *knobs)
    except TypeError:
        return (id(module), *knobs)


def generate(module, variables, prompt_ids, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             eos_id: Optional[int] = None,
             rng: Optional[jax.Array] = None,
             spec: str = "", spec_k: int = 4,
             draft_module=None, draft_variables=None,
             spec_exit_layer: Optional[int] = None) -> GenerateResult:
    """Sample ``max_new_tokens`` continuations of ``prompt_ids`` [B, Lp].

    Greedy when ``temperature == 0`` (default); ``temperature > 0`` REQUIRES
    an explicit ``rng`` (a silent default key would return the identical
    "sample" on every call). ``top_k`` truncates before the draw. Rows that
    emit ``eos_id`` keep their cache warm but output ``PAD_ID`` from then
    on; ``lengths`` counts actually-generated tokens (a live row may emit
    vocab id 0 — e.g. "!" in GPT-2 — so trust ``lengths``, not a PAD scan).
    Prompts must be dense: decode mode treats every input token as real.
    ``prompt_len + max_new_tokens - 1`` must fit the model's ``max_len``
    (the last sampled token is returned without a cache write).
    Compiles once per (knobs, shapes): repeat calls hit the cached
    program. For a long-lived serving loop, hold your own
    ``make_generate_fn`` result instead.

    ``spec`` ("self" | "draft") routes through speculative decoding
    (``make_speculative_generate_fn``); the drafter IDENTITY and depth are
    part of the jit-cache key — toggling spec modes, changing ``spec_k`` /
    ``spec_exit_layer``, or swapping draft modules can never serve a stale
    compiled program (draft WEIGHTS are call arguments, draft architecture
    is the keyed identity).
    """
    if temperature > 0.0 and rng is None:
        raise GenerationInputError(
            "temperature > 0 requires an explicit rng (PRNGKey) — otherwise "
            "every call returns the same draw")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if temperature <= 0.0:
        top_k = None  # greedy ignores top_k — normalizing the key keeps
        # byte-identical programs from compiling (and caching) twice
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    # the drafter's identity rides the cache key: the draft module itself
    # when hashable (equal configs share a program), else its id — and the
    # cache entry holds the ref so the id can't be recycled
    if spec:
        try:
            hash(draft_module)
            draft_id = draft_module
        except TypeError:
            draft_id = id(draft_module)
        spec_knobs = (spec, int(spec_k), spec_exit_layer, draft_id)
    else:
        spec_knobs = ("", 0, None, None)
    key = _cache_key(module, (max_new_tokens, float(temperature), top_k,
                              eos_id, *spec_knobs))
    with _GENERATE_CACHE_LOCK:
        entry = _GENERATE_CACHE.get(key)  # hit: non-destructive recency bump
        if entry is not None:
            _GENERATE_CACHE.move_to_end(key)
    if entry is None:
        # build outside the lock (the jit wrapper is cheap; compilation is
        # lazy at call time); setdefault keeps one winner under a race
        if spec:
            fn = make_speculative_generate_fn(
                module, max_new_tokens=max_new_tokens, spec=spec,
                spec_k=spec_k, draft_module=draft_module,
                exit_layer=spec_exit_layer, temperature=temperature,
                top_k=top_k, eos_id=eos_id)
        else:
            fn = make_generate_fn(module, max_new_tokens=max_new_tokens,
                                  temperature=temperature, top_k=top_k,
                                  eos_id=eos_id)
        with _GENERATE_CACHE_LOCK:
            # the value holds the module refs too: for the id()-keyed
            # fallback the ids must not be recycled while the entry lives
            entry = _GENERATE_CACHE.setdefault(key, (module, fn, draft_module))
            _GENERATE_CACHE.move_to_end(key)
            while len(_GENERATE_CACHE) > _GENERATE_CACHE_MAX:
                _GENERATE_CACHE.popitem(last=False)  # least recent
    if spec:
        out = entry[1](variables, prompt_ids, rng, draft_variables)
        return GenerateResult(out.tokens, out.lengths)
    return entry[1](variables, prompt_ids, rng)


def generate_from_request(module, variables, req) -> dict:
    """Serve an ``api.types.GenerateRequest`` — the wire-level entry shared by
    the PS ``/generate`` route and the live job engines. Returns
    ``{"tokens": [[...]], "lengths": [...]}``; user-shape problems (a module
    with no decode path, bad prompt shapes, capacity overflow) surface as
    KubeMLError 400, never a 500."""
    import numpy as np

    from ..api.errors import KubeMLError

    prompts = np.asarray(req.prompts)
    if prompts.ndim != 2 or not np.issubdtype(prompts.dtype, np.integer):
        raise KubeMLError(
            "prompts must be a [batch, prompt_len] integer token array", 400)
    # probe decode support EXPLICITLY (signature, not a TypeError net around
    # the whole pipeline — that would relabel genuine server bugs as 400s)
    import inspect

    try:
        supports_decode = "decode" in inspect.signature(module.__call__).parameters
    except (TypeError, ValueError):
        supports_decode = False
    if not supports_decode:
        raise KubeMLError(
            "model does not support KV-cache decode (generation needs a "
            "causal LM like CausalTransformer)", 400)
    lengths = req.prompt_lengths
    if lengths is not None and any(int(v) != prompts.shape[1] for v in lengths):
        # ragged batch: decode each row at its true length, grouped by length
        # so equal-length rows share one program (the LRU caches per shape).
        # The continuous batcher (kubeml_tpu.serving) serves ragged batches in
        # one program; this is the one-shot fallback's correct-but-simple form.
        return _generate_ragged(module, variables, prompts, req)
    try:
        rng = (jax.random.PRNGKey(req.seed) if req.seed is not None
               else None)  # greedy path; sampling enforces a seed upstream
        out = generate(module, variables, prompts.astype(np.int32),
                       max_new_tokens=req.max_new_tokens,
                       temperature=req.temperature, top_k=req.top_k,
                       eos_id=req.eos_id, rng=rng)
    except GenerationInputError as e:
        # ONLY the deliberate user-input guards (cache capacity, missing
        # max_len, rng-for-sampling); any other ValueError is a server fault
        raise KubeMLError(str(e), 400)
    return {"tokens": np.asarray(out.tokens).tolist(),
            "lengths": np.asarray(out.lengths).tolist()}


def _generate_ragged(module, variables, prompts, req) -> dict:
    """One-shot serving of a ragged batch: rows grouped by true length, one
    ``generate`` call per group, results re-assembled in row order."""
    import numpy as np

    from ..api.errors import KubeMLError

    B = prompts.shape[0]
    by_len: dict = {}
    for i, plen in enumerate(int(v) for v in req.prompt_lengths):
        by_len.setdefault(plen, []).append(i)
    tokens: list = [None] * B
    lengths: list = [None] * B
    try:
        for plen, rows in sorted(by_len.items()):
            sub = prompts[rows, :plen].astype(np.int32)
            rng = (jax.random.PRNGKey(req.seed) if req.seed is not None else None)
            if rng is not None:
                rng = jax.random.fold_in(rng, plen)  # distinct draws per group
            out = generate(module, variables, sub,
                           max_new_tokens=req.max_new_tokens,
                           temperature=req.temperature, top_k=req.top_k,
                           eos_id=req.eos_id, rng=rng)
            toks = np.asarray(out.tokens).tolist()
            lens = np.asarray(out.lengths).tolist()
            for j, row in enumerate(rows):
                tokens[row] = toks[j]
                lengths[row] = lens[j]
    except GenerationInputError as e:
        raise KubeMLError(str(e), 400)
    return {"tokens": tokens, "lengths": lengths}
