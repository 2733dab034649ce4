"""Paged KV-cache serving engine (ISSUE 12): block allocator invariants,
prefix-trie semantics, paged-vs-dense token parity, per-token admission's
dead-step guarantee, and the cancel/shed/expire chaos exactness bar.

Correctness bars:

* TOKEN PARITY — the paged engine must be token-identical to the one-shot
  ``models.generation.generate`` path for greedy decode AND to the dense
  slot engine for seeded sampling (both engines share one per-row key-split
  chain by construction), including requests served through the shared
  prefix cache.
* ALLOCATOR EXACTNESS — after any storm of cancels, sheds, timeouts and
  deadline expiries, every page is returned exactly once: at drain the
  only held pages are the prefix trie's, and flushing the trie frees the
  whole arena. No page is ever reachable from two non-prefix-shared
  requests (``KVPool.check`` raises on any broken invariant).
* DEAD-STEP ZERO — per-token admission sizes chunks to the earliest
  completion, so a no-EOS mixed-length workload burns ZERO dead slot-steps
  (the PR-1 pre-free hack existed to approximate this; the regression test
  holds the new engine to the exact version).
"""

import threading
import time

import numpy as np
import pytest

import jax

from kubeml_tpu.api.errors import KubeMLError
from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import generate, supports_paged_decode
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.serving.batcher import BatchingDecoder, PagedBatchingDecoder
from kubeml_tpu.serving.kvpool import KVPool, PageAllocError

VOCAB = 101


def tiny(pos="learned", max_len=64):
    return CausalTransformer(vocab_size=VOCAB, max_len=max_len, embed_dim=64,
                             depth=2, num_heads=4, pos=pos)


@pytest.fixture(scope="module", params=["learned", "rope"])
def served(request):
    m = tiny(request.param)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    return m, variables


def one_shot(m, variables, prompt, n, **kw):
    out = generate(m, variables, np.asarray(prompt, np.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out.tokens), np.asarray(out.lengths)


# --- KVPool / allocator units (no device work) ---


def test_pool_alloc_release_exactness():
    pool = KVPool(17, 4, prefix_cache=False)
    assert pool.capacity == 16
    a = pool.admit(np.arange(1, 9), 8)   # 8 + 7 = 15 positions -> 4 pages
    assert a is not None and len(a.pages) == 4 and a.shared == 0
    assert 0 not in a.pages              # trash page never handed out
    b = pool.admit(np.arange(1, 5), 40)  # 4 + 39 = 43 -> 11 pages
    assert b is not None and len(b.pages) == 11
    assert not set(a.pages) & set(b.pages)
    assert pool.free_pages() == 1
    assert pool.admit(np.arange(1, 9), 8) is None  # 4 pages > 1 free
    assert pool.free_pages() == 1       # failed admit changed nothing
    pool.release(a)
    pool.release(a)                     # idempotent per lease
    assert pool.free_pages() == 5
    pool.release(b)
    assert pool.free_pages() == 16
    pool.check()


def test_pool_double_free_raises():
    pool = KVPool(5, 4, prefix_cache=False)
    lease = pool.admit(np.arange(1, 5), 1)
    pool.release(lease)
    with pytest.raises(PageAllocError):
        pool._release_one(lease.pages[0])


def test_pool_capacity_check():
    pool = KVPool(5, 4, prefix_cache=False)  # 4 usable pages = 16 positions
    assert pool.can_admit(8, 9)       # 16 positions exactly
    assert not pool.can_admit(8, 10)  # 17 positions


@pytest.mark.parametrize("step", ["admit", "around_the_ring", "release",
                                  "rings_run_out", "pages_run_out",
                                  "reserve", "double_free", "sizes"])
def test_pool_two_kinds_of_lease(step):
    """A model with window layers: a lease is the full layers' pages for
    every position AND a ring of the window arena's pages, whatever the
    depth; both or neither, and ``check`` accounts for both kinds after
    every move."""
    pool = KVPool(33, 4, prefix_cache=False, window_pages=9, window_ring=4)
    a = pool.admit(np.arange(1, 9), 8)        # 15 positions -> 4 pages
    assert len(a.pages) == 4 and len(a.window) == 4
    assert 0 not in a.window                  # the window arena's trash page
    if step == "admit":
        assert pool.check() == {"free": 28, "held": 4, "trie_pages": 0,
                                "refs_total": 4, "window_free": 4,
                                "window_held": 4}
        tel = pool.telemetry()
        assert (tel["window_pages_total"], tel["window_pages_free"],
                tel["window_ring_pages"]) == (8.0, 4.0, 4.0)
    elif step == "around_the_ring":
        # the page of position p in a window layer is slot (p // 4) mod 4
        # of the lease's ring: a row that advances re-uses its own pages
        # and the lease never grows
        b = pool.admit(np.arange(1, 5), 100)  # 103 positions -> 26 pages
        assert len(b.pages) == 26 and len(b.window) == 4
        slots = [b.window[(p // 4) % 4] for p in range(103)]
        assert set(slots) == set(b.window) and slots[0] == slots[16]
        assert not set(a.window) & set(b.window)
        assert pool.check()["window_held"] == 8
    elif step == "release":
        pool.release(a)
        pool.release(a)                       # idempotent per lease
        assert pool.check() == {"free": 32, "held": 0, "trie_pages": 0,
                                "refs_total": 0, "window_free": 8,
                                "window_held": 0}
    elif step == "rings_run_out":
        b = pool.admit(np.arange(1, 5), 2)
        assert pool.admit(np.arange(1, 5), 2) is None   # no third ring
        assert pool.free_pages() == 32 - 4 - 2          # nothing taken
        pool.release(b)
        assert pool.admit(np.arange(1, 5), 2) is not None
        pool.check()
    elif step == "pages_run_out":
        assert pool.admit(np.arange(1, 5), 200) is None  # 51 pages > 28
        assert pool.check()["window_held"] == 4          # its ring came back
    elif step == "reserve":
        b = pool.reserve(10)
        assert len(b.pages) == 3 and len(b.window) == 4
        assert pool.reserve(10) is None and pool.check()["held"] == 7
    elif step == "double_free":
        pool.release(a)
        with pytest.raises(PageAllocError, match="window page"):
            pool._free_ring(a.window)
    else:
        assert pool.can_admit(8, 9)
        with pytest.raises(ValueError, match="prefix"):
            KVPool(33, 4, prefix_cache=True, window_pages=9, window_ring=4)
        with pytest.raises(ValueError, match="ring"):
            KVPool(33, 4, prefix_cache=False, window_pages=4, window_ring=4)
        with pytest.raises(ValueError, match="ring"):
            KVPool(33, 4, prefix_cache=False, window_pages=9)
        # one kind: what the pool always was
        plain = KVPool(5, 4, prefix_cache=False)
        assert plain.admit(np.arange(1, 5), 1).window == []
        assert plain.check()["window_free"] == 0


def test_prefix_trie_match_insert_and_sharing():
    pool = KVPool(33, 4)
    prompt = np.arange(1, 14)  # 13 tokens: 3 full blocks + 1
    a = pool.admit(prompt, 4)
    assert a.shared == 0
    pool.register_prefix(prompt, a)
    assert pool.trie.nodes == 3
    # identical prompt: all 3 full blocks shared (cap (13-1)//4 = 3)
    b = pool.admit(prompt, 4)
    assert b.shared == 3 and b.prefix_tokens == 12
    assert b.pages[:3] == a.pages[:3]
    # same 2-block header, different tail: partial chain match
    c_prompt = np.concatenate([prompt[:8], [77, 78, 79]])
    c = pool.admit(c_prompt, 4)
    assert c.shared == 2 and c.pages[:2] == a.pages[:2]
    # a page-aligned prompt never shares its LAST block (>=1 token of
    # suffix must remain for the first sampled token's logits)
    d = pool.admit(prompt[:8], 4)
    assert d.shared == 1
    for lease in (a, b, c, d):
        pool.release(lease)
    chk = pool.check()
    assert chk["held"] == chk["trie_pages"] == 3
    assert pool.trie.flush() == 3
    assert pool.free_pages() == pool.capacity
    pool.check()


def test_trie_eviction_leaf_first_and_only_unreferenced():
    pool = KVPool(9, 4)  # 8 usable
    p1 = np.arange(1, 9)        # 2 full blocks
    a = pool.admit(p1, 1)       # 2 pages
    pool.register_prefix(p1, a)
    b = pool.admit(np.arange(20, 28), 1)  # 2 pages
    pool.register_prefix(np.arange(20, 28), b)
    pool.release(b)             # b's blocks now trie-only
    # a still holds its lease: its trie pages are NOT evictable, b's are
    big = pool.admit(np.arange(50, 54), 20)  # 4+19=23 -> 6 pages; 4 free
    assert big is not None
    assert pool.evictions >= 2  # b's chain evicted to cover the shortfall
    assert set(a.pages) & set(p for p in pool.trie.pages()) == set(a.pages[:2])
    pool.release(a)
    pool.release(big)
    pool.check()


def test_pool_rejects_bad_page_tokens():
    with pytest.raises(ValueError):
        KVPool(8, 3)
    with pytest.raises(ValueError):
        KVPool(1, 4)


# --- engine parity ---


def test_paged_greedy_matches_one_shot_mixed_lengths(served):
    """Mixed prompt lengths and generation lengths through few program rows
    exercise per-token admission, retire-at-dispatch and page churn — every
    row must stay token-identical to the one-shot path."""
    m, variables = served
    dec = PagedBatchingDecoder(m, variables, slots=3, chunk_steps=8,
                               page_tokens=4)
    try:
        rng = np.random.default_rng(0)
        lens = [3, 9, 5, 12, 7, 4, 10, 6, 15, 8]
        max_news = [6, 12, 3, 1, 9, 17, 5, 8, 2, 11]
        prompts = [rng.integers(1, VOCAB, size=(1, l)).astype(np.int32)
                   for l in lens]
        refs = [one_shot(m, variables, p, n)[0][0].tolist()
                for p, n in zip(prompts, max_news)]
        entries = [dec.submit(GenerateRequest(prompts=p.tolist(),
                                              max_new_tokens=n))
                   for p, n in zip(prompts, max_news)]
        for e, ref in zip(entries, refs):
            assert dec.wait(e, timeout=600)["tokens"][0] == ref
        t = dec.telemetry()
        # the partition identity holds under the paged engine's capacity
        assert (t["live_slot_steps"] + t["dead_slot_steps"]
                + t["idle_slot_steps"]) == t["slot_steps"]
        # at drain only the prefix trie holds pages
        chk = dec._pool.check()
        assert chk["held"] == chk["trie_pages"]
    finally:
        dec.close()


def test_paged_seeded_sampling_matches_slot_engine(served):
    """Acceptance (c): same sampled tokens at a fixed seed, slot vs paged —
    the engines share one per-row key-split chain by construction."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    req = dict(prompts=p.tolist(), max_new_tokens=9, temperature=0.8,
               top_k=7, seed=42)
    outs = []
    for cls, kw in ((BatchingDecoder, {}),
                    (PagedBatchingDecoder, {"page_tokens": 4})):
        dec = cls(m, variables, slots=2, chunk_steps=4, **kw)
        try:
            outs.append(dec.wait(dec.submit(GenerateRequest(**req)),
                                 timeout=600))
        finally:
            dec.close()
    assert outs[0]["tokens"] == outs[1]["tokens"]
    assert outs[0]["lengths"] == outs[1]["lengths"]


def test_paged_eos_and_single_token(served):
    m, variables = served
    p = np.arange(2, 10, dtype=np.int32)[None]
    ref, _ = one_shot(m, variables, p, 8)
    eos = int(ref[0, 2])
    ref_eos, ref_len = one_shot(m, variables, p, 8, eos_id=eos)
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=8,
                               page_tokens=4)
    try:
        out = dec.wait(dec.submit(GenerateRequest(
            prompts=p.tolist(), max_new_tokens=8, eos_id=eos)), timeout=600)
        assert out["tokens"][0] == ref_eos[0].tolist()
        assert out["lengths"] == [int(ref_len[0])]
        one = dec.wait(dec.submit(GenerateRequest(
            prompts=p.tolist(), max_new_tokens=1)), timeout=600)
        assert one["tokens"][0] == ref[0][:1].tolist()
        assert one["lengths"] == [1]
    finally:
        dec.close()


# --- shared-prefix reuse ---


def test_prefix_reuse_payload_and_parity(served):
    """A second request sharing a long system prompt reuses the cached
    blocks: the payload reports prefix_cached_tokens, prefill runs only on
    the suffix (stats), and the tokens stay one-shot-identical."""
    m, variables = served
    rng = np.random.default_rng(5)
    sysp = rng.integers(1, VOCAB, size=12).astype(np.int32)
    p1 = np.concatenate([sysp, rng.integers(1, VOCAB, size=5).astype(np.int32)])
    p2 = np.concatenate([sysp, rng.integers(1, VOCAB, size=3).astype(np.int32)])
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4)
    try:
        r1 = dec.wait(dec.submit(GenerateRequest(prompts=[p1.tolist()],
                                                 max_new_tokens=6)),
                      timeout=600)
        assert r1["prefix_cached_tokens"] == 0
        r2 = dec.wait(dec.submit(GenerateRequest(prompts=[p2.tolist()],
                                                 max_new_tokens=6)),
                      timeout=600)
        assert r2["prefix_cached_tokens"] == 12  # 3 full pages of 4
        assert r2["tokens"][0] == one_shot(m, variables, p2[None], 6)[0][0].tolist()
        snap = dec.stats.snapshot()
        assert snap["prefix_hits"] == 1.0
        assert snap["prefix_tokens_saved"] == 12.0
        # prefill accounting: the second request computed only its suffix
        assert snap["prefill_tokens"] == len(p1) + (len(p2) - 12)
        t = dec.telemetry()
        assert t["prefix_cache_pages"] >= 3
    finally:
        dec.close()


def test_prefix_cache_off_still_parities(served):
    m, variables = served
    p = np.arange(1, 17, dtype=np.int32)[None]
    ref, _ = one_shot(m, variables, p, 5)
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, prefix_cache=False)
    try:
        for _ in range(2):
            out = dec.wait(dec.submit(GenerateRequest(
                prompts=p.tolist(), max_new_tokens=5)), timeout=600)
            assert out["tokens"][0] == ref[0].tolist()
            assert out["prefix_cached_tokens"] == 0
        assert dec.stats.snapshot()["prefix_hits"] == 0.0
        # nothing retained at drain with the trie off
        assert dec._pool.check()["held"] == 0
    finally:
        dec.close()


# --- per-token admission: the dead-step regression (satellite 1) ---


def test_dead_steps_zero_on_mixed_length_workload(served):
    """The PR-1 pre-free hack existed because finished rows burned dead
    steps until the host noticed. Per-token admission retires the hack:
    chunks end exactly at the earliest completion, so a no-EOS workload
    must burn ZERO dead slot-steps (occupancy_dead_total ~ 0)."""
    m, variables = served
    dec = PagedBatchingDecoder(m, variables, slots=4, chunk_steps=16,
                               page_tokens=4, pipeline_depth=4)
    try:
        rng = np.random.default_rng(2)
        entries = []
        for i in range(12):
            p = rng.integers(1, VOCAB, size=(1, int(rng.integers(3, 20))))
            entries.append(dec.submit(GenerateRequest(
                prompts=p.astype(np.int32).tolist(),
                max_new_tokens=int(rng.integers(2, 30)))))
        for e in entries:
            dec.wait(e, timeout=600)
        t = dec.telemetry()
        assert t["dead_slot_steps"] == 0.0
        assert (t["live_slot_steps"] + t["idle_slot_steps"]
                == t["slot_steps"])
    finally:
        dec.close()


# --- page-budget admission ---


def test_page_budget_queues_then_completes(served):
    """A pool too small for the whole workload serializes admission (the
    head of the line waits for pages) but every request still completes,
    token-identical."""
    m, variables = served
    # 18 usable pages of 4: one 30-token-deep request uses ~8
    dec = PagedBatchingDecoder(m, variables, slots=4, chunk_steps=8,
                               page_tokens=4, pages=19)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, VOCAB, size=(1, 9)).astype(np.int32)
                   for _ in range(6)]
        refs = [one_shot(m, variables, p, 22)[0][0].tolist() for p in prompts]
        entries = [dec.submit(GenerateRequest(prompts=p.tolist(),
                                              max_new_tokens=22))
                   for p in prompts]
        for e, ref in zip(entries, refs):
            assert dec.wait(e, timeout=600)["tokens"][0] == ref
    finally:
        dec.close()


def test_request_larger_than_arena_is_400(served):
    m, variables = served
    dec = PagedBatchingDecoder(m, variables, slots=2, chunk_steps=4,
                               page_tokens=4, pages=5)  # 4 usable pages
    try:
        with pytest.raises(KubeMLError) as ei:
            dec.submit(GenerateRequest(prompts=[[1, 2, 3]],
                                       max_new_tokens=30))
        assert ei.value.status_code == 400
        assert "KV pages" in str(ei.value)
    finally:
        dec.close()


def test_paged_int8_matches_dense_int8_engine():
    """Weight-only int8 composes with paging (the arena is cache state,
    not weights): the paged int8 decoder is token-identical to the dense
    int8 slot engine on the same request."""
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    p = np.arange(1, 10, dtype=np.int32)[None]
    req = dict(prompts=p.tolist(), max_new_tokens=6)
    outs = []
    for cls, kw in ((BatchingDecoder, {}),
                    (PagedBatchingDecoder, {"page_tokens": 4})):
        dec = cls(m, variables, slots=2, chunk_steps=4, quantize="int8", **kw)
        try:
            outs.append(dec.wait(dec.submit(GenerateRequest(**req)),
                                 timeout=600))
        finally:
            dec.close()
    assert outs[0]["tokens"] == outs[1]["tokens"]


def test_unsupported_module_refused():
    moe = CausalTransformer(vocab_size=VOCAB, max_len=32, embed_dim=64,
                            depth=2, num_heads=4, moe_every=2)
    assert not supports_paged_decode(moe)
    variables = moe.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    with pytest.raises(Exception):
        PagedBatchingDecoder(moe, variables, slots=2)


# --- allocator invariants under chaos (satellite 3) ---


@pytest.mark.paged
def test_allocator_exactness_under_cancel_timeout_shed_chaos(served):
    """Seeded randomized storm: concurrent submitters, waiter timeouts,
    explicit cancels, queue-limit sheds and queued-deadline expiries. At
    drain the free list and refcounts must balance exactly — every page
    returned once, the trie the only holder, a trie flush freeing the
    whole arena."""
    from kubeml_tpu.utils import resilience

    m, variables = served
    dec = PagedBatchingDecoder(m, variables, slots=3, chunk_steps=8,
                               page_tokens=4, pages=41,
                               queue_limit=6, shed_policy="oldest")
    rng = np.random.default_rng(1234)
    sysp = rng.integers(1, VOCAB, size=8).astype(np.int32)
    errors = []

    def client(i):
        r = np.random.default_rng(1000 + i)
        try:
            for _ in range(3):
                if r.random() < 0.4:
                    prompt = np.concatenate(
                        [sysp, r.integers(1, VOCAB, size=int(r.integers(2, 6)))])
                else:
                    prompt = r.integers(1, VOCAB, size=int(r.integers(3, 14)))
                req = GenerateRequest(
                    prompts=[prompt.astype(np.int32).tolist()],
                    max_new_tokens=int(r.integers(2, 24)),
                    temperature=0.7 if r.random() < 0.3 else 0.0,
                    seed=int(r.integers(1, 1 << 30)))
                roll = r.random()
                try:
                    if roll < 0.2:
                        # deadline likely already expired while queued
                        with resilience.bind_deadline(time.time() + 0.01):
                            e = dec.submit(req)
                        dec.wait(e, timeout=30)
                    elif roll < 0.45:
                        e = dec.submit(req)
                        dec.wait(e, timeout=0.01)  # waiter gives up fast
                    elif roll < 0.6:
                        e = dec.submit(req)
                        time.sleep(float(r.random()) * 0.05)
                        dec.cancel(e)
                    else:
                        e = dec.submit(req)
                        dec.wait(e, timeout=600)
                except KubeMLError:
                    pass  # 429/504s are the point of the storm
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        assert not errors
        # wait for the engine to fully drain (canceled work finishing)
        deadline = time.time() + 60
        while time.time() < deadline:
            with dec._cond:
                idle = (not dec._pending and not dec._busy()
                        and not dec._draining)
            if idle:
                break
            time.sleep(0.05)
        assert idle, "engine did not drain"
        chk = dec._pool.check()  # raises on leak / double-free / overlap
        assert chk["held"] == chk["trie_pages"]
        # refcounts balance exactly: flushing the trie frees everything
        dec._pool.trie.flush()
        assert dec._pool.free_pages() == dec._pool.capacity
        dec._pool.check()
        # no slot leaked either
        with dec._cond:
            assert sorted(dec._free) == [0, 1, 2]
            assert all(r is None for r in dec._slot_rows)
    finally:
        dec.close()


@pytest.mark.slow
@pytest.mark.paged
def test_allocator_chaos_storm_chunked_prefill():
    """The chaos storm re-run with KUBEML_PREFILL_CHUNK_TOKENS=8 and long
    prompts (16-40 tokens, some prefix-shared): cancels, timeouts and
    deadline expiries now land BETWEEN a row's prefill chunks — while its
    pages are reserved and partially written but the row is device-dead.
    The exactness bar is unchanged: every page returned once, the trie
    the only holder at drain, no slot leaked, the prefill ledger empty."""
    from kubeml_tpu.utils import resilience

    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = PagedBatchingDecoder(m, variables, slots=3, chunk_steps=8,
                               page_tokens=4, pages=61, queue_limit=6,
                               shed_policy="oldest",
                               prefill_chunk_tokens=8)
    rng = np.random.default_rng(1919)
    sysp = rng.integers(1, VOCAB, size=16).astype(np.int32)
    errors = []

    def client(i):
        r = np.random.default_rng(2000 + i)
        try:
            for _ in range(3):
                if r.random() < 0.4:
                    prompt = np.concatenate(
                        [sysp,
                         r.integers(1, VOCAB, size=int(r.integers(4, 20)))])
                else:
                    prompt = r.integers(1, VOCAB, size=int(r.integers(16, 41)))
                req = GenerateRequest(
                    prompts=[prompt.astype(np.int32).tolist()],
                    max_new_tokens=int(r.integers(2, 24)),
                    temperature=0.7 if r.random() < 0.3 else 0.0,
                    seed=int(r.integers(1, 1 << 30)))
                roll = r.random()
                try:
                    if roll < 0.2:
                        # deadline likely expires while queued or mid-chunk
                        with resilience.bind_deadline(time.time() + 0.01):
                            e = dec.submit(req)
                        dec.wait(e, timeout=30)
                    elif roll < 0.45:
                        e = dec.submit(req)
                        dec.wait(e, timeout=0.01)  # waiter gives up fast
                    elif roll < 0.6:
                        e = dec.submit(req)
                        # sleeps sized to the multi-chunk prefill window so
                        # cancels hit rows in every prefill_pos state
                        time.sleep(float(r.random()) * 0.1)
                        dec.cancel(e)
                    else:
                        e = dec.submit(req)
                        dec.wait(e, timeout=600)
                except KubeMLError:
                    pass  # 429/504s are the point of the storm
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        assert not errors
        deadline = time.time() + 60
        while time.time() < deadline:
            with dec._cond:
                idle = (not dec._pending and not dec._busy()
                        and not dec._draining)
            if idle:
                break
            time.sleep(0.05)
        assert idle, "engine did not drain"
        assert dec._prefill_pending == []
        chk = dec._pool.check()  # raises on leak / double-free / overlap
        assert chk["held"] == chk["trie_pages"]
        dec._pool.trie.flush()
        assert dec._pool.free_pages() == dec._pool.capacity
        dec._pool.check()
        with dec._cond:
            assert sorted(dec._free) == [0, 1, 2]
            assert all(r is None for r in dec._slot_rows)
    finally:
        dec.close()


# --- stats: partition identity under variable capacity (satellite 6) ---


def test_chunk_occupancy_capacity_generalization():
    from kubeml_tpu.serving.stats import DecoderStats

    s = DecoderStats(slots=4)
    s.chunk_occupancy(8, live=24, dead=4, idle=4)            # slots default
    s.chunk_occupancy(4, live=20, dead=2, idle=10, capacity=8)  # wider chunk
    s.chunk_occupancy(2, live=2, dead=0, idle=0, capacity=1)    # narrower
    snap = s.snapshot()
    assert snap["slot_steps"] == 8 * 4 + 4 * 8 + 2 * 1
    assert (snap["live_slot_steps"] + snap["dead_slot_steps"]
            + snap["idle_slot_steps"]) == snap["slot_steps"]
    hist = snap["hist"]["occupancy_ratio"]
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(24 / 32 + 20 / 32 + 2 / 2)


# --- PS integration: engine selection + payload field ---


PAGED_FN = """
import optax
from kubeml_tpu.runtime.model import KubeModel
from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gpt import CausalTransformer

class Tokens(KubeDataset):
    def __init__(self):
        super().__init__("tokens")

class Model(KubeModel):
    def __init__(self):
        super().__init__(Tokens())
    def build(self):
        return CausalTransformer(vocab_size=64, max_len=32, embed_dim=32,
                                 depth=2, num_heads=4)
    def configure_optimizers(self):
        return optax.adamw(self.lr)
"""


def _finished_job(tmp_path, **config):
    """A finished job "pagedjob" of the function "pagedfn" under
    ``tmp_path``: (config, registry)."""
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore

    cfg = Config(data_root=tmp_path, serving_slots=2, serving_chunk_steps=4,
                 serving_page_tokens=4, **config)
    cfg.ensure_dirs()
    module = CausalTransformer(vocab_size=64, max_len=32, embed_dim=32,
                               depth=2, num_heads=4)
    variables = module.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    import flax.linen as nn

    variables = jax.tree.map(np.asarray, nn.meta.unbox(variables))
    reg = FunctionRegistry(config=cfg)
    reg.create("pagedfn", PAGED_FN)
    CheckpointStore(config=cfg).save(
        "pagedjob", variables, epoch=1, tag=FINAL_TAG,
        meta={"request": {"function_name": "pagedfn"}})
    return cfg, reg


@pytest.mark.paged
def test_ps_serves_finished_checkpoint_through_paged_engine(tmp_path):
    """The PS picks the paged engine for capable models
    (KUBEML_SERVING_PAGED default) and the /generate payload carries
    prefix_cached_tokens; with the knob off it builds the dense engine."""
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.ps.parameter_server import ParameterServer

    cfg, reg = _finished_job(tmp_path)
    ps = ParameterServer(registry=reg, config=cfg)
    out = ps.generate("pagedjob", GenerateRequest(
        prompts=[[1, 2, 3, 4, 5, 6, 7, 8]], max_new_tokens=4))
    assert "prefix_cached_tokens" in out
    dec = ps._decoders["pagedjob"][0]
    assert isinstance(dec, PagedBatchingDecoder)
    # same prompt again: the shared blocks come from the trie
    out2 = ps.generate("pagedjob", GenerateRequest(
        prompts=[[1, 2, 3, 4, 5, 6, 7, 8]], max_new_tokens=4))
    assert out2["prefix_cached_tokens"] == 4  # one full page of 4
    assert out2["tokens"] == out["tokens"]

    cfg_off = Config(data_root=tmp_path, serving_slots=2,
                     serving_chunk_steps=4, serving_paged=False)
    ps2 = ParameterServer(registry=FunctionRegistry(config=cfg_off),
                          config=cfg_off)
    ps2.generate("pagedjob", GenerateRequest(prompts=[[1, 2, 3]],
                                             max_new_tokens=2))
    dec2 = ps2._decoders["pagedjob"][0]
    assert isinstance(dec2, BatchingDecoder)
    assert not isinstance(dec2, PagedBatchingDecoder)


@pytest.mark.paged
def test_a_fresh_replicas_first_request_is_one_tree(tmp_path):
    """Where a replica's start goes (ISSUE 39): the first /generate on a
    finished job loads it under the request's server span (restore, hold,
    decoder, then the request itself, one trace) and the decoder's stats
    take the seconds; a second request loads nothing."""
    from kubeml_tpu.ps.parameter_server import ParameterServer
    from kubeml_tpu.utils import tracing

    cfg, reg = _finished_job(tmp_path, serving_param_dtype="bfloat16")
    ps = ParameterServer(registry=reg, config=cfg)
    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enabled = True
    load = ("ps.serving.restore", "ps.serving.hold", "ps.serving.decoder")
    try:
        trees = []
        for _ in range(2):
            # utils.httpd records this span around the route's handler
            with tracer.span("ps POST /generate/pagedjob", service="ps") as s:
                out = ps.generate("pagedjob", GenerateRequest(
                    prompts=[[1, 2, 3, 4, 5, 6, 7, 8]], max_new_tokens=4))
            under = [c for c in tracer.spans() if c.parent_id == s.span_id]
            assert {c.trace_id for c in under} == {s.trace_id}
            (req,) = [c for c in under if c.name == "serving.request"]
            assert req.attrs["job"] == out["request_id"]
            trees.append((s, [c for c in under if c.name in load]))
        stats = ps._decoders["pagedjob"][0].telemetry()
    finally:
        tracer.enabled = was_on
        tracer.clear()
        for dec, _ in ps._decoders.values():
            dec.close()
    (first, loaded), (_, again) = trees
    assert [c.name for c in loaded] == list(load) and again == []
    restore, hold, decoder = loaded
    assert restore.start + restore.duration <= hold.start <= decoder.start
    assert decoder.start + decoder.duration <= first.start + first.duration
    assert restore.attrs["job"] == "pagedjob"
    assert restore.attrs["kind"] == "flat" and restore.attrs["leaves"] > 0
    # stored wide, held narrow: the hold's casts compiled on this thread
    assert (hold.attrs["from"], hold.attrs["to"]) == ("float32", "bfloat16")
    assert restore.attrs["bytes"] == 2 * hold.attrs["bytes"] > 0
    assert hold.attrs["programs"] >= 1 and hold.attrs["backend_s"] > 0.0
    assert decoder.attrs["slots"] == 2 and decoder.attrs["pages"] == 17
    assert decoder.attrs["arena_bytes"] > 0
    for span, key in zip(loaded, ("restore", "hold", "decoder")):
        assert stats[f"startup_{key}_seconds"] == pytest.approx(
            span.duration, abs=5e-3)
    assert stats["startup_slab_seconds"] > 0.0
    # the engine programs' sums hold nothing of the hold's casts
    assert stats["compile_wall_seconds"] >= (
        stats["compile_trace_seconds"] + stats["compile_lower_seconds"]
        + stats["compile_backend_seconds"]) > 0.0
