"""Mid-stream serving recovery (ISSUE 20): portable KV snapshots (KMS1),
request migration across decoders, fault-recovery replay, and graceful
drain.

Correctness bars:

* MIGRATION PARITY — a request snapshotted mid-stream by one decoder and
  restored into a FRESH decoder (new arena, new page pool) must finish
  with the greedy token stream bit-identical to the uninterrupted run.
* REPLAY, NOT SHED — an engine fault mid-decode snapshots resident rows
  before the arena rebuild and replays them through admission; the waiter
  sees a normal completion, not an error. Whatever cannot be snapshotted
  fails FAST with a retryable 503 carrying the partial tokens (never a
  done_evt hang — the PR-20 regression).
* ALLOCATOR EXACTNESS ACROSS FAULTS — after any storm of faults, drains
  and restores, ``KVPool.check()`` comes back clean and no page leaks.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeml_tpu.api.errors import (EngineFaultError, KubeMLError,
                                   OverloadedError)
from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import generate
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.serving import kvsnap
from kubeml_tpu.serving.batcher import BatchingDecoder, PagedBatchingDecoder

VOCAB = 101


def tiny(max_len=64):
    return CausalTransformer(vocab_size=VOCAB, max_len=max_len,
                             embed_dim=64, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def served():
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    return m, variables


def one_shot(m, variables, prompt, n, **kw):
    out = generate(m, variables, np.asarray(prompt, np.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out.tokens), np.asarray(out.lengths)


def paged(m, variables, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("page_tokens", 4)
    kw.setdefault("name", "tinymodel")
    return PagedBatchingDecoder(m, variables, **kw)


def first_token(dec, entry):
    """Block until the entry's row 0 has at least one consumed emission."""
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if entry.rows[0].out or entry.done_evt.is_set():
            return
        time.sleep(0.01)
    raise AssertionError("no token emitted within 120s")


def arm_fault(dec, exc=None):
    """Poison the next paged chunk dispatch once (the engine-loop fault
    seam); subsequent dispatches run normally on the rebuilt engine."""
    orig = dec._dispatch_chunk_paged
    state = {"armed": True}

    def boom(size):
        if state["armed"]:
            state["armed"] = False
            raise exc or RuntimeError("injected device fault")
        return orig(size)

    dec._dispatch_chunk_paged = boom
    return state


# --- KMS1 codec units (no device work) ---


def synth_snap(out=(7, 8, 9), kv_quant="none", layers=2, npages=None,
               page_tokens=4, key=(1, 2)):
    rng = np.random.default_rng(0)
    prompt = list(range(1, 12))
    n = (kvsnap.snapshot_pages_needed(len(prompt), len(out), page_tokens)
         if npages is None else npages)
    ls = []
    for i in range(layers):
        shape = (n, page_tokens, 4, 16)
        if kv_quant == "int8":
            ls.append(kvsnap.LayerSnapshot(
                name=f"layers_{i}",
                k=rng.integers(-128, 128, shape).astype(np.int8),
                v=rng.integers(-128, 128, shape).astype(np.int8),
                k_scale=rng.random((n, 4)).astype(np.float32),
                v_scale=rng.random((n, 4)).astype(np.float32)))
        else:
            ls.append(kvsnap.LayerSnapshot(
                name=f"layers_{i}",
                k=rng.random(shape).astype(np.float32),
                v=rng.random(shape).astype(np.float32)))
    return kvsnap.RequestSnapshot(
        model="tinymodel", request_id="req-1", page_tokens=page_tokens,
        kv_quant=kv_quant, spec="off", prompt=prompt, out=list(out),
        max_new=8, temp=0.0, topk=0, eos=-1, key=key, layers=ls)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("compress", [False, True])
def test_kms1_roundtrip(kv_quant, compress):
    snap = synth_snap(kv_quant=kv_quant)
    payload = kvsnap.encode_snapshot(snap, compress=compress)
    assert payload[:4] == kvsnap.MAGIC
    hdr = kvsnap.peek_header(payload)
    assert hdr["model"] == "tinymodel" and hdr["request_id"] == "req-1"
    back = kvsnap.decode_snapshot(payload)
    assert (back.prompt, back.out, back.max_new) == (snap.prompt, snap.out,
                                                     snap.max_new)
    assert (back.temp, back.topk, back.eos) == (snap.temp, snap.topk,
                                                snap.eos)
    assert tuple(back.key) == tuple(snap.key)
    assert back.kv_quant == kv_quant and back.npages == snap.npages
    assert len(back.layers) == len(snap.layers)
    for a, b in zip(snap.layers, back.layers):
        assert a.name == b.name
        if compress and kv_quant == "none":
            # q8 is deliberately lossy (per-channel int8): close, not equal
            np.testing.assert_allclose(np.asarray(a.k), np.asarray(b.k),
                                       atol=0.02)
            np.testing.assert_allclose(np.asarray(a.v), np.asarray(b.v),
                                       atol=0.02)
        else:
            # raw float frames and int8 arenas round-trip bit-exactly
            np.testing.assert_array_equal(np.asarray(a.k), np.asarray(b.k))
            np.testing.assert_array_equal(np.asarray(a.v), np.asarray(b.v))
        if kv_quant == "int8":
            np.testing.assert_array_equal(np.asarray(a.k_scale),
                                          np.asarray(b.k_scale))
            np.testing.assert_array_equal(np.asarray(a.v_scale),
                                          np.asarray(b.v_scale))


# the frames the PARENT of PR 33 made (head-major arenas ``[N, Hkv, pt, D]``,
# ``k_pages[idx].swapaxes(1, 2)``) of ``arena_content``'s pages 4, 1, 5:
# (bytes, sha256), written down from a run of that commit's kvsnap.py
PARENT_FRAMES = {
    "none": (6667, "8f35ab79a81ad367d0ab2f156a21174696db40a5b2ea33a9efdc6956"
                   "f7dedce4"),
    "int8": (2147, "75cdb3876514ae5daa488a47a4fb1b00b4e0f852eeaec21f7fb7e452"
                   "f72c33d3"),
}


def arena_content(kv_quant):
    """Two layers of six pages of 4 tokens, 2 K/V heads of 16, token-major
    (the recipe the parent's frames were made from: do not reorder)."""
    rng = np.random.default_rng(33)
    layers = []
    for _ in range(2):
        shape = (6, 4, 2, 16)
        if kv_quant == "int8":
            layers.append(dict(
                k=rng.integers(-127, 128, shape).astype(np.int8),
                v=rng.integers(-127, 128, shape).astype(np.int8),
                ks=rng.random((6, 2)).astype(np.float32),
                vs=rng.random((6, 2)).astype(np.float32)))
        else:
            layers.append(dict(k=rng.normal(size=shape).astype(np.float32),
                               v=rng.normal(size=shape).astype(np.float32)))
    return layers


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_kms1_frame_bytes_survive_the_row_arena(kv_quant):
    """A frame gathered from the arena of token rows (K‖V, here 64 lanes of
    K and V and 64 of zeros) is byte for byte the frame the parent gathered
    from its head-major arenas, and the frame built from the token-major
    arrays directly; it restores into a fresh arena's rows."""
    import hashlib

    from kubeml_tpu.ops.paged_attention import pack_kv_rows

    content = arena_content(kv_quant)

    def arena(fill):
        cache = {}
        for i, l in enumerate(content):
            rows = pack_kv_rows(l["k"], l["v"])
            attn = {"kv_rows": rows if fill else jnp.zeros_like(rows)}
            if kv_quant == "int8":
                attn["k_scale"] = jnp.asarray(l["ks"]) * fill
                attn["v_scale"] = jnp.asarray(l["vs"]) * fill
            cache[f"block_{i}"] = {"attn": attn}
        return cache

    def snap(layers):
        return kvsnap.RequestSnapshot(
            model="tinymodel", request_id="req-33", page_tokens=4,
            kv_quant=kv_quant, spec="off", prompt=list(range(1, 10)),
            out=[7, 8, 9], max_new=8, temp=0.0, topk=0, eos=-1, key=(1, 2),
            layers=layers)

    pages = [4, 1, 5]
    cache = arena(1)
    assert cache["block_0"]["attn"]["kv_rows"].shape == (6, 4, 128)
    frame = kvsnap.encode_snapshot(snap(kvsnap.gather_pages(cache, pages,
                                                            2, 16)))
    assert (len(frame), hashlib.sha256(frame).hexdigest()) == \
        PARENT_FRAMES[kv_quant]
    direct = kvsnap.encode_snapshot(snap([
        kvsnap.LayerSnapshot(
            name=f"block_{i}", k=l["k"][pages], v=l["v"][pages],
            k_scale=l["ks"][pages] if kv_quant == "int8" else None,
            v_scale=l["vs"][pages] if kv_quant == "int8" else None)
        for i, l in enumerate(content)]))
    assert frame == direct
    # restore: other physical pages of an empty arena hold the same rows
    back = kvsnap.decode_snapshot(frame)
    fresh = kvsnap.scatter_pages(arena(0), [2, 3, 0], back.layers)
    for name, attn in cache.items():
        got = fresh[name]["attn"]
        for key, value in attn["attn"].items():
            np.testing.assert_array_equal(
                np.asarray(got[key])[[2, 3, 0]], np.asarray(value)[pages])
        assert not np.asarray(got["kv_rows"])[[1, 4, 5]].any()
    # a frame of other heads does not fit these rows
    wide = [kvsnap.LayerSnapshot(name=l.name, k=np.repeat(l.k, 4, axis=2),
                                 v=np.repeat(l.v, 4, axis=2),
                                 k_scale=l.k_scale, v_scale=l.v_scale)
            for l in back.layers]
    with pytest.raises(kvsnap.SnapshotError):
        kvsnap.scatter_pages(arena(0), [2, 3, 0], wide)


def test_kms1_rejects_corrupt_frames():
    payload = kvsnap.encode_snapshot(synth_snap())
    with pytest.raises(kvsnap.SnapshotError):
        kvsnap.decode_snapshot(b"XXXX" + payload[4:])   # magic
    with pytest.raises(kvsnap.SnapshotError):
        kvsnap.decode_snapshot(payload[:4] + b"\x63" + payload[5:])  # ver
    with pytest.raises(kvsnap.SnapshotError):
        kvsnap.decode_snapshot(payload[:-3])            # truncated
    with pytest.raises(kvsnap.SnapshotError):
        kvsnap.decode_snapshot(payload + b"\x00")       # trailing bytes
    with pytest.raises(kvsnap.SnapshotError):
        kvsnap.decode_snapshot(b"KM")                   # too short


def test_snapshot_page_math():
    # a row with m consumed emissions wrote positions 0..plen+m-2
    assert kvsnap.snapshot_pages_needed(11, 0, 4) == 0   # stateless
    assert kvsnap.snapshot_pages_needed(11, 1, 4) == 3   # 11 written
    assert kvsnap.snapshot_pages_needed(11, 2, 4) == 3   # 12 written
    assert kvsnap.snapshot_pages_needed(11, 3, 4) == 4   # 13 written
    assert kvsnap.snapshot_pages_needed(1, 1, 4) == 1


# --- drain -> cross-decoder migration ---


def test_drain_snapshots_and_cross_decoder_restore_parity(served):
    """The migration bar: decoder A drains mid-stream; its KMS1 frame
    restores into a FRESH decoder B whose continuation is bit-identical
    to the uninterrupted greedy run. A's waiter fails retryably with the
    partial tokens; A's pool comes back clean; A 429s new work."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    ref = one_shot(m, variables, p, 20)[0][0].tolist()
    a = paged(m, variables)
    try:
        entry = a.submit(GenerateRequest(prompts=p.tolist(),
                                         max_new_tokens=20, stream=True))
        gen = a.stream(entry)
        next(gen)                       # mid-stream: >=1 token consumed
        frames = a.drain(grace=0.2)
        assert len(frames) == 1
        with pytest.raises(EngineFaultError) as ei:
            list(gen)
        assert ei.value.retryable and ei.value.status_code == 503
        assert ei.value.partial_tokens and ei.value.partial_tokens[0]
        assert ei.value.partial_tokens[0] == ref[:len(
            ei.value.partial_tokens[0])]
        # drain gate: new admissions 429 with a Retry-After hint
        with pytest.raises(OverloadedError):
            a.submit(GenerateRequest(prompts=p.tolist(), max_new_tokens=2))
        chk = a._pool.check()
        assert chk["held"] == chk["trie_pages"]   # nothing leaked
        s = a.stats.snapshot()
        assert s["snapshot_saved"] == 1.0
        assert a.telemetry()["draining"] == 1.0
    finally:
        a.close()
    b = paged(m, variables)
    try:
        hdr = kvsnap.peek_header(frames[0])
        assert hdr["model"] == "tinymodel" and hdr["out_len"] >= 1
        restored = b.submit_snapshot(frames[0])
        out = b.wait(restored, timeout=600)
        assert out["tokens"][0][:out["lengths"][0]] == ref
        s = b.stats.snapshot()
        assert s["snapshot_restored"] == 1.0
        assert s.get("snapshot_failed", 0.0) == 0.0
        assert b._pool.check()["held"] == b._pool.check()["trie_pages"]
    finally:
        b.close()


def test_stateless_snapshot_replays_as_prefill(served):
    """A zero-emission frame (queued / mid-prefill at drain) re-prefills
    from its prompt on restore — same tokens as a fresh submit."""
    m, variables = served
    p = np.arange(3, 17, dtype=np.int32)
    ref = one_shot(m, variables, p[None], 6)[0][0].tolist()
    snap = kvsnap.RequestSnapshot(
        model="", request_id="r-stateless", page_tokens=4, kv_quant="none",
        spec="off", prompt=[int(t) for t in p], out=[], max_new=6,
        temp=0.0, topk=0, eos=-1, key=(0, 0), layers=[])
    dec = paged(m, variables)
    try:
        out = dec.wait(dec.submit_snapshot(kvsnap.encode_snapshot(snap)),
                       timeout=600)
        assert out["tokens"][0][:out["lengths"][0]] == ref
        assert out["request_id"] == "r-stateless"
    finally:
        dec.close()


def test_completed_snapshot_resolves_immediately(served):
    m, variables = served
    snap = kvsnap.RequestSnapshot(
        model="", request_id="r-done", page_tokens=4, kv_quant="none",
        spec="off", prompt=[1, 2, 3], out=[9, 8], max_new=2, temp=0.0,
        topk=0, eos=-1, key=(0, 0), layers=[])
    dec = paged(m, variables)
    try:
        entry = dec.submit_snapshot(snap)
        assert entry.done_evt.is_set()
        out = dec.wait(entry, timeout=5)
        assert out["tokens"][0][:2] == [9, 8] and out["lengths"] == [2]
    finally:
        dec.close()


def test_snapshot_mismatches_rejected(served):
    """Version/geometry/storage guards: a frame must only restore into a
    byte-compatible arena — everything else 409s (or 400s) up front."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    a = paged(m, variables)
    try:
        entry = a.submit(GenerateRequest(prompts=p.tolist(),
                                         max_new_tokens=16, stream=True))
        next(a.stream(entry))
        frames = a.drain(grace=0.2)
        assert len(frames) == 1
    finally:
        a.close()
    # page-geometry mismatch: engine carved into 8-token pages
    b = paged(m, variables, page_tokens=8)
    try:
        with pytest.raises(KubeMLError) as ei:
            b.submit_snapshot(frames[0])
        assert ei.value.status_code == 409 and "page_tokens" in str(ei.value)
    finally:
        b.close()
    # arena-storage mismatch: engine stores int8 pages, frame is f32
    b = paged(m, variables, kv_quant="int8")
    try:
        with pytest.raises(KubeMLError) as ei:
            b.submit_snapshot(frames[0])
        assert ei.value.status_code == 409 and "KV_QUANT" in str(ei.value)
    finally:
        b.close()
    # model mismatch + empty prompt
    b = paged(m, variables, name="othermodel")
    try:
        with pytest.raises(KubeMLError) as ei:
            b.submit_snapshot(frames[0])
        assert ei.value.status_code == 409
        empty = synth_snap()
        empty.model = ""
        empty.prompt = []
        with pytest.raises(KubeMLError) as ei:
            b.submit_snapshot(empty)
        assert ei.value.status_code == 400
    finally:
        b.close()


def test_restore_waits_for_page_budget(served):
    """Budget-refused restore REQUEUES (admission order preserved) instead
    of failing: it dispatches once the occupant's pages free."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    ref = one_shot(m, variables, p, 20)[0][0].tolist()
    a = paged(m, variables)
    try:
        entry = a.submit(GenerateRequest(prompts=p.tolist(),
                                         max_new_tokens=20, stream=True))
        next(a.stream(entry))
        frames = a.drain(grace=0.2)
    finally:
        a.close()
    # 8 usable pages; the occupant's 11+16-1=26 positions hold 7 of them,
    # so the restore (8 pages for 11+20-1 positions) must wait
    b = paged(m, variables, pages=9, prefix_cache=False, slots=2)
    try:
        occupant = b.submit(GenerateRequest(prompts=p.tolist(),
                                            max_new_tokens=16))
        restored = b.submit_snapshot(frames[0])
        out = b.wait(restored, timeout=600)
        assert out["tokens"][0][:out["lengths"][0]] == ref
        b.wait(occupant, timeout=600)
        assert b._pool.check()["held"] == 0
    finally:
        b.close()


# --- fault recovery: snapshot-what-you-can, replay after rebuild ---


def test_fault_recovery_replays_midstream(served):
    """An engine fault mid-decode no longer sheds the in-flight request:
    resident rows snapshot, the arena rebuilds, the rows replay — the
    waiter sees a normal, bit-identical completion. Queued work of
    healthy entries survives too."""
    m, variables = served
    rng = np.random.default_rng(7)
    p1 = np.arange(1, 12, dtype=np.int32)[None]
    p2 = rng.integers(1, VOCAB, size=(1, 7)).astype(np.int32)
    ref1 = one_shot(m, variables, p1, 20)[0][0].tolist()
    ref2 = one_shot(m, variables, p2, 10)[0][0].tolist()
    dec = paged(m, variables)
    try:
        e1 = dec.submit(GenerateRequest(prompts=p1.tolist(),
                                        max_new_tokens=20))
        first_token(dec, e1)
        arm_fault(dec)
        e2 = dec.submit(GenerateRequest(prompts=p2.tolist(),
                                        max_new_tokens=10))
        out1 = dec.wait(e1, timeout=600)
        out2 = dec.wait(e2, timeout=600)
        assert out1["tokens"][0][:out1["lengths"][0]] == ref1
        assert out2["tokens"][0][:out2["lengths"][0]] == ref2
        s = dec.stats.snapshot()
        assert s["snapshot_saved"] >= 1.0
        assert s["snapshot_restored"] >= 1.0
        assert s["snapshot_replayed"] >= 1.0
        chk = dec._pool.check()
        assert chk["held"] == chk["trie_pages"]
    finally:
        dec.close()


def test_unsalvageable_fault_fails_fast_retryable(served):
    """The PR-20 regression, upgraded seam: when a row CANNOT cross the
    rebuild (its snapshot fails — poisoned device state), the waiter gets
    a deterministic retryable 503 carrying the partial tokens, never a
    done_evt hang."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    dec = paged(m, variables)
    try:
        entry = dec.submit(GenerateRequest(prompts=p.tolist(),
                                           max_new_tokens=20))
        first_token(dec, entry)
        dec._snapshot_row = lambda row: None   # salvage impossible
        arm_fault(dec)
        with pytest.raises(EngineFaultError) as ei:
            dec.wait(entry, timeout=120)
        assert ei.value.retryable and ei.value.status_code == 503
        assert ei.value.partial_tokens and ei.value.partial_tokens[0]
        assert entry.done_evt.is_set()
        # the engine rebuilt: fresh work still serves
        ref = one_shot(m, variables, p, 4)[0][0].tolist()
        out = dec.wait(dec.submit(GenerateRequest(
            prompts=p.tolist(), max_new_tokens=4)), timeout=600)
        assert out["tokens"][0][:4] == ref
    finally:
        dec.close()


def test_dense_engine_fault_is_retryable_with_partial_tokens(served):
    """Satellite regression on the DENSE engine (no snapshot seam there):
    a loop fault fails in-flight entries with the typed retryable error +
    partial tokens instead of a bare 500."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    # pipeline_depth=1: the dense engine otherwise dispatches the whole
    # request's chunks up front and the armed fault never fires
    dec = BatchingDecoder(m, variables, slots=2, chunk_steps=2,
                          pipeline_depth=1, name="tinymodel")
    try:
        entry = dec.submit(GenerateRequest(prompts=p.tolist(),
                                           max_new_tokens=20))
        first_token(dec, entry)
        orig = dec._dispatch_chunk
        state = {"armed": True}

        def boom(*a, **kw):
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected device fault")
            return orig(*a, **kw)

        dec._dispatch_chunk = boom
        with pytest.raises(EngineFaultError) as ei:
            dec.wait(entry, timeout=120)
        assert ei.value.retryable
        assert ei.value.partial_tokens and ei.value.partial_tokens[0]
    finally:
        dec.close()


def test_error_envelope_roundtrips_partial_tokens():
    """EngineFaultError survives the JSON envelope hop-by-hop (api.errors
    contract): retryable + partial_tokens rebuild on the client side."""
    from kubeml_tpu.api.errors import error_from_envelope

    e = EngineFaultError("decode engine fault: boom",
                         partial_tokens=[[1, 2, 3]])
    back = error_from_envelope(e.to_json(), 503)
    assert isinstance(back, EngineFaultError)
    assert back.retryable and back.status_code == 503
    assert back.partial_tokens == [[1, 2, 3]]


# --- pool-audit watchdog ---


def test_pool_audit_watchdog_runs(served):
    m, variables = served
    p = np.arange(1, 10, dtype=np.int32)[None]
    dec = paged(m, variables, pool_audit_interval=0.02)
    try:
        dec.wait(dec.submit(GenerateRequest(prompts=p.tolist(),
                                            max_new_tokens=6)), timeout=600)
        s = dec.stats.snapshot()
        assert s["pool_audit_runs"] >= 1.0
        assert s["pool_audit_failures"] == 0.0
    finally:
        dec.close()


def test_pool_audit_failure_triggers_rebuild(served):
    """A tripped invariant audit routes through the fault-recovery seam:
    the failure is counted, the arena rebuilds, and the decoder keeps
    serving (fresh pool, monkeypatched check gone)."""
    m, variables = served
    p = np.arange(1, 10, dtype=np.int32)[None]
    ref = one_shot(m, variables, p, 4)[0][0].tolist()
    dec = paged(m, variables, pool_audit_interval=0.01)
    try:
        entry = dec.submit(GenerateRequest(prompts=p.tolist(),
                                           max_new_tokens=20))
        first_token(dec, entry)
        from kubeml_tpu.serving.kvpool import PageAllocError

        def tripped():
            raise PageAllocError("injected invariant break")

        dec._pool.check = tripped
        out = dec.wait(entry, timeout=600)   # replayed across the rebuild
        assert out["lengths"][0] == 20
        s = dec.stats.snapshot()
        assert s["pool_audit_failures"] >= 1.0
        out2 = dec.wait(dec.submit(GenerateRequest(
            prompts=p.tolist(), max_new_tokens=4)), timeout=600)
        assert out2["tokens"][0][:4] == ref
    finally:
        dec.close()


# --- compose: int8 arena + self-speculative decoding ---


def test_int8_kv_snapshot_restore_parity(served):
    """Int8 pages migrate as raw bytes + scale rows: the restored stream
    must equal the UNINTERRUPTED int8 engine's output (int8 storage
    rounds differently from f32, so the baseline is an int8 run)."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    base = paged(m, variables, kv_quant="int8")
    try:
        ref = base.wait(base.submit(GenerateRequest(
            prompts=p.tolist(), max_new_tokens=16)), timeout=600)
        ref = ref["tokens"][0][:16]
    finally:
        base.close()
    a = paged(m, variables, kv_quant="int8")
    try:
        entry = a.submit(GenerateRequest(prompts=p.tolist(),
                                         max_new_tokens=16, stream=True))
        next(a.stream(entry))
        frames = a.drain(grace=0.2)
        assert len(frames) == 1
        assert kvsnap.peek_header(frames[0])["kv_quant"] == "int8"
    finally:
        a.close()
    b = paged(m, variables, kv_quant="int8")
    try:
        out = b.wait(b.submit_snapshot(frames[0]), timeout=600)
        assert out["tokens"][0][:out["lengths"][0]] == ref
    finally:
        b.close()


def test_spec_self_snapshot_restore_parity(served):
    """KUBEML_SERVING_SPEC=self composes: the one shared arena covers the
    drafter's truncated-stack layers too, so a drained spec-self row
    restores into a fresh spec-self engine and stays greedy-identical to
    the one-shot run (spec greedy == plain greedy by acceptance rule)."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    ref = one_shot(m, variables, p, 16)[0][0].tolist()
    a = paged(m, variables, spec="self", spec_exit_layer=1, spec_k=2)
    try:
        entry = a.submit(GenerateRequest(prompts=p.tolist(),
                                         max_new_tokens=16, stream=True))
        next(a.stream(entry))
        frames = a.drain(grace=0.2)
        assert len(frames) == 1
        assert kvsnap.peek_header(frames[0])["spec"] == "self"
    finally:
        a.close()
    b = paged(m, variables, spec="self", spec_exit_layer=1, spec_k=2)
    try:
        out = b.wait(b.submit_snapshot(frames[0]), timeout=600)
        assert out["tokens"][0][:out["lengths"][0]] == ref
    finally:
        b.close()


def test_spec_draft_snapshot_rejected(served):
    """spec='draft' keeps a separate drafter arena KMS1 does not capture:
    mid-stream frames refuse to restore there (409), and draft rows are
    unsalvageable at fault time by design."""
    m, variables = served
    p = np.arange(1, 12, dtype=np.int32)[None]
    a = paged(m, variables)
    try:
        entry = a.submit(GenerateRequest(prompts=p.tolist(),
                                         max_new_tokens=16, stream=True))
        next(a.stream(entry))
        frames = a.drain(grace=0.2)
    finally:
        a.close()
    b = paged(m, variables)
    b.spec = "draft"   # geometry checks run before any draft machinery
    try:
        with pytest.raises(KubeMLError) as ei:
            b.submit_snapshot(frames[0])
        assert ei.value.status_code == 409 and "draft" in str(ei.value)
    finally:
        b.spec = ""
        b.close()


# --- the chaos bar (slow tier) ---


@pytest.mark.slow
def test_chaos_storm_recovery_exactness(served):
    """Seeded storm: >=8 live mixed-length streams (incl. a prefix-shared
    pair), an injected engine fault mid-decode, plus a cancel — every
    surviving stream completes greedy-bit-identical to its uninterrupted
    baseline, every page is returned exactly once (``check()`` clean),
    and the snapshot counters account for the round trip."""
    m, variables = served
    rng = np.random.default_rng(11)
    sysp = rng.integers(1, VOCAB, size=12).astype(np.int32)
    prompts = [np.concatenate([sysp,
                               rng.integers(1, VOCAB, size=3 + i).astype(
                                   np.int32)]) for i in range(2)]
    prompts += [rng.integers(1, VOCAB, size=l).astype(np.int32)
                for l in (3, 9, 5, 12, 7, 16)]
    max_news = [14, 9, 6, 17, 3, 11, 8, 12]
    refs = [one_shot(m, variables, p[None], n)[0][0].tolist()
            for p, n in zip(prompts, max_news)]
    dec = paged(m, variables, slots=3)
    try:
        entries = [dec.submit(GenerateRequest(prompts=[p.tolist()],
                                              max_new_tokens=n))
                   for p, n in zip(prompts, max_news)]
        first_token(dec, entries[0])
        arm_fault(dec)
        victim = dec.submit(GenerateRequest(prompts=[prompts[0].tolist()],
                                            max_new_tokens=30))
        dec.cancel(victim)
        for e, ref in zip(entries, refs):
            out = dec.wait(e, timeout=600)
            assert out["tokens"][0][:out["lengths"][0]] == ref
        s = dec.stats.snapshot()
        assert s["snapshot_replayed"] >= 1.0
        assert s.get("snapshot_failed", 0.0) == 0.0
        chk = dec._pool.check()
        assert chk["held"] == chk["trie_pages"]
        if dec._pool.trie is not None:
            dec._pool.trie.flush()
            assert dec._pool.check()["held"] == 0
    finally:
        dec.close()
