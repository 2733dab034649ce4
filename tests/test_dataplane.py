"""Weight-movement data-plane tests: codec round-trips, error-feedback
convergence, delta publish/fetch with per-leaf versions, and the seqlock
invariant under concurrent publish/fetch (PR 7 tentpole)."""

import json
import threading

import numpy as np
import pytest

from kubeml_tpu.engine.dataplane import (
    MIN_Q8_SIZE, BaseVersionMismatch, DataPlaneError, DeltaDecoder,
    DeltaEncoder, WeightsWire, decode_tree, encode_tree)
from kubeml_tpu.native.weights import (
    FetchCache, PublishState, fetch_variables, publish_variables,
    read_version)


class MemKV:
    """Dict-backed TensorStore stand-in with op counters."""

    def __init__(self):
        self.d = {}
        self.sets = 0
        self.gets = 0

    def set(self, k, v):
        self.d[k] = np.asarray(v).copy()
        self.sets += 1

    def get(self, k):
        self.gets += 1
        v = self.d.get(k)
        return None if v is None else v.copy()


def _tree(seed=0, big=256):
    r = np.random.default_rng(seed)
    import ml_dtypes

    return {
        "params": {
            "dense": {
                "kernel": r.normal(size=(big, 64)).astype(np.float32),
                "bias": np.zeros(64, np.float32),
            },
            "emb": r.normal(size=(32, 16)).astype(ml_dtypes.bfloat16),
        },
        "stats": {"count": np.array([7], np.int64)},
    }


def _assert_tree_equal(a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, z in zip(la, lb):
        assert x.dtype == z.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


# --- codec round-trips ---


def test_raw_roundtrip_bit_exact():
    tree = _tree()
    got, version = decode_tree(encode_tree(tree, version=9, codec="raw"))
    assert version == 9
    _assert_tree_equal(got, tree)


@pytest.mark.parametrize("codec", ["delta", "delta-int8"])
def test_first_encode_is_full_snapshot(codec):
    """No base -> full raw snapshot, whatever the codec (chain bootstrap)."""
    tree = _tree()
    enc = DeltaEncoder(codec)
    got, version = DeltaDecoder().decode(enc.encode(tree, 1))
    assert version == 1
    _assert_tree_equal(got, tree)


def test_delta_skips_unchanged_and_stays_bit_exact():
    tree = _tree()
    enc, dec = DeltaEncoder("delta"), DeltaDecoder()
    p1 = enc.encode(tree, 1)
    dec.decode(p1)
    tree2 = {  # same structure, one changed leaf
        "params": {
            "dense": {"kernel": tree["params"]["dense"]["kernel"] + 1.0,
                      "bias": tree["params"]["dense"]["bias"]},
            "emb": tree["params"]["emb"],
        },
        "stats": tree["stats"],
    }
    p2 = enc.encode(tree2, 2)
    assert len(p2) < len(p1)  # unchanged leaves shipped as skip markers
    got, version = dec.decode(p2)
    assert version == 2
    _assert_tree_equal(got, tree2)


def test_delta_int8_tolerance_and_mirror():
    """One lossy step: reconstruction within a quant step of the truth, and
    the decoder holds EXACTLY the encoder's synced state (the invariant the
    multi-round convergence argument rests on)."""
    tree = _tree()
    enc, dec = DeltaEncoder("delta-int8"), DeltaDecoder()
    dec.decode(enc.encode(tree, 1))
    delta = 0.01 * np.random.default_rng(1).normal(
        size=tree["params"]["dense"]["kernel"].shape).astype(np.float32)
    tree2 = {
        "params": {
            "dense": {"kernel": tree["params"]["dense"]["kernel"] + delta,
                      "bias": tree["params"]["dense"]["bias"]},
            "emb": tree["params"]["emb"],
        },
        "stats": tree["stats"],
    }
    p2 = enc.encode(tree2, 2)
    got, _ = dec.decode(p2)
    err = np.abs(got["params"]["dense"]["kernel"]
                 - tree2["params"]["dense"]["kernel"]).max()
    # one quantization step of a per-channel-scaled 0.01-magnitude delta
    assert err <= np.abs(delta).max() / 127.0 * 1.5 + 1e-7
    for key, a in enc.synced.items():
        np.testing.assert_array_equal(a, dec.tree[key])
    # and the payload is ~4x smaller than the raw leaf it carries
    kernel_bytes = tree["params"]["dense"]["kernel"].nbytes
    assert len(p2) < kernel_bytes / 2


def test_delta_int8_error_feedback_keeps_chain_convergent():
    """A drifting weight stream through many lossy rounds: with the
    error-feedback residual the reconstruction error stays BOUNDED (a few
    quant steps, no growth with round count); a feedback-free chain over the
    same stream accumulates a random walk and ends measurably worse."""
    rounds, step = 60, 0.01

    def chain(feedback: bool):
        r = np.random.default_rng(0)
        w = r.normal(size=(MIN_Q8_SIZE,)).astype(np.float32).reshape(64, -1)
        enc, dec = DeltaEncoder("delta-int8"), DeltaDecoder()
        errs = []
        for i in range(1, rounds + 1):
            w = w + (step * r.normal(size=w.shape)).astype(np.float32)
            got, _ = dec.decode(enc.encode({"w": w}, i))
            if not feedback:
                # ablation: chain against the TRUE weights instead of the
                # receiver-synced state — the residual never re-ships, so
                # the decoder's error random-walks
                enc.synced = {"w": w.copy()}
            errs.append(float(np.abs(got["w"] - w).max()))
        return errs, enc, dec, w

    errs, enc, dec, w = chain(feedback=True)
    errs_nofb, _, _, _ = chain(feedback=False)
    # bounded: the tail error is no worse than the early error (no growth)
    assert max(errs[-10:]) < 3.0 * max(errs[:10]) + 1e-6
    # and the full-feedback error stays well under the per-round drift
    assert errs[-1] < step / 2
    # the ablation drifts: feedback must end strictly tighter
    assert errs[-1] < errs_nofb[-1]
    # the error-feedback carry is implicit: the mirrors agree bit-exactly,
    # and truth - synced (the un-shipped remainder) is what errs[-1] bounds
    np.testing.assert_array_equal(enc.synced["w"], dec.tree["w"])


def test_delta_int8_small_and_int_leaves_ship_exact():
    """Leaves below MIN_Q8_SIZE and integer leaves never quantize."""
    small = np.random.default_rng(2).normal(size=(8, 8)).astype(np.float32)
    tree = {"small": small, "n": np.array([1], np.int64)}
    enc, dec = DeltaEncoder("delta-int8"), DeltaDecoder()
    dec.decode(enc.encode(tree, 1))
    tree2 = {"small": small + 0.5, "n": np.array([2], np.int64)}
    got, _ = dec.decode(enc.encode(tree2, 2))
    _assert_tree_equal(got, tree2)  # bit-exact, no quantization


def test_base_version_mismatch_and_malformed_payload():
    tree = _tree()
    enc = DeltaEncoder("delta")
    enc.encode(tree, 1)
    p2 = enc.encode(tree, 2)  # delta against v1
    dec = DeltaDecoder()  # holds nothing
    with pytest.raises(BaseVersionMismatch):
        dec.decode(p2)
    with pytest.raises(DataPlaneError):
        dec.decode(b"not a payload at all")


def test_weights_wire_delta_full_current():
    wire = WeightsWire("delta-int8")
    assert wire.get() is None
    t1 = _tree(seed=3)
    wire.publish(t1, 1)
    full, v = wire.get()
    assert v == 1
    dec = DeltaDecoder()
    got, _ = dec.decode(full)
    _assert_tree_equal(got, t1)
    assert wire.get(1) == ("current", 1)
    t2 = {
        "params": {
            "dense": {"kernel": t1["params"]["dense"]["kernel"] * 1.01,
                      "bias": t1["params"]["dense"]["bias"]},
            "emb": t1["params"]["emb"],
        },
        "stats": t1["stats"],
    }
    wire.publish(t2, 2)
    delta, v = wire.get(1)
    assert v == 2 and len(delta) < len(full)
    got2, _ = dec.decode(delta)  # the client at v1 applies the delta
    # a fresh client pulls the full snapshot and lands on the SAME tree
    snap, v = wire.get(None)
    assert v == 2
    got_snap, _ = DeltaDecoder().decode(snap)
    _assert_tree_equal(got2, got_snap)
    # a client two versions behind gets the full snapshot, not the delta
    wire.publish(t1, 3)
    payload, v = wire.get(1)
    assert v == 3
    head = json.loads(payload[9:9 + int.from_bytes(payload[5:9], "little")])
    assert head["base_version"] is None


# --- delta publish/fetch through the store channel ---


def test_publish_state_skips_unchanged_leaves():
    kv = MemKV()
    state = PublishState()
    tree = _tree(seed=4)
    publish_variables(kv, tree, 1, state=state)
    sets_after_full = kv.sets
    tree2 = {
        "params": {
            "dense": {"kernel": tree["params"]["dense"]["kernel"] + 1,
                      "bias": tree["params"]["dense"]["bias"]},
            "emb": tree["params"]["emb"],
        },
        "stats": tree["stats"],
    }
    publish_variables(kv, tree2, 2, state=state)
    # version sentinel + 1 changed leaf + manifest + version = 4 writes
    assert kv.sets - sets_after_full == 4
    got, v = fetch_variables(kv)
    assert v == 2
    _assert_tree_equal(got, tree2)


def test_fetch_cache_pulls_only_stale_leaves():
    kv = MemKV()
    state, cache = PublishState(), FetchCache()
    tree = _tree(seed=5)
    publish_variables(kv, tree, 1, state=state)
    got, v = fetch_variables(kv, cache=cache)
    assert v == 1
    tree2 = {
        "params": {
            "dense": {"kernel": tree["params"]["dense"]["kernel"] + 1,
                      "bias": tree["params"]["dense"]["bias"]},
            "emb": tree["params"]["emb"],
        },
        "stats": tree["stats"],
    }
    publish_variables(kv, tree2, 2, state=state)
    gets_before = kv.gets
    got2, v2 = fetch_variables(kv, cache=cache)
    # version (pre+post recheck) + manifest + exactly ONE stale leaf
    assert kv.gets - gets_before == 4
    assert v2 == 2
    _assert_tree_equal(got2, tree2)


def test_manifest_v1_compat():
    """A plain key-list manifest (pre-delta writers) still fetches."""
    kv = MemKV()
    kv.set("a/w", np.arange(6).astype(np.float32).reshape(2, 3))
    kv.set("b", np.ones(3, np.float32))
    kv.set("__manifest__",
           np.frombuffer(json.dumps(["a/w", "b"]).encode(), np.uint8))
    kv.set("__version__", np.array([4], np.int64))
    got, v = fetch_variables(kv)
    assert v == 4
    np.testing.assert_array_equal(got["a"]["w"],
                                  np.arange(6).reshape(2, 3))


def test_flatten_and_manifest_key_cache_reused():
    """Same structure between publishes -> the key list and its JSON
    encoding come from the cache; a structure change invalidates it."""
    state = PublishState()
    tree = _tree(seed=6)
    kv = MemKV()
    publish_variables(kv, tree, 1, state=state)
    keys_obj, json_obj = state.keys, state.keys_json
    publish_variables(kv, tree, 2, state=state)
    assert state.keys is keys_obj and state.keys_json is json_obj
    tree2 = {**tree, "extra": np.zeros(3, np.float32)}
    publish_variables(kv, tree2, 3, state=state)
    assert state.keys is not keys_obj
    assert "extra" in state.keys
    got, v = fetch_variables(kv)
    assert v == 3 and "extra" in got


def test_structure_change_invalidates_stale_digests():
    """A path that newly appears after a structure change must be written
    even if an unrelated leaf once hashed the same."""
    state = PublishState()
    kv = MemKV()
    a = np.random.default_rng(7).normal(size=(4, 4)).astype(np.float32)
    publish_variables(kv, {"x": a}, 1, state=state)
    publish_variables(kv, {"x": a, "y": a.copy()}, 2, state=state)
    got, v = fetch_variables(kv)
    assert v == 2
    np.testing.assert_array_equal(got["y"], a)


def test_torn_fetch_accounts_wasted_bytes_and_retries():
    from kubeml_tpu.utils import profiler

    profiler.reset_accounting()
    kv = MemKV()
    publish_variables(kv, _tree(seed=8), 1)

    class Torn:
        """First leaf read of the first attempt returns None (torn)."""

        def __init__(self, inner):
            self.inner = inner
            self.fail = 1

        def get(self, k):
            if not k.startswith("__") and self.fail:
                self.fail -= 1
                return None
            return self.inner.get(k)

    got, v = fetch_variables(Torn(kv))
    assert v == 1 and got is not None
    snap = profiler.counters_snapshot()
    assert snap["retries"].get("weights.fetch") == 1
    assert "weights.fetch_torn" in snap["dataplane"]
    # the torn phase renders on the exposition next to the byte counters
    text = "\n".join(profiler.render_metrics())
    assert 'kubeml_dataplane_retries_total{phase="weights.fetch"} 1' in text
    assert 'kubeml_dataplane_bytes_total{phase="weights.fetch_torn"}' in text


def test_concurrent_publish_fetch_never_serves_mixed_epoch():
    """The per-leaf-versioned seqlock under a publish/fetch race: every
    fetched tree must be single-epoch consistent (all leaves carry the same
    stamp), with and without a FetchCache, while half the leaves change per
    version (exercising skip-writes and per-leaf versions)."""
    kv = MemKV()
    lock = threading.Lock()
    orig_set, orig_get = kv.set, kv.get

    def locked_set(k, v):
        with lock:
            orig_set(k, v)

    def locked_get(k):
        with lock:
            return orig_get(k)

    kv.set, kv.get = locked_set, locked_get

    n_leaves = 8

    def tree_at(version):
        # even leaves change every version; odd leaves are frozen — but every
        # CHANGING leaf is stamped with the version, so a mixed-epoch tree is
        # detectable by inspection
        return {f"leaf{i}": np.full((64,), float(version if i % 2 == 0 else -1),
                                    np.float32)
                for i in range(n_leaves)}

    stop = threading.Event()
    errors = []

    def writer():
        state = PublishState()
        v = 1
        while not stop.is_set() and v < 400:
            publish_variables(kv, tree_at(v), v, state=state)
            v += 1

    def reader(use_cache):
        cache = FetchCache() if use_cache else None
        seen = 0
        while seen < 50 and not stop.is_set():
            got, v = fetch_variables(kv, retries=50, cache=cache)
            if got is None:
                continue
            seen += 1
            stamps = {float(got[f"leaf{i}"][0]) for i in range(0, n_leaves, 2)}
            if stamps != {float(v)}:
                errors.append(f"mixed-epoch tree at v={v}: stamps {stamps}")
                stop.set()
                return

    w = threading.Thread(target=writer)
    readers = [threading.Thread(target=reader, args=(uc,))
               for uc in (True, False)]
    w.start()
    for t in readers:
        t.start()
    for t in readers:
        t.join(timeout=60)
    stop.set()
    w.join(timeout=60)
    assert not errors, errors


# --- the engine seams ---


def test_round_prefetcher_orders_and_depth():
    from kubeml_tpu.engine.kavg import RoundPrefetcher

    class RB:
        def __init__(self, i):
            self.x = np.full((1, 1, 2, 3), i, np.float32)
            self.y = np.zeros((1, 1, 2), np.int32)
            self.mask = np.ones((1, 1, 2), np.float32)
            self.round_index = i

    staged_log = []

    class FakeTrainer:
        def stage_round(self, x, y, mask, n):
            staged_log.append(int(x[0, 0, 0, 0]))
            return (x, y, mask)

    rounds = [RB(i) for i in range(5)]
    out = list(RoundPrefetcher(FakeTrainer(), rounds, 1, depth=2))
    assert [rb.round_index for rb, _ in out] == [0, 1, 2, 3, 4]
    assert all(staged is not None for _, staged in out)
    # with depth=2, rounds 0..2 stage before round 0 is yielded
    assert staged_log[:3] == [0, 1, 2]
    # depth=0: nothing staged ahead, consumer stages itself
    staged_log.clear()
    out = list(RoundPrefetcher(FakeTrainer(), rounds, 1, depth=0))
    assert staged_log == [] and all(s is None for _, s in out)


def test_job_runner_weights_route(tmp_config):
    """GET /weights through the runner's handler: 404 before any publish,
    binary full payload, 204 when current, delta when one behind."""
    from kubeml_tpu.api.errors import KubeMLError
    from kubeml_tpu.engine.dataplane import VERSION_HEADER, WeightsWire
    from kubeml_tpu.engine.job_runner import JobRunner
    from kubeml_tpu.utils.httpd import Request

    runner = JobRunner("wiretest", config=tmp_config)

    def req(**query):
        return Request("GET", "/weights", {},
                       {k: [str(v)] for k, v in query.items()}, b"", {})

    with pytest.raises(KubeMLError):
        runner._weights(req())
    t1 = _tree(seed=9)
    runner._weights_wire = WeightsWire("delta")
    runner._weights_wire.publish(t1, 1)
    resp = runner._weights(req())
    assert resp.status == 200
    assert resp.headers[VERSION_HEADER] == "1"
    got, v = DeltaDecoder().decode(resp.body)
    assert v == 1
    _assert_tree_equal(got, t1)
    assert runner._weights(req(since=1)).status == 204
    runner._weights_wire.publish(t1, 2)
    resp = runner._weights(req(since=1))
    assert resp.status == 200 and resp.headers[VERSION_HEADER] == "2"
    with pytest.raises(KubeMLError):
        runner._weights(req(since="nan"))


def test_async_publish_drains_latest(tmp_config):
    """The runner's background publisher: publishes land off the calling
    thread, superseded queue entries are dropped, the newest version wins."""
    import time

    from kubeml_tpu.engine.job_runner import JobRunner

    runner = JobRunner("asyncpub", config=tmp_config)
    t = _tree(seed=10)
    for epoch in range(3):
        runner._publish_weights(t, epoch)
    deadline = time.time() + 10
    while time.time() < deadline:
        wire = runner._weights_wire
        if wire is not None and wire.version == 3:
            break
        time.sleep(0.01)
    runner._join_publisher()
    assert runner._weights_wire.version == 3
    got, v = DeltaDecoder().decode(runner._weights_wire.get()[0])
    assert v == 3
    _assert_tree_equal(got, t)


def test_toy_job_converges_through_delta_int8():
    """The full feedback loop of the weight channel: K-AVG training that
    continues every round from the DECODED tree must reach (numerically)
    the same loss as training that never left the device — the error
    feedback keeps the quantized chain convergent."""
    import jax

    # tiny toy: 2 workers x k=2 x batch=8 on the kavg test model
    import optax

    from kubeml_tpu.engine.kavg import KAvgTrainer
    from kubeml_tpu.runtime.model import KubeModel
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.relu(nn.Dense(64)(x))
            return nn.Dense(4)(x)

    class _FakeDataset:
        dataset = "fake"

    class Model(KubeModel):
        def __init__(self):
            super().__init__(_FakeDataset())
            self.lr = 0.1

        def build(self):
            return Net()

        def configure_optimizers(self):
            return optax.sgd(self.lr)

    r = np.random.default_rng(0)
    n, k, b, dim = 2, 2, 8, 32
    x = r.normal(size=(n, k, b, dim)).astype(np.float32)
    y = r.integers(0, 4, size=(n, k, b)).astype(np.int32)
    mask = np.ones((n, k, b), np.float32)
    rng = jax.random.PRNGKey(0)

    def run(codec):
        trainer = KAvgTrainer(Model(), precision="f32", donate=False)
        variables = trainer.init_variables(rng, x[0, 0], n)
        enc, dec = ((DeltaEncoder(codec), DeltaDecoder())
                    if codec else (None, None))
        loss = None
        for i in range(15):
            variables, loss = trainer.sync_round(
                variables, x, y, mask, jax.random.fold_in(rng, i), lr=0.1)
            if codec:
                ref = trainer.reference_variables(variables)
                decoded, _ = dec.decode(enc.encode(ref, i + 1))
                variables = trainer.place_reference(decoded, n)
        return float(loss)

    baseline = run(None)
    quantized = run("delta-int8")
    assert quantized == pytest.approx(baseline, abs=0.05)


def _wire_header(payload):
    import struct

    (hlen,) = struct.unpack("<I", payload[5:9])
    return json.loads(payload[9:9 + hlen])


def test_delta_int8_frozen_quantizable_leaf_skips():
    """A bit-synced quantizable leaf (a frozen embedding table) ships a
    0-byte skip marker under delta-int8 — not a full all-zero q8 payload
    plus its scale vector, round after round."""
    tree = _tree()
    enc, dec = DeltaEncoder("delta-int8"), DeltaDecoder()
    dec.decode(enc.encode(tree, 1))
    tree2 = {  # only the small bias moves; the big kernel is frozen
        "params": {
            "dense": {"kernel": tree["params"]["dense"]["kernel"],
                      "bias": tree["params"]["dense"]["bias"] + 1.0},
            "emb": tree["params"]["emb"],
        },
        "stats": tree["stats"],
    }
    p2 = enc.encode(tree2, 2)
    entries = {l["path"]: l for l in _wire_header(p2)["leaves"]}
    assert entries["params/dense/kernel"]["enc"] == "skip"
    assert entries["params/dense/kernel"]["nbytes"] == 0
    # the payload carries only the bias + header, a fraction of the kernel
    assert len(p2) < tree["params"]["dense"]["kernel"].nbytes // 8
    got, _ = dec.decode(p2)
    _assert_tree_equal(got, tree2)


def test_metric_push_carries_dataplane_deltas_to_ps(tmp_config, monkeypatch):
    """Standalone runners expose no scraped /metrics route: their
    encode-side dataplane counters ride the per-epoch metric push as
    sequenced delta batches and fold into the PS registry — the one
    exposition the Grafana codec/compression panels query. Delivery is
    effectively-once: a push the PS never saw re-rides the next push
    (same seq) until acked, and a push the PS processed whose RESPONSE
    was lost re-delivers without double-counting (per-job seq
    high-water mark)."""
    from kubeml_tpu.api.types import MetricUpdate
    from kubeml_tpu.engine.job_runner import JobRunner
    from kubeml_tpu.ps.metrics import MetricsRegistry
    from kubeml_tpu.utils import profiler, traced_http

    profiler.reset_accounting()
    runner = JobRunner("dpush", config=tmp_config)
    sent = []

    class _Resp:
        status_code = 200

    def fake_post(url, **kw):
        sent.append(kw["json"])
        return _Resp()

    monkeypatch.setattr(traced_http, "post", fake_post)
    profiler.account("weights.encode.delta-int8", 4096, 0.004)
    profiler.account("weights.encode.dense", 65536)
    runner._push_metrics(MetricUpdate(job_id="dpush"))
    (batch,) = sent[0]["dataplane"]
    assert batch["seq"] == 1
    assert batch["phases"]["weights.encode.delta-int8"]["bytes"] == 4096
    assert batch["phases"]["weights.encode.delta-int8"]["events"] == 1
    assert batch["phases"]["weights.encode.dense"]["bytes"] == 65536
    # acked + no new traffic -> nothing rides the next push
    runner._push_metrics(MetricUpdate(job_id="dpush"))
    assert sent[1]["dataplane"] == []

    # a push the PS never saw: its batch re-rides the next push, same seq,
    # alongside the new traffic's batch — no bytes vanish
    profiler.account("weights.encode.delta-int8", 1024, 0.001)

    def broken_post(url, **kw):
        raise traced_http.RequestException("PS down")

    monkeypatch.setattr(traced_http, "post", broken_post)
    runner._push_metrics(MetricUpdate(job_id="dpush"))
    monkeypatch.setattr(traced_http, "post", fake_post)
    profiler.account("weights.encode.delta-int8", 256, 0.001)
    runner._push_metrics(MetricUpdate(job_id="dpush"))
    redelivered = sent[-1]["dataplane"]
    assert [b["seq"] for b in redelivered] == [2, 3]
    assert redelivered[0]["phases"]["weights.encode.delta-int8"]["bytes"] == 1024
    assert redelivered[1]["phases"]["weights.encode.delta-int8"]["bytes"] == 256
    runner._push_metrics(MetricUpdate(job_id="dpush"))
    assert sent[-1]["dataplane"] == []  # acked batches cleared

    # the PS side folds batches into its own registry/exposition — and a
    # redelivery of an already-applied batch (lost RESPONSE) folds 0 extra
    profiler.reset_accounting()  # now playing the PS process
    reg = MetricsRegistry()
    reg.update(MetricUpdate.from_dict(sent[0]))
    reg.update(MetricUpdate.from_dict(sent[0]))  # same seq: must not re-apply
    text = "\n".join(profiler.render_metrics())
    assert ('kubeml_dataplane_bytes_total{phase="weights.encode.delta-int8"}'
            ' 4096' in text)
    assert ('kubeml_dataplane_bytes_total{phase="weights.encode.dense"}'
            ' 65536' in text)
    profiler.reset_accounting()


def test_delta_int8_quantizes_bfloat16_leaves():
    """bf16 registers with numpy as kind 'V' (not np.floating): the
    quantizable check must still catch it, or every changed bf16 leaf — the
    dominant dtype on the chip runs this PR targets — ships raw and the
    advertised byte cut silently collapses."""
    import ml_dtypes

    r = np.random.default_rng(3)
    w = r.normal(size=(64, MIN_Q8_SIZE // 64)).astype(ml_dtypes.bfloat16)
    enc, dec = DeltaEncoder("delta-int8"), DeltaDecoder()
    dec.decode(enc.encode({"w": w}, 1))
    w2 = (w.astype(np.float32)
          + 0.01 * r.normal(size=w.shape).astype(np.float32)
          ).astype(ml_dtypes.bfloat16)
    p2 = enc.encode({"w": w2}, 2)
    (entry,) = _wire_header(p2)["leaves"]
    assert entry["enc"] == "q8"
    assert len(p2) < w.nbytes  # int8 payload beats the bf16 leaf it updates
    got, _ = dec.decode(p2)
    assert got["w"].dtype == w2.dtype
    # within a quant step of the truth (plus bf16 rounding)
    err = np.abs(got["w"].astype(np.float32) - w2.astype(np.float32)).max()
    assert err < 0.01


def test_metric_push_error_status_is_not_an_ack(tmp_config, monkeypatch):
    """traced_http RETURNS retryable-status responses (429/504/chaos 500)
    instead of raising: a non-2xx answer must keep the unacked dataplane
    batches queued for redelivery, not clear them."""
    from kubeml_tpu.api.types import MetricUpdate
    from kubeml_tpu.engine.job_runner import JobRunner
    from kubeml_tpu.utils import profiler, traced_http

    profiler.reset_accounting()
    runner = JobRunner("dpack", config=tmp_config)
    sent = []

    class _Resp:
        def __init__(self, code):
            self.status_code = code

    codes = iter([429, 504, 200, 200])

    def post(url, **kw):
        sent.append(kw["json"])
        return _Resp(next(codes))

    monkeypatch.setattr(traced_http, "post", post)
    profiler.account("weights.encode.delta-int8", 2048, 0.002)
    runner._push_metrics(MetricUpdate(job_id="dpack"))  # 429: no ack
    runner._push_metrics(MetricUpdate(job_id="dpack"))  # 504: no ack
    runner._push_metrics(MetricUpdate(job_id="dpack"))  # 200: acked
    assert [b["seq"] for b in sent[0]["dataplane"]] == [1]
    assert [b["seq"] for b in sent[1]["dataplane"]] == [1]
    assert [b["seq"] for b in sent[2]["dataplane"]] == [1]
    runner._push_metrics(MetricUpdate(job_id="dpack"))
    assert sent[-1]["dataplane"] == []
    profiler.reset_accounting()


def test_concurrent_wire_infer_never_mixes_epochs(tmp_config):
    """The PS's _infer_from_wire pulls OUTSIDE the per-model lock (so one
    slow runner response cannot serialize the whole serving path) and
    decodes under it. Hammered from many threads against a wire whose
    version keeps advancing, every serve must still come from one
    internally consistent epoch — two leaves published with the same fill
    value must never disagree — and racing threads holding the same delta
    payload must not double-apply it into the shared stateful decoder
    (which would corrupt the chain and fail decodes from then on)."""
    import threading as th
    import time
    from types import SimpleNamespace

    from kubeml_tpu.ps.parameter_server import ParameterServer
    from kubeml_tpu.storage import HistoryStore
    from kubeml_tpu.utils import traced_http

    wire = WeightsWire("delta")

    def tree_at(v):
        fill = float(v)
        return {"a": np.full((64, 64), fill, np.float32),
                "b": np.full((128,), fill, np.float32)}

    wire.publish(tree_at(1), 1)

    class _Resp:
        def __init__(self, status, content=b"", version=None):
            from kubeml_tpu.engine.dataplane import VERSION_HEADER

            self.status_code = status
            self.content = content
            self.headers = ({VERSION_HEADER: str(version)}
                            if version is not None else {})

    def fake_get(url, **kw):
        since = None
        if "since=" in url:
            since = int(url.rsplit("since=", 1)[1])
        got = wire.get(since)
        if got is None:
            return _Resp(404)
        payload, version = got
        if payload == "current":
            return _Resp(204, version=version)
        return _Resp(200, payload, version=version)

    class _Model:
        def preprocess(self, x):
            return x

        def infer(self, variables, x):
            a, b = variables["a"], variables["b"]
            # (epoch the tree claims, cross-leaf mismatch): a mixed-epoch
            # tree shows up as a nonzero mismatch
            return np.array([float(a.flat[0]),
                             float(a.flat[0]) - float(b.flat[0])])

    ps = ParameterServer(history_store=HistoryStore(config=tmp_config),
                         config=tmp_config)
    ps.registry = SimpleNamespace(load=lambda name: _Model())
    record = SimpleNamespace(
        url="http://fake-runner",
        task=SimpleNamespace(parameters=SimpleNamespace(function_name="f")))

    orig_get = traced_http.get
    traced_http.get = fake_get
    try:
        stop = th.Event()
        errors, serves = [], []

        # warm jax dispatch once so the threaded window measures the wire,
        # not the first-call compile (1-core box)
        ps._infer_from_wire("wjob", record, [[0.0]])

        def writer():
            for v in range(2, 40):
                wire.publish(tree_at(v), v)
                time.sleep(0.02)
            stop.set()

        def reader():
            while not stop.is_set():
                try:
                    epoch, mismatch = ps._infer_from_wire(
                        "wjob", record, [[0.0]])
                    serves.append((epoch, mismatch))
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

        threads = [th.Thread(target=writer)] + [
            th.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # the shared decoder chain stayed sound: one more serve lands on
        # the final published version
        final = ps._infer_from_wire("wjob", record, [[0.0]])
    finally:
        traced_http.get = orig_get

    assert not errors, errors[:3]
    assert len(serves) > 20
    published = {float(v) for v in range(1, 40)}
    for epoch, mismatch in serves:
        assert mismatch == 0.0, "mixed-epoch tree served"
        assert epoch in published
    assert tuple(final) == (39.0, 0.0)
