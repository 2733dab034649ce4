"""Weight-only int8 decode (serving/quant.py, VERDICT r4 next-2).

Correctness bar: int8 decode through the batcher is TOKEN-IDENTICAL to
one-shot decode with the dequantized weights (same numbers, one engine vs
the other), the quantization error itself is bounded and reported, and the
HBM accounting shows the ~2x byte cut the throughput claim rests on."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeml_tpu.api.types import GenerateRequest
from kubeml_tpu.models.generation import generate
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.serving.batcher import BatchingDecoder
from kubeml_tpu.serving.quant import (
    QuantizedTensor, dequantize_tree, quality_report, quantize_tree,
    quantized_bytes)

VOCAB = 101


def tiny():
    return CausalTransformer(vocab_size=VOCAB, max_len=64, embed_dim=64,
                             depth=2, num_heads=4)


@pytest.fixture(scope="module")
def served():
    m = tiny()
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    return m, variables


def test_quantize_roundtrip_error_bounded(served):
    _, variables = served
    q = quantize_tree(variables)
    d = dequantize_tree(q, jnp.float32)
    import flax.linen as nn

    flat_ref = jax.tree.leaves(nn.meta.unbox(variables))
    flat_q = jax.tree.leaves(d)
    for a, b in zip(flat_ref, flat_q):
        a, b = np.asarray(a), np.asarray(b)
        if a.size >= 4096 and a.ndim >= 2:
            # per-channel symmetric int8: worst-case error is scale/2
            per_ch = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)),
                            keepdims=True) / 127.0
            assert np.all(np.abs(a - b) <= per_ch / 2 + 1e-6)
        else:
            np.testing.assert_array_equal(a, b)  # small leaves stay exact


def test_small_leaves_not_quantized(served):
    _, variables = served
    q = quantize_tree(variables)
    # LayerNorm scales/biases stay plain arrays
    ln = q["params"]["ln_f"]["scale"]
    assert not isinstance(ln, QuantizedTensor)
    # a big kernel is quantized to int8
    k = q["params"]["block_0"]["mlp_in"]["kernel"]
    assert isinstance(k, QuantizedTensor) and k.q.dtype == jnp.int8


def test_quantized_bytes_halved(served):
    _, variables = served
    dense = quantized_bytes(variables)
    quant = quantized_bytes(quantize_tree(variables))
    # f32 -> int8(+scales) is ~4x on the big leaves; whole-tree at least 2x
    assert quant < dense / 2


def test_int8_decoder_matches_oneshot_on_dequantized_weights(served):
    """The engine adds NO error beyond quantization itself: int8 batched
    decode == one-shot greedy decode run on the dequantized tree."""
    m, variables = served
    qd = dequantize_tree(quantize_tree(variables), jnp.float32)
    dec = BatchingDecoder(m, variables, slots=3, chunk_steps=4,
                          quantize="int8")
    try:
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, VOCAB, size=(1, int(l))).astype(np.int32)
                   for l in (4, 7, 9)]
        refs = [np.asarray(generate(m, qd, p, max_new_tokens=8).tokens)
                for p in prompts]
        entries = [dec.submit(GenerateRequest(prompts=p.tolist(),
                                              max_new_tokens=8))
                   for p in prompts]
        for e, ref in zip(entries, refs):
            assert dec.wait(e, timeout=300)["tokens"][0] == ref[0].tolist()
        assert dec.weight_bytes < quantized_bytes(variables) / 2
    finally:
        dec.close()


def test_native_int8_matmul_token_parity(served, monkeypatch):
    """KUBEML_INT8_MATMUL=1 (acceptance criterion): QuantizedTensor leaves
    flow INTO module.apply — no dense W~ in the step program — and greedy
    decode through the batcher stays token-identical to the one-shot oracle
    on the dequantized tree, for both the Pallas interpret kernel and the
    dot_general fallback."""
    from kubeml_tpu.api.config import Config, get_config, set_config

    m, variables = served
    qd = dequantize_tree(quantize_tree(variables), jnp.float32)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, size=(1, int(l))).astype(np.int32)
               for l in (3, 6, 10)]
    refs = [np.asarray(generate(m, qd, p, max_new_tokens=8).tokens)
            for p in prompts]
    prev = get_config()
    monkeypatch.setenv("KUBEML_INT8_MATMUL", "1")
    try:
        for impl in ("dot", "pallas"):
            monkeypatch.setenv("KUBEML_INT8_MATMUL_IMPL", impl)
            set_config(Config())
            # the engines read no process config: the knob travels as the
            # parameter server hands it over (_new_decoder)
            dec = BatchingDecoder(m, variables, slots=3, chunk_steps=4,
                                  quantize="int8",
                                  int8_matmul=get_config().int8_matmul)
            try:
                assert dec.int8_matmul  # the env knob reached the engine
                entries = [dec.submit(GenerateRequest(
                    prompts=p.tolist(), max_new_tokens=8)) for p in prompts]
                for e, ref in zip(entries, refs):
                    out = dec.wait(e, timeout=300)
                    assert out["tokens"][0] == ref[0].tolist(), impl
                # the byte accounting is untouched: weights stay s8
                assert dec.weight_bytes < quantized_bytes(variables) / 2
            finally:
                dec.close()
    finally:
        set_config(prev)


def test_native_int8_matmul_moe_falls_back(served, monkeypatch):
    """Modules the quant-aware dense layers don't cover (MoE expert
    stacks) must keep the dequantize path, loudly."""
    m = CausalTransformer(vocab_size=VOCAB, max_len=64, embed_dim=64,
                          depth=2, num_heads=4, moe_every=2)
    variables = m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    dec = BatchingDecoder(m, variables, slots=2, chunk_steps=4,
                          quantize="int8", int8_matmul=True)
    try:
        assert dec.int8_matmul is False
    finally:
        dec.close()


def test_quality_report_bounds(served):
    m, variables = served
    rng = np.random.default_rng(0)
    toks = rng.integers(1, VOCAB, size=(4, 16)).astype(np.int32)
    rep = quality_report(m, variables, toks)
    assert rep["rel_l2_err"] < 0.05
    assert rep["top1_agreement"] > 0.9
    assert rep["max_abs_err"] < 1.0


def test_int8_composes_with_serving_mesh(served):
    """int8 + tp mesh: quantization runs AFTER placement so the int8
    values inherit the kernel's tp sharding (and the per-channel scales
    shard with their channel axis); decode stays token-identical to the
    one-shot oracle on the host-dequantized tree."""
    import flax.linen as nn

    from kubeml_tpu.parallel.mesh import make_mesh

    m, variables = served
    mesh = make_mesh(shape={"tp": 2}, devices=jax.devices()[:2])
    qd = dequantize_tree(quantize_tree(variables), jnp.float32)
    p = np.arange(1, 9, dtype=np.int32)[None]
    ref = np.asarray(generate(m, qd, p, max_new_tokens=8).tokens)
    dec = BatchingDecoder(m, variables, slots=2, chunk_steps=4, mesh=mesh,
                          quantize="int8")
    try:
        r = dec.wait(dec.submit(GenerateRequest(prompts=p.tolist(),
                                                max_new_tokens=8)),
                     timeout=300)
        assert r["tokens"][0] == ref[0].tolist()
        leaf = nn.meta.unbox(
            dec._variables)["params"]["block_0"]["mlp_in"]["kernel"]
        assert isinstance(leaf, QuantizedTensor)
        assert str(leaf.q.dtype) == "int8"
        from jax.sharding import PartitionSpec as P

        assert leaf.q.sharding.spec == P(None, "tp")
        # the per-channel scales shard WITH their channel axis (the claim
        # the docs make; a silent gather/replicate must fail here)
        assert leaf.s.sharding.spec == P(None, "tp")
    finally:
        dec.close()


def test_ps_quantize_knob(tmp_config):
    """KUBEML_SERVING_QUANTIZE=int8 routes finished-model /generate through
    an int8 decoder (and the telemetry shows the byte cut)."""
    from kubeml_tpu.api.config import Config
    from kubeml_tpu.api.types import TrainOptions, TrainRequest, TrainTask
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.ps.parameter_server import ParameterServer
    from kubeml_tpu.storage import ShardStore

    store = ShardStore(config=tmp_config)
    r = np.random.default_rng(0)
    x = r.integers(1, 64, size=(128, 16)).astype(np.int32)
    store.create("tokens", x, np.zeros(128, np.int64),
                 x[:32], np.zeros(32, np.int64))
    reg = FunctionRegistry(config=tmp_config)
    reg.create("lmfn", LM_FN)
    cfg = Config(data_root=tmp_config.data_root, serving_quantize="int8")
    ps = ParameterServer(registry=reg, store=store, config=cfg)
    req = TrainRequest(batch_size=16, epochs=1, dataset="tokens", lr=1e-3,
                       function_name="lmfn",
                       options=TrainOptions(engine="spmd", precision="f32",
                                            validate_every=0))
    ps.start_task(TrainTask(job_id="qjob", parameters=req))
    assert ps.wait("qjob", timeout=400)
    out = ps.generate("qjob", GenerateRequest(prompts=[[1, 2, 3]],
                                              max_new_tokens=6))
    assert len(out["tokens"][0]) == 6
    dec = ps._decoders["qjob"][0]
    assert dec.quantize == "int8"
    assert 'kubeml_serving_weight_bytes{model="qjob"}' in ps.metrics.render()


LM_FN = """
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
        return CausalTransformer(vocab_size=64, max_len=16, embed_dim=32,
                                 depth=2, num_heads=4, mesh=self.mesh)
    def configure_optimizers(self):
        return optax.adamw(self.lr)
"""


def test_storage_tree_roundtrip(served):
    from kubeml_tpu.serving.quant import (from_storage_tree,
                                          is_quantized_storage,
                                          to_storage_tree)

    _, variables = served
    q = quantize_tree(variables)
    storage = to_storage_tree(q)
    assert is_quantized_storage(storage)
    back = from_storage_tree(storage)
    for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # plain trees pass through untouched
    assert not is_quantized_storage({"params": {"w": np.ones(3)}})


def _assert_trees_bit_exact(a, b):
    """Same structure, same dtypes, byte-identical leaf values."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


def test_quantized_sharded_checkpoint_roundtrip(served, tmp_path):
    """int8 leaves through the sharded store's host-assembly restore: the
    storage-form tree comes back with q int8 / s f32 BIT-EXACT — a lossy
    hop here would silently corrupt every final-int8 serve."""
    from kubeml_tpu.serving.quant import from_storage_tree, to_storage_tree
    from kubeml_tpu.storage.sharded_checkpoint import ShardedCheckpointStore

    _, variables = served
    q = quantize_tree(variables)
    store = ShardedCheckpointStore(root=tmp_path)
    store.save("qjob", jax.tree.map(np.asarray, to_storage_tree(q)),
               epoch=1, tag="final-int8")
    back = from_storage_tree(store.restore("qjob", "final-int8").variables)
    kernel = back["params"]["block_0"]["mlp_in"]["kernel"]
    assert isinstance(kernel, QuantizedTensor)
    assert kernel.q.dtype == np.int8 and kernel.s.dtype == np.float32
    _assert_trees_bit_exact(q, back)


def test_quantized_sharded_checkpoint_slicewise_restore_on_mesh(served,
                                                                tmp_path):
    """The SLICE-WISE path: restore the int8 storage tree straight onto a
    tp=2 serving mesh through storage_shardings — QuantizedTensor leaves
    land sharded (q with its kernel's spec, s with its channel axis) and
    stay bit-exact against the host tree."""
    from jax.sharding import PartitionSpec as P

    from kubeml_tpu.parallel.mesh import make_mesh
    from kubeml_tpu.serving.batcher import storage_shardings
    from kubeml_tpu.serving.quant import from_storage_tree, to_storage_tree
    from kubeml_tpu.storage.sharded_checkpoint import ShardedCheckpointStore

    m, variables = served
    q = quantize_tree(variables)
    store = ShardedCheckpointStore(root=tmp_path)
    store.save("qjob", jax.tree.map(np.asarray, to_storage_tree(q)),
               epoch=1, tag="final-int8")
    mesh = make_mesh(shape={"tp": 2}, devices=jax.devices()[:2])
    manifest = store.read_manifest("qjob", "final-int8")
    sh = storage_shardings(manifest["leaves"], m, mesh)
    back = from_storage_tree(store.restore("qjob", "final-int8",
                                           shardings=sh).variables)
    kernel = back["params"]["block_0"]["mlp_in"]["kernel"]
    assert isinstance(kernel, QuantizedTensor)
    assert str(kernel.q.dtype) == "int8"
    assert kernel.q.sharding.spec == P(None, "tp")
    assert kernel.s.sharding.spec == P(None, "tp")
    _assert_trees_bit_exact(q, back)


def test_quantized_tree_native_weights_roundtrip(served):
    """int8 leaves through the native TensorStore publish/fetch seqlock
    (the standalone-runner live-serving channel): bit-exact q/s."""
    from kubeml_tpu.native.weights import fetch_variables, publish_variables
    from kubeml_tpu.serving.quant import from_storage_tree, to_storage_tree

    class MemKV:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = np.asarray(v)

        def get(self, k):
            return self.d.get(k)

    _, variables = served
    q = quantize_tree(variables)
    kv = MemKV()
    publish_variables(kv, jax.tree.map(np.asarray, to_storage_tree(q)),
                      version=3)
    tree, version = fetch_variables(kv)
    assert version == 3
    back = from_storage_tree(tree)
    kernel = back["params"]["block_0"]["mlp_in"]["kernel"]
    assert isinstance(kernel, QuantizedTensor)
    assert kernel.q.dtype == np.int8 and kernel.s.dtype == np.float32
    _assert_trees_bit_exact(q, back)


@pytest.mark.slow
def test_quantized_checkpoint_serves_on_mesh(tmp_config):
    """The full no-dense-transient path: train (spmd tp=2, sharded final)
    -> offline `checkpoint quantize` -> int8+mesh serving restores the
    int8 values/scales SLICE-WISE onto the serving mesh (QuantizedTensor
    leaves, tp shardings) and produces the same greedy tokens as
    single-device int8 serving of the same export."""
    import flax.linen as nn

    from jax.sharding import PartitionSpec as P

    from kubeml_tpu.api.config import Config
    from kubeml_tpu.api.types import TrainOptions, TrainRequest, TrainTask
    from kubeml_tpu.controller.controller import Controller
    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.ps.parameter_server import ParameterServer
    from kubeml_tpu.serving.quant import INT8_TAG
    from kubeml_tpu.storage import ShardStore

    store = ShardStore(config=tmp_config)
    r = np.random.default_rng(0)
    x = r.integers(1, 64, size=(256, 16)).astype(np.int32)
    store.create("tokens", x, np.zeros(256, np.int64),
                 x[:64], np.zeros(64, np.int64))
    reg = FunctionRegistry(config=tmp_config)
    reg.create("lmfn", LM_FN)
    ps = ParameterServer(registry=reg, store=store, config=tmp_config)
    req = TrainRequest(batch_size=16, epochs=1, dataset="tokens", lr=1e-3,
                       function_name="lmfn",
                       options=TrainOptions(engine="spmd", precision="f32",
                                            validate_every=0,
                                            mesh_shape={"tp": 2},
                                            sharded_checkpoints=True))
    ps.start_task(TrainTask(job_id="qckpt", parameters=req))
    assert ps.wait("qckpt", timeout=600)

    ctl = Controller(None, None, registry=reg, config=tmp_config)

    class Req:
        params = {"id": "qckpt"}

        @staticmethod
        def arg(name):
            return None

    out = ctl._ckpt_quantize(Req)
    assert out["tag"] == INT8_TAG and out["form"] == "sharded"

    greq = dict(prompts=[[1, 2, 3], [9, 8, 7]], max_new_tokens=8)
    # single-device int8 serving of the final-int8 export
    cfg1 = Config(data_root=tmp_config.data_root, serving_quantize="int8")
    ps1 = ParameterServer(registry=FunctionRegistry(config=cfg1), config=cfg1)
    ref = ps1.generate("qckpt", GenerateRequest(**greq))
    dec1 = ps1._decoders["qckpt"][0]
    assert dec1.quantize == "int8"

    # int8 + tp=2 mesh serving of the SAME export
    cfg2 = Config(data_root=tmp_config.data_root, serving_quantize="int8",
                  serving_mesh="tp=2")
    ps2 = ParameterServer(registry=FunctionRegistry(config=cfg2), config=cfg2)
    outm = ps2.generate("qckpt", GenerateRequest(**greq))
    assert outm["tokens"] == ref["tokens"]
    dec2 = ps2._decoders["qckpt"][0]
    assert dec2.mesh is not None and dec2.quantize == "int8"
    leaf = nn.meta.unbox(
        dec2._variables)["params"]["block_0"]["mlp_in"]["kernel"]
    assert isinstance(leaf, QuantizedTensor)
    assert leaf.q.sharding.spec == P(None, "tp")
    assert leaf.s.sharding.spec == P(None, "tp")
