"""Model zoo tests: forward shapes, mutable-state handling, and one full
K-AVG sync round per family (tiny configs; 8-dev CPU mesh from conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.engine.kavg import KAvgTrainer
from kubeml_tpu.runtime.model import make_synthetic_model


def _forward(module, x, train=False, seed=0):
    variables = module.init(jax.random.PRNGKey(seed), x, train=False)
    mutable = [k for k in variables if k != "params"]
    if train and mutable:
        out, _ = module.apply(variables, x, train=True, mutable=mutable,
                              rngs={"dropout": jax.random.PRNGKey(1)})
    else:
        out = module.apply(variables, x, train=False)
    return variables, out


class TestForwardShapes:
    def test_resnet18(self):
        from kubeml_tpu.models.resnet import ResNet18

        x = jnp.zeros((2, 32, 32, 3))
        variables, out = _forward(ResNet18(num_classes=10), x)
        assert out.shape == (2, 10)
        assert "batch_stats" in variables  # BN state must be a mutable collection

    def test_resnet34_imagenet_stem(self):
        from kubeml_tpu.models.resnet import ResNet34

        x = jnp.zeros((1, 64, 64, 3))
        _, out = _forward(ResNet34(num_classes=100, cifar_stem=False), x)
        assert out.shape == (1, 100)

    def test_resnet50_bottleneck(self):
        from kubeml_tpu.models.resnet import ResNet50

        x = jnp.zeros((1, 32, 32, 3))
        _, out = _forward(ResNet50(num_classes=10), x)
        assert out.shape == (1, 10)

    def test_vgg11(self):
        from kubeml_tpu.models.vgg import VGG11

        x = jnp.zeros((2, 32, 32, 3))
        variables, out = _forward(VGG11(num_classes=100), x, train=True)
        assert out.shape == (2, 100)

    def test_vit_tiny(self):
        from kubeml_tpu.models.vit import ViT

        x = jnp.zeros((2, 32, 32, 3))
        _, out = _forward(ViT(num_classes=100, depth=2, embed_dim=64, num_heads=2), x)
        assert out.shape == (2, 100)

    def test_bert_tiny(self):
        from kubeml_tpu.models.bert import BertTiny

        ids = jnp.array([[5, 8, 9, 0, 0], [3, 0, 0, 0, 0]], jnp.int32)
        _, out = _forward(BertTiny(num_classes=2), ids)
        assert out.shape == (2, 2)

    def test_bert_padding_invariance(self):
        """Padding tokens must not change a sequence's logits."""
        from kubeml_tpu.models.bert import BertTiny

        m = BertTiny(num_classes=2)
        ids_short = jnp.array([[5, 8, 9, 0, 0]], jnp.int32)
        ids_long = jnp.array([[5, 8, 9, 0, 0, 0, 0, 0]], jnp.int32)
        variables = m.init(jax.random.PRNGKey(0), ids_long, train=False)
        out_short = m.apply(variables, ids_short, train=False)
        out_long = m.apply(variables, ids_long, train=False)
        np.testing.assert_allclose(np.asarray(out_short), np.asarray(out_long),
                                   atol=1e-5)


class TestAttentionOp:
    def test_masked_matches_reference_softmax(self):
        from kubeml_tpu.ops.attention import dot_product_attention

        r = np.random.default_rng(0)
        q = jnp.asarray(r.normal(size=(2, 4, 2, 8)).astype(np.float32))
        k = jnp.asarray(r.normal(size=(2, 6, 2, 8)).astype(np.float32))
        v = jnp.asarray(r.normal(size=(2, 6, 2, 8)).astype(np.float32))
        out = dot_product_attention(q, k, v)
        # reference computation via jax.nn.softmax
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
        expected = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_fully_masked_rows_are_zero(self):
        from kubeml_tpu.ops.attention import dot_product_attention

        q = jnp.ones((1, 2, 1, 4))
        k = jnp.ones((1, 3, 1, 4))
        v = jnp.ones((1, 3, 1, 4))
        mask = jnp.zeros((1, 1, 2, 3), bool)
        out = dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(out), 0.0)


class TestSyncRoundPerFamily:
    """One K-AVG round per family: trains, averages (incl. mutable state),
    and produces finite loss on the 8-device mesh."""

    def _round(self, module, sample_shape, classes=10, dtype=np.float32, n=4, k=2, b=4):
        model = make_synthetic_model(module)
        trainer = KAvgTrainer(model, precision="f32")
        r = np.random.default_rng(0)
        if np.issubdtype(dtype, np.integer):
            x = r.integers(1, 50, size=(n, k, b, *sample_shape)).astype(dtype)
        else:
            x = r.normal(size=(n, k, b, *sample_shape)).astype(dtype)
        y = r.integers(0, classes, size=(n, k, b)).astype(np.int64)
        mask = np.ones((n, k, b), np.float32)
        rng = jax.random.PRNGKey(0)
        variables = trainer.init_variables(rng, x[0, 0], n)
        variables, loss = trainer.sync_round(variables, x, y, mask, rng, lr=0.01)
        assert np.isfinite(float(loss))
        # post-sync replicas identical
        leaves = jax.tree.leaves(variables)
        for leaf in leaves[:3]:
            first = np.asarray(leaf[0])
            for w in range(1, leaf.shape[0]):
                np.testing.assert_allclose(np.asarray(leaf[w]), first, rtol=1e-5, atol=1e-6)

    def test_resnet18_round(self):
        from kubeml_tpu.models.resnet import ResNet18

        self._round(ResNet18(num_classes=10), (16, 16, 3))

    def test_vit_round(self):
        from kubeml_tpu.models.vit import ViT

        self._round(ViT(num_classes=10, depth=2, embed_dim=32, num_heads=2, patch_size=4),
                    (16, 16, 3))

    def test_bert_round(self):
        from kubeml_tpu.models.bert import BertTiny

        self._round(BertTiny(num_classes=2, vocab_size=100), (16,), classes=2,
                    dtype=np.int32)


class TestMixedPrecision:
    """bf16 computation dtype: params stay f32 masters, logits come back f32,
    and a K-AVG round still trains to a finite loss."""

    def _check(self, module, sample_shape, dtype=np.float32):
        r = np.random.default_rng(0)
        if np.issubdtype(dtype, np.integer):
            x = jnp.asarray(r.integers(1, 50, size=(4, *sample_shape)).astype(dtype))
        else:
            x = jnp.asarray(r.normal(size=(4, *sample_shape)).astype(dtype))
        variables = module.init(jax.random.PRNGKey(0), x, train=False)
        for leaf in jax.tree.leaves(variables["params"]):
            assert leaf.dtype == jnp.float32, "params must be f32 masters"
        logits = module.apply(variables, x, train=False)
        assert logits.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(logits)))

    def test_resnet18_bf16(self):
        from kubeml_tpu.models.resnet import ResNet18

        self._check(ResNet18(num_classes=10, dtype=jnp.bfloat16), (16, 16, 3))

    def test_lenet_bf16(self):
        from kubeml_tpu.models.lenet import LeNet

        self._check(LeNet(num_classes=10, dtype=jnp.bfloat16), (28, 28, 1))

    def test_vgg11_bf16(self):
        from kubeml_tpu.models.vgg import VGG11

        self._check(VGG11(num_classes=10, dtype=jnp.bfloat16), (32, 32, 3))

    def test_vit_bf16(self):
        from kubeml_tpu.models.vit import ViT

        self._check(ViT(num_classes=10, depth=2, embed_dim=32, num_heads=2,
                        patch_size=4, dtype=jnp.bfloat16), (16, 16, 3))

    def test_bert_bf16(self):
        from kubeml_tpu.models.bert import BertTiny

        self._check(BertTiny(num_classes=2, vocab_size=100, dtype=jnp.bfloat16),
                    (16,), dtype=np.int32)

    def test_gpt_bf16(self):
        from kubeml_tpu.models.gpt import GPTTiny

        self._check(GPTTiny(vocab_size=100, max_len=16, dtype=jnp.bfloat16),
                    (16,), dtype=np.int32)

    def test_moe_bf16(self):
        from kubeml_tpu.parallel.moe import MoETransformer

        self._check(
            MoETransformer(vocab_size=100, max_len=16, embed_dim=64, depth=2,
                           num_heads=4, moe_every=2, dtype=jnp.bfloat16),
            (16,), dtype=np.int32)

    def test_bf16_kavg_round_learns(self):
        """A bf16-compute LeNet actually reduces loss over a few K-AVG rounds."""
        from kubeml_tpu.models.lenet import LeNet

        model = make_synthetic_model(LeNet(num_classes=4, dtype=jnp.bfloat16))
        trainer = KAvgTrainer(model, precision="bf16")
        r = np.random.default_rng(1)
        n, k, b = 2, 2, 8
        # linearly separable-ish blobs so a few steps visibly reduce loss
        y = r.integers(0, 4, size=(n, k, b)).astype(np.int64)
        x = r.normal(size=(n, k, b, 28, 28, 1)).astype(np.float32) + y[..., None, None, None]
        mask = np.ones((n, k, b), np.float32)
        rng = jax.random.PRNGKey(0)
        variables = trainer.init_variables(rng, x[0, 0], n)
        first = last = None
        for i in range(6):
            variables, loss = trainer.sync_round(
                variables, x, y, mask, jax.random.fold_in(rng, i), lr=0.05
            )
            last = float(loss)
            if first is None:
                first = last
        assert np.isfinite(last)
        assert last < first
