"""The BASELINE.md job shapes, small, through the real scheduler -> PS ->
TrainJob path (port of the reference's experiment harness,
ml/experiments/common/experiment.py; the driver is tests/scenario_support.py)."""

import numpy as np
import pytest

from scenario_support import (
    ExperimentDriver,
    Scenario,
    _req,
    scenarios,
    synth_images,
    synth_tokens,
)


def test_synthetic_generators():
    x, y = synth_images(32, (28, 28, 1), 10, seed=0)
    assert x.shape == (32, 28, 28, 1) and y.shape == (32,)
    # quantized at rest like real image datasets; dequant happens on device
    assert x.dtype == np.uint8 and 0 <= y.min() and y.max() < 10
    # the class signal (brightest 2-row band) survives quantization
    band_means = x[:, :20].astype(np.float32).reshape(32, 10, -1).mean(axis=2)
    assert (band_means.argmax(axis=1) == y).mean() > 0.9
    t, ty = synth_tokens(16, 24, 100, 2, seed=0)
    assert t.shape == (16, 24) and (t[:, -2:] == 0).all()
    assert set(np.unique(ty)) <= {0, 1}


def test_scenario_definitions_cover_baseline():
    names = [s.name for s in scenarios()]
    assert names == ["digits-real", "lenet-mnist", "resnet18-cifar10",
                     "vit-cifar100", "bert-sst2", "gpt-lm-spmd"]
    for s in scenarios():
        assert s.function_source.strip()
        assert s.request.dataset and s.request.function_name


def test_digits_real_is_real_data_and_converges(tmp_config):
    """The digits-real scenario trains on ACTUAL handwritten digits (sklearn's
    UCI corpus, not a synthetic band task) and learns them through the live
    control plane — the in-environment real-data convergence check."""
    sc = {s.name: s for s in scenarios()}["digits-real"]
    xtr, ytr, xte, yte = sc.make_data()
    assert len(xtr) + len(xte) == 1797  # the real corpus, nothing synthetic
    assert xtr.shape[1:] == (8, 8, 1) and xtr.max() <= 16
    assert set(np.unique(ytr)) == set(range(10))
    with ExperimentDriver(tmp_config) as driver:
        result = driver.run(sc)
    assert result.status == "ok", result.error
    # real learning: 5 epochs beat the 10% chance floor by a wide margin
    assert result.accuracy and result.accuracy[-1] > 60.0, result.accuracy


@pytest.mark.parametrize("name", ["lenet-mnist", "bert-sst2", "gpt-lm-spmd"])
def test_single_scenario_quick(tmp_config, name):
    sc = {s.name: s for s in scenarios()}[name]
    with ExperimentDriver(tmp_config) as driver:
        result = driver.run(sc)
    assert result.status == "ok", result.error
    assert result.epochs >= 1
    assert all(np.isfinite(l) for l in result.train_loss)
    assert len(result.epoch_seconds) == result.epochs


def test_elastic_multijob_quick(tmp_config):
    with ExperimentDriver(tmp_config, max_parallelism=4) as driver:
        result = driver.run_elastic_multijob()
    assert result.status == "ok", result.error
    # two jobs, >= 2 epochs each
    assert result.epochs >= 4
    assert len(result.parallelism) == result.epochs
    assert all(p >= 1 for p in result.parallelism)


def test_failed_job_reported_as_failed(tmp_config):
    """A job that errors must surface status='failed' with the recorded error —
    a broken run must never look green."""
    # imports cleanly (passes create-time validation) but fails at job start
    broken_src = (
        "from kubeml_tpu.runtime.model import KubeModel\n"
        "from kubeml_tpu.data.dataset import KubeDataset\n"
        "class Ds(KubeDataset):\n"
        "    def __init__(self):\n"
        "        super().__init__('broken-ds')\n"
        "class Model(KubeModel):\n"
        "    def __init__(self):\n"
        "        raise RuntimeError('intentionally broken model')\n"
        "    def build(self):\n"
        "        pass\n"
    )
    broken = Scenario(
        "broken", broken_src,
        lambda: synth_images(64, (8, 8, 1), 4, 0) + synth_images(32, (8, 8, 1), 4, 1),
        _req("broken", "broken-ds", epochs=1,
             options=dict(default_parallelism=1, static_parallelism=True)),
    )
    with ExperimentDriver(tmp_config) as driver:
        result = driver.run(broken)
    assert result.status in ("failed", "error"), result
    assert result.error
