"""Multi-host distributed helpers (single-process behavior + layout math) and
deploy asset sanity."""

import json
from pathlib import Path

import jax
import pytest

from kubeml_tpu.parallel.distributed import (
    global_mesh,
    init_distributed,
    local_batch_slice,
    num_slices,
)

REPO = Path(__file__).resolve().parent.parent


def test_init_distributed_single_process_noop(monkeypatch):
    monkeypatch.delenv("KUBEML_COORDINATOR", raising=False)
    monkeypatch.delenv("KUBEML_NUM_PROCESSES", raising=False)
    assert init_distributed() is False
    # still a working single-process jax
    assert jax.process_count() == 1


def test_one_host_tpu_vm_is_not_a_pod(monkeypatch):
    """A single TPU VM exports the pod variables with ONE host in them: that
    must stay single-process without ever calling jax.distributed (only an
    environment naming several hosts is auto-detected, and its failure is
    then fatal, not swallowed)."""
    from kubeml_tpu.parallel import distributed

    assert distributed._pod_hosts({}) == 0
    assert distributed._pod_hosts({"TPU_WORKER_HOSTNAMES": "localhost"}) == 1
    assert distributed._pod_hosts(
        {"TPU_WORKER_HOSTNAMES": "10.0.0.2,10.0.0.3"}) == 2
    assert distributed._pod_hosts(
        {"TPU_PROCESS_ADDRESSES": "a:8476,b:8476,c:8476,d:8476"}) == 4
    assert distributed._pod_hosts(
        {"MEGASCALE_COORDINATOR_ADDRESS": "10.0.0.2"}) > 1
    monkeypatch.delenv("KUBEML_COORDINATOR", raising=False)
    monkeypatch.delenv("KUBEML_NUM_PROCESSES", raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setenv("CLOUD_TPU_TASK_ID", "0")
    monkeypatch.setattr(jax.distributed, "initialize", lambda *a, **k: (
        _ for _ in ()).throw(AssertionError("initialize() on one host")))
    assert init_distributed() is False


def test_num_slices_cpu_is_one():
    assert num_slices() == 1


def test_global_mesh_single_slice_fallback():
    mesh = global_mesh(tp=2, sp=2)
    assert mesh.shape["tp"] == 2 and mesh.shape["sp"] == 2
    assert mesh.shape["dp"] == len(jax.devices()) // 4
    # all global devices accounted for
    assert mesh.devices.size == len(jax.devices())


def test_local_batch_slice_single_process():
    start, end = local_batch_slice(64)
    assert (start, end) == (0, 64)


def test_global_mesh_rejects_bad_model_factor():
    # model axes exceeding the device count must fail loudly via mesh_shape_for
    with pytest.raises(ValueError):
        global_mesh(tp=64)


# --- hybrid DCN x ICI layout math (multi-slice; CPU reports one slice, so the
# pure factorization is covered directly and the grid via fake devices) ---


def test_hybrid_mesh_shapes_dp_across_slices():
    from kubeml_tpu.parallel.distributed import hybrid_mesh_shapes

    # 2 slices x 4 chips: dp=4 splits as 2 per slice (ICI) x 2 slices (DCN)
    names, ici, dcn = hybrid_mesh_shapes({"dp": 4, "tp": 2}, n_slices=2,
                                         n_devices=8)
    assert names == ("dp", "tp")
    assert ici == [2, 2]
    assert dcn == [2, 1]


def test_hybrid_mesh_shapes_properties():
    """For every legal (shape, slices) combination: elementwise
    ici*dcn == requested shape; only the dcn_axis crosses slices; the ICI
    factor covers exactly one slice's devices."""
    import numpy as np

    from kubeml_tpu.parallel.distributed import hybrid_mesh_shapes

    for n_slices in (2, 4):
        for per_slice in (4, 8):
            n_devices = n_slices * per_slice
            for tp in (1, 2, 4):
                for sp in (1, 2):
                    model = tp * sp
                    if per_slice % model:
                        continue
                    dp = n_devices // model
                    if dp % n_slices:
                        continue
                    shape = {"dp": dp, "sp": sp, "tp": tp}
                    names, ici, dcn = hybrid_mesh_shapes(
                        shape, n_slices, n_devices
                    )
                    for ax, i, d in zip(names, ici, dcn):
                        assert i * d == shape[ax]
                        if ax != "dp":
                            assert d == 1  # model axes never cross DCN
                    assert int(np.prod(ici)) == per_slice
                    assert int(np.prod(dcn)) == n_slices


def test_hybrid_mesh_shapes_rejections():
    from kubeml_tpu.parallel.distributed import hybrid_mesh_shapes

    with pytest.raises(ValueError):  # dcn axis absent from the shape
        hybrid_mesh_shapes({"tp": 8}, n_slices=2, n_devices=8)
    with pytest.raises(ValueError):  # model axes don't divide one slice
        hybrid_mesh_shapes({"dp": 2, "tp": 3}, n_slices=2, n_devices=8)
    with pytest.raises(ValueError):  # dp not divisible by slice count
        hybrid_mesh_shapes({"dp": 3, "tp": 2}, n_slices=2, n_devices=12)


def test_hybrid_grid_places_model_axes_within_slices():
    """Drive mesh_utils.create_hybrid_device_mesh with FAKE 2-slice devices:
    in the resulting grid every tp-neighbor pair shares a slice (ICI) and the
    dp axis walks across slices (DCN) — the scaling-book layout rule."""
    from dataclasses import dataclass

    import numpy as np
    from jax.experimental import mesh_utils

    from kubeml_tpu.parallel.distributed import hybrid_mesh_shapes

    @dataclass(frozen=True)
    class FakeDev:
        id: int
        process_index: int
        slice_index: int
        platform: str = "cpu"
        device_kind: str = "fake"

    n_slices, per_slice = 2, 4
    devs = [FakeDev(i, i // per_slice, i // per_slice)
            for i in range(n_slices * per_slice)]
    names, ici, dcn = hybrid_mesh_shapes({"dp": 4, "tp": 2}, n_slices,
                                         len(devs))
    grid = mesh_utils.create_hybrid_device_mesh(ici, dcn, devices=devs)
    slices = np.vectorize(lambda d: d.slice_index)(grid)  # [dp, tp]
    # tp pairs stay within one slice
    assert (slices[:, 0] == slices[:, 1]).all()
    # dp axis spans both slices
    assert set(slices[:, 0].tolist()) == {0, 1}


# --- deploy assets ---


def test_grafana_dashboard_parses_and_covers_reference_panels():
    d = json.loads((REPO / "deploy/grafana/kubeml-dashboard.json").read_text())
    titles = {p["title"] for p in d["panels"]}
    assert {"Running jobs", "Train loss", "Validation loss",
            "Validation accuracy (%)", "Parallelism", "Epoch duration (s)"} <= titles
    exprs = [t["expr"] for p in d["panels"] for t in p["targets"]]
    for metric in ("kubeml_job_train_loss", "kubeml_job_validation_loss",
                   "kubeml_job_validation_accuracy", "kubeml_job_parallelism",
                   "kubeml_job_epoch_duration_seconds", "kubeml_job_running_total"):
        assert any(metric in e for e in exprs), metric


def test_dashboard_metrics_exist_in_registry():
    """Every metric the dashboard queries is one the PS actually exports."""
    from kubeml_tpu.ps.metrics import MetricsRegistry
    from kubeml_tpu.api.types import MetricUpdate

    from kubeml_tpu.serving.stats import DecoderStats

    reg = MetricsRegistry()
    reg.task_started("train")
    reg.update(MetricUpdate(job_id="j", train_loss=1.0, validation_loss=2.0,
                            accuracy=50.0, parallelism=2, epoch_duration=1.5,
                            round_seconds=[0.2], merge_seconds=0.05,
                            round_divergence=[0.01], round_loss_spread=[0.1],
                            round_skew_ratio=1.5))
    # scale-decision counters (the decisions-by-reason panel queries them)
    reg.set_decision_source(lambda: {("up", "speedup"): 1})
    # serving traffic so the histogram _bucket series render too (the
    # dashboard's histogram_quantile panels query those directly)
    stats = DecoderStats(slots=2)
    stats.completed(0.2)
    stats.first_token(0.05)
    stats.chunk_fetched(0.1, 10)
    stats.fetch_started()
    stats.fetch_finished(0.01)
    # lifecycle-phase + occupancy histograms (PR 11 panels query them)
    for phase in ("queue_wait", "prefill", "decode_active", "slot_idle"):
        stats.phase(phase, 0.01)
    stats.chunk_occupancy(8, live=10, dead=2, idle=4)
    stats.admit_tokens(real=6, padding=10)
    stats.emitted(4)
    # one speculative verify step so the acceptance-ratio histogram's
    # _bucket series renders (the spec acceptance panel queries it)
    stats.spec_step(drafted=8, accepted=6, proposed=10)
    # one decode chunk's KV reads so the achieved-bandwidth histogram's
    # _bucket series renders (the KV-read panel queries it); the
    # paged_attn gauge rides the snapshot like the engine's telemetry
    stats.kv_read(1 << 20, 0.01)
    # latency-anatomy signals (PR 18 panels: ITL quantiles + histogram,
    # HOL stall rate, the cause-split decode histogram, per-program
    # compiles and the cold-start/compile quantile panels)
    stats.chunk_fetched(0.09, 8, colocated=True)
    stats.inter_token(0.02)
    stats.hol_stall(0.1, 2)
    stats.cold_start(0.5)
    if stats.compile_begin("step", (8,)):
        stats.compiled("step", 0.4)
    # serving-recovery signals (ISSUE 20 panels: snapshot counters + size/
    # latency histograms, pool-audit watchdog counters, draining gauge)
    stats.snapshot_save(1 << 16, 0.01)
    stats.snapshot_restore(1 << 16, 0.02)
    stats.snapshot_replay(2)
    stats.snapshot_fail()
    stats.pool_audit(True)
    stats.pool_audit(False)
    snap = stats.snapshot()
    snap["paged_attn_kernel"] = 0.0
    snap["draining"] = 0.0
    reg.set_serving_source(lambda: {"m": snap})
    # SLO burn/state gauges (the burn-rate and alert-state panels)
    reg.set_slo_source(lambda: {"burn": {("o", "fast"): 0.5},
                                "state": {"o": 0}})
    # one blocking data-plane transfer so the staging-bandwidth _bucket
    # series renders (the dashboard's bandwidth quantile panel queries it)
    from kubeml_tpu.utils import profiler

    profiler.account("dash-test", 1000, 0.1)
    # and one retried transfer: kubeml_dataplane_retries_total renders only
    # when a retry happened (the dashboard's torn-fetch panel queries it)
    profiler.record_retry("dash-test")
    try:
        text = reg.render()
    finally:
        profiler.reset_accounting()
    d = json.loads((REPO / "deploy/grafana/kubeml-dashboard.json").read_text())
    import re

    for p in d["panels"]:
        for t in p["targets"]:
            # extract bare metric identifiers from arbitrary promQL (sum,
            # rate, label selectors all strip away)
            names = re.findall(r"kubeml_[a-z0-9_]+", t["expr"])
            assert names, f"no metric in expr {t['expr']!r}"
            for name in names:
                assert name in text, \
                    f"dashboard queries unknown metric {name}"


def test_prometheus_and_systemd_assets_exist():
    assert (REPO / "deploy/prometheus.yml").read_text().strip()
    unit = (REPO / "deploy/systemd/kubeml.service").read_text()
    assert "kubeml_tpu.cli start" in unit
