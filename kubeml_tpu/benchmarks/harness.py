"""Shared benchmark/dryrun harness: flagship model selection + synthetic KubeModel.

Used by both ``bench.py`` (driver benchmark) and ``__graft_entry__.py``
(compile checks) so model selection and harness wiring cannot drift apart.

``vs_baseline`` denominators: primarily a MEASURED same-architecture torch
comparator (``benchmarks/comparator.py``, the reference's own methodology —
ml/experiments/common/experiment.py:263-337). Each flagship also carries a
conservative single-GPU samples/sec estimate for the reference's hardware
class (CUDA 10.1-era GPUs, torch 1.7: reference ml/environment/Dockerfile:1-31)
— a labeled FALLBACK used only when torch is unavailable, and reported
separately as the reference-class ratio. A LeNet fallback is normalized
against a LeNet figure, never a ResNet one.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

# the bench regression gate's metric vocabulary (scripts/bench_compare.py):
# normalized key -> (source field in the bench JSON line, direction).
# serving_fraction_of_one_shot rides SERVING rows (benchmarks/serving.py
# fraction_of_batchN — the long-workload continuous-batching ratio); train
# rows don't
# carry the field, so the gate skips it there instead of failing.
#
# DIRECTION is per metric, not assumed: "higher" means a drop beyond the
# threshold regresses (throughputs, ratios), "lower" means a RISE does
# (latencies). The compare code reads this table, so the spec-decode gate
# (tokens/step, acceptance — benchmarks/spec_decode.py rows) and the
# serving-fraction gate share one code path.
GATE_METRICS = {
    "device_samples_per_sec": ("value", "higher"),
    "end_to_end_samples_per_sec": ("end_to_end", "higher"),
    "mfu": ("mfu", "higher"),
    "serving_fraction_of_one_shot": ("fraction_of_batchN", "higher"),
    # speculative decoding (results/spec_decode.jsonl rows): emitted tokens
    # per verify step and the drafter's acceptance rate — a drafter
    # regression (worse acceptance, fewer tokens/step) fails the gate
    "spec_tokens_per_step": ("spec_tokens_per_step", "higher"),
    "spec_accept_ratio": ("spec_accept_ratio", "higher"),
    # serving latency rides the same table with the opposite direction
    "serving_latency_p95_ms": ("latency_p95_ms", "lower"),
    # paged-attention decode-step cost (results/paged_attn.jsonl rows,
    # benchmarks/paged_attn_bench.py): per-step wall time of the paged
    # decode read path — the live-width clamp / Pallas page-walk kernel
    # regress the gate if a candidate's step gets slower
    "paged_decode_step_ms": ("decode_step_ms", "lower"),
    # int8 KV-page capacity win (paged_attn_bench --serving capacity row):
    # tokens admitted under KUBEML_KV_QUANT=int8 over tokens admitted
    # unquantized at the SAME arena byte budget. The gate baseline carries
    # the ideal storage ratio (2.0 for bf16 arenas), so the 10% threshold
    # holds the measured candidate to >= ~1.8x admitted tokens.
    "kv_quant_capacity_ratio": ("kv_quant_capacity_ratio", "higher"),
    # chunked prefill (results/chunked_prefill.jsonl rows,
    # benchmarks/scenarios.py run_chunked_prefill): head-of-line decode
    # seconds charged per completed request on the mixed short/long
    # workload — the number KUBEML_PREFILL_CHUNK_TOKENS exists to push
    # down; a candidate whose chunking regresses (more stall per request)
    # fails the gate
    "serving_hol_stall_per_request": ("hol_stall_seconds_per_request",
                                      "lower"),
}


def metric_direction(key: str) -> str:
    """The gate direction for a normalized metric key ("higher"/"lower")."""
    return GATE_METRICS[key][1]


def normalize_bench_row(doc: dict) -> Dict[str, Optional[float]]:
    """One normalized metric row from a bench record — either the driver's
    raw one-JSON-line output of ``bench.py`` or the driver's wrapper
    holding it under ``parsed``. Missing/unreported metrics come
    back None (the regression gate skips them rather than failing on an
    unknown-hardware MFU); an error row keeps its ``error`` so the gate can
    fail a broken candidate outright."""
    row = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    out: Dict[str, Optional[float]] = {"metric": row.get("metric")}
    for key, (field_name, _direction) in GATE_METRICS.items():
        v = row.get(field_name)
        try:
            out[key] = float(v) if v is not None else None
        except (TypeError, ValueError):
            out[key] = None
    if row.get("error"):
        out["error"] = str(row["error"])
    return out


@dataclass(frozen=True)
class Flagship:
    module: object
    sample_shape: Tuple[int, ...]
    name: str
    num_classes: int
    # conservative reference single-GPU throughput (samples/sec): the labeled
    # ESTIMATE fallback — the measured denominator comes from baseline_for()
    baseline_sps: float


def baseline_for(fs: Flagship) -> Tuple[float, dict]:
    """The ``vs_baseline`` denominator for a flagship: the measured torch
    comparator when available (with its provenance row), else the
    hardware-class constant (labeled estimate)."""
    try:
        from .comparator import measured_baseline

        row = measured_baseline(fs.name)
    except Exception:
        # measured_baseline itself returns None when torch is absent; an
        # exception here is a real comparator bug — fall back, but LOUDLY
        import logging

        logging.getLogger("kubeml.bench").exception(
            "torch comparator failed; falling back to the hardware-class "
            "estimate")
        row = None
    if row and row.get("samples_per_sec", 0) > 0:
        return float(row["samples_per_sec"]), row
    return fs.baseline_sps, {
        "model": fs.name,
        "samples_per_sec": fs.baseline_sps,
        "method": "hardware-class estimate (reference-era single GPU); "
                  "fallback — torch comparator unavailable",
    }


def flagship(dtype=None) -> Flagship:
    """The headline benchmark model: ResNet-18/CIFAR-10 (BASELINE.md target
    #2). ``KUBEML_FLAGSHIP=lenet`` selects LeNet/MNIST (target #1) instead —
    a diagnostic knob (e.g. driving the full bench body on a CPU dev box,
    where the ResNet round is minutes of compute per rep).

    ``dtype`` selects the computation precision (e.g. ``jnp.bfloat16`` for the
    MXU's native mixed-precision passes); None = model default (f32)."""
    import os

    kw = {} if dtype is None else {"dtype": dtype}
    if os.environ.get("KUBEML_FLAGSHIP", "").lower() == "lenet":
        from ..models.lenet import LeNet

        return Flagship(
            module=LeNet(num_classes=10, **kw),
            sample_shape=(28, 28, 1),
            name="lenet-mnist",
            num_classes=10,
            baseline_sps=20000.0,  # LeNet is tiny; GPUs push O(10k) samples/sec
        )
    from ..models.resnet import ResNet18

    return Flagship(
        module=ResNet18(num_classes=10, **kw),
        sample_shape=(32, 32, 3),
        name="resnet18-cifar10",
        num_classes=10,
        baseline_sps=1000.0,  # ResNet-class model, single 2020-era GPU
    )


def make_synthetic_model(module, dataset_name: str = "synthetic",
                         uint8_inputs: bool = False):
    """Wrap a Flax module in a KubeModel over a placeholder dataset (the
    harness feeds data directly, so the dataset is never attached).

    ``uint8_inputs=True`` installs the device-side dequantize preprocess
    (uint8 [0,255] -> bf16 [-1,1]) so the host stages quantized images — 4x
    fewer host->HBM bytes than f32."""
    import jax.numpy as jnp
    import optax

    from ..data.dataset import KubeDataset
    from ..runtime.model import KubeModel

    class _SyntheticDataset(KubeDataset):
        def __init__(self):
            super().__init__(dataset_name)

    class _SyntheticModel(KubeModel):
        def __init__(self):
            super().__init__(_SyntheticDataset())

        def build(self):
            return module

        def configure_optimizers(self):
            return optax.sgd(self.lr, momentum=0.9)

        if uint8_inputs:
            def preprocess(self, x):
                return x.astype(jnp.bfloat16) / 127.5 - 1.0

    return _SyntheticModel()
