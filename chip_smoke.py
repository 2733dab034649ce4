#!/usr/bin/env python3
"""chip_smoke.py — the train -> serve path, once, on the chip, checked.

The quickest proof that the system still starts on a TPU. One command, no
arguments, from the repo root:

    python3 chip_smoke.py

It drives the primary deployment (the all-in-one ``LocalCluster`` that
``kubeml start`` boots, talked to over HTTP with the CLI's own client) through
one pass of what a user does, and fails on the first thing that is wrong:

``device``   jax's default backend must be a TPU (no CPU carry-on); the
             device kind must have an entry in the peaks table.
``kernels``  every Pallas kernel in ``kubeml_tpu/ops`` compiled for real at
             GPT-2-small widths and compared with its XLA oracle.
``train``    deploy a ResNet-18 function, upload a CIFAR-10-shaped dataset,
             train it with K-AVG (batch 128, K=8, one worker per chip)
             through controller -> scheduler -> PS -> TrainJob, checkpoint,
             and ``/infer`` on the finished job.
``serve``    GPT-2-small (768 x 12 heads x 12 layers, vocab 50257, context
             1024, bf16, seeded random weights) registered as a finished
             job and served under DEFAULT configuration: eight concurrent
             ``/generate`` requests through the paged engine, two of them on
             a shared 256-token prefix; first tokens checked against a plain
             forward pass.
``spmd``     (four chips only) a few ``--engine spmd`` steps of the same
             GPT on ``tp=2,dp=2``, weights split over ``tp``.

A chip belongs to one process, so the command itself never touches jax: it
runs the pass twice as two sequential child processes — ``cold`` then, once
that has exited, ``warm`` — and reports what the persistent compile cache
saved the second one. Exit code 0 only if every phase of both passes passed;
the last stdout line is then one JSON object with exactly the keys ``ok``
and ``device`` (``platform``, ``kind``, ``count``, as jax reports them); the
line before it is the summary (compile seconds cold vs warm, ``"claim": null``).
Datasets and weights are generated from seeds; nothing untracked is read.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass

PASSES = ("cold", "warm")
# both passes together must fit the driver's 1200 s; a pass still running
# when this budget is spent is killed (a hung chip must not outlive us)
BUDGET_SECONDS = 1150.0


@dataclass
class Sizes:
    """The shapes of one pass. The defaults ARE the smoke; another value is
    only ever passed by a debugging driver."""

    # train: ResNet-18, CIFAR, batch 128, K=8
    batch: int = 128
    k: int = 8
    rounds_per_epoch: int = 3
    epochs: int = 2
    image: tuple = (32, 32, 3)
    # serve: GPT-2-small at its published widths
    vocab: int = 50257
    context: int = 1024
    embed: int = 768
    heads: int = 12
    depth: int = 12
    new_tokens: int = 64
    prefix: int = 256              # the shared prompt prefix (whole pages)
    pair_suffixes: tuple = (24, 48)  # the two sharers' private tails
    warm_suffix: int = 32          # the request that plants the prefix
    prompts: tuple = (32, 60, 64, 120, 128, 512)  # the other six
    # spmd (four chips): a few steps of the same GPT on tp=2,dp=2
    spmd_seq: int = 512
    spmd_batch: int = 8
    spmd_steps: int = 3
    # kernels
    flash_len: int = 2048
    arena_pages: int = 513         # 8 slots x 64 pages + the trash page
    page_tokens: int = 16


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


# --------------------------------------------------------------------------
# compile accounting: the program's own compile clock (utils/tracing.py), its
# sum over all threads (the phases compile on the cluster's) read at each
# change of phase: seconds in the backend compiler (a persistent-cache hit
# lands here as its read time), programs, cache hits and writes
# --------------------------------------------------------------------------

def compile_row(before: dict, after: dict) -> dict:
    return {
        "compile_seconds": round(after["backend_s"] - before["backend_s"], 2),
        "programs": after["programs"] - before["programs"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        # jax counts a "miss" where it WRITES an entry
        "cache_writes": after["cache_misses"] - before["cache_misses"]}


# --------------------------------------------------------------------------
# phase: device
# --------------------------------------------------------------------------

def phase_device(cache_dir) -> dict:
    import jax
    import jaxlib

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: jax found platform {d0.platform!r} "
            f"({d0.device_kind}, {len(devices)} device(s)), not a TPU — "
            f"this smoke only passes on the chip")
    if jax.default_backend() != "tpu":
        # every `== "tpu"` branch in the product (kernel interpret flags,
        # slab donation, auto kernel selection) keys on this name
        raise SystemExit(
            f"chip_smoke: devices are TPUs but jax.default_backend() is "
            f"{jax.default_backend()!r}")
    from kubeml_tpu.utils.roofline import hbm_bandwidth, peak_flops

    peak, bw = peak_flops(d0), hbm_bandwidth(d0)
    if not peak or not bw:
        raise SystemExit(
            f"chip_smoke: device kind {d0.device_kind!r} has no entry in "
            f"the peaks table (kubeml_tpu/utils/roofline.py)")
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # a libtpu pip does not know is reported so
        libtpu = "unknown"
    from kubeml_tpu.native.bindings import native_available

    # is block_until_ready a barrier here? Time a chain of matmuls to the
    # barrier and again to a fetched value: a barrier that returns early
    # shows up as a much shorter first number (and an impossible FLOP/s)
    import jax.numpy as jnp

    n, reps = 4096, 20
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        for _ in range(reps):
            x = (x @ a) * (1.0 / n)
        return x

    float(chain(a)[0, 0])  # compiles the chain and the fetch's slice
    t0 = time.perf_counter()
    jax.block_until_ready(chain(a))
    t_barrier = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(chain(a)[0, 0])
    t_fetch = time.perf_counter() - t0
    flops = 2.0 * n ** 3 * reps
    if flops / t_barrier > 1.05 * peak or t_barrier < 0.5 * t_fetch:
        raise SystemExit(
            f"chip_smoke: block_until_ready is not a barrier here "
            f"({t_barrier:.4f}s to the barrier vs {t_fetch:.4f}s to a value; "
            f"{flops / t_barrier / 1e12:.0f} TFLOP/s implied, peak "
            f"{peak / 1e12:.0f})")
    info = {
        "platform": d0.platform, "device_kind": d0.device_kind,
        "count": len(devices), "default_backend": jax.default_backend(),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "peak_flops": peak, "hbm_bandwidth": bw,
        "native_library_loaded": bool(native_available()),
        "compile_cache_dir": str(cache_dir),
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "barrier_seconds": round(t_barrier, 5),
        "fetch_seconds": round(t_fetch, 5),
        "barrier_tflops": round(flops / t_barrier / 1e12, 1),
        # what a multi-host launcher would key on (parallel/distributed.py)
        "tpu_env": {k: os.environ[k] for k in sorted(os.environ)
                    if k.startswith(("TPU_", "CLOUD_TPU", "MEGASCALE"))},
    }
    emit(phase="device", ok=True, **info)
    return info


# --------------------------------------------------------------------------
# phase: kernels
# --------------------------------------------------------------------------

def _max_err(got, want) -> float:
    """Largest ``|got - want| / (1 + |want|)``: absolute where values are
    small, relative where they are not (a bf16 result of magnitude 4 is
    already 0.016 from its neighbour). For O(1) attention outputs."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))


def _scaled_err(got, want) -> float:
    """Largest ``|got - want|`` as a share of the largest ``|want|``: for
    results whose scale is not O(1) (matmul outputs, gradients)."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def phase_kernels(sz: Sizes) -> dict:
    """Every Pallas kernel compiled by Mosaic (``interpret=False``) at
    GPT-2-small widths against its XLA oracle. Oracles run in f32 under
    ``jax.default_matmul_precision("highest")``; inputs are unit normals, so
    attention outputs are O(1); errors are ``_max_err``'s mixed measure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.ops.attention import dot_product_attention
    from kubeml_tpu.ops.flash_attention import flash_attention
    from kubeml_tpu.ops.int8_matmul import int8_dot, int8_matmul
    from kubeml_tpu.ops.paged_attention import pack_kv_rows, paged_attention

    rng = np.random.default_rng(0)
    H, D, pt, N = sz.heads, sz.embed // sz.heads, sz.page_tokens, sz.arena_pages
    B = 8
    P = sz.context // pt
    results = {}

    # --- paged attention vs the gather path, three arena dtypes ---
    # One tolerance. bf16 / int8 arenas: the kernel rounds probabilities to
    # the page dtype (bf16: 2^-9 relative) before PV and rounds its output to
    # bf16 — a few 1e-3 on O(1) values; 2e-2 leaves room for the long rows,
    # far under what a wrong page, mask or scale would do (O(1)). f32: the
    # MXU's default f32 contraction is not the oracle's six-pass one
    # (measured 8e-3), so the bound is the same 2e-2, not 1e-6.
    paged_tol = 2e-2

    def gather_oracle(q, k_tok, v_tok, pages, pos):
        # the module's fallback read (models/gpt.py): gather the table into
        # a contiguous block, attend under the positional causal mask
        L = q.shape[1]
        kg = k_tok[pages].reshape(B, P * pt, H, D)
        vg = v_tok[pages].reshape(B, P * pt, H, D)
        k_pos = jnp.arange(P * pt)[None, None, None, :]
        mask = k_pos <= (pos[:, None] + jnp.arange(L))[:, None, :, None]
        with jax.default_matmul_precision("highest"):
            return dot_product_attention(q, kg, vg, mask=mask, impl="xla")

    kf = rng.normal(size=(N, pt, H, D)).astype(np.float32)
    vf = rng.normal(size=(N, pt, H, D)).astype(np.float32)
    # int8 storage the way the write path makes it: per-page-per-head absmax
    amax_k = np.abs(kf).max(axis=(1, 3))
    amax_v = np.abs(vf).max(axis=(1, 3))
    kq = np.clip(np.round(kf * 127.0 / amax_k[:, None, :, None]),
                 -127, 127).astype(np.int8)
    vq = np.clip(np.round(vf * 127.0 / amax_v[:, None, :, None]),
                 -127, 127).astype(np.int8)
    pages = jnp.asarray(
        np.stack([rng.permutation(np.arange(1, N))[:P] for _ in range(B)]),
        jnp.int32)
    cases = {
        # L == 1: decode steps at mixed depths, first page to last token
        1: [0, 5, 15, 16, 100, 511, 777, sz.context - 1],
        # L == 5: a speculative verify window (k + 1)
        5: [0, 3, 11, 16, 250, 500, 900, sz.context - 5],
        # L == 128: page-aligned suffix prefill after a prefix hit
        128: [0, 16, 32, 128, 256, 512, 640, sz.context - 128],
    }
    for L, positions in cases.items():
        pos = jnp.asarray(positions, jnp.int32)
        qf = rng.normal(size=(B, L, H, D)).astype(np.float32)
        for name, dt in (("bf16", jnp.bfloat16), ("f32", jnp.float32)):
            q = jnp.asarray(qf, dt)
            k_tok, v_tok = jnp.asarray(kf, dt), jnp.asarray(vf, dt)
            got = paged_attention(q, pack_kv_rows(k_tok, v_tok), pages, pos,
                                  interpret=False)
            want = gather_oracle(q.astype(jnp.float32),
                                 k_tok.astype(jnp.float32),
                                 v_tok.astype(jnp.float32), pages, pos)
            results[f"paged_attention/{name}/L{L}"] = (
                _max_err(got, want), paged_tol)
        # int8 arena, bf16 queries (the serving dtype); the oracle reads the
        # same bytes through the same q * s / 127 reconstruction
        q = jnp.asarray(qf, jnp.bfloat16)
        got = paged_attention(
            q, pack_kv_rows(jnp.asarray(kq), jnp.asarray(vq)), pages, pos,
            interpret=False, k_scale=jnp.asarray(amax_k),
            v_scale=jnp.asarray(amax_v))
        want = gather_oracle(
            q.astype(jnp.float32),
            jnp.asarray(kq, jnp.float32)
            * (jnp.asarray(amax_k) / 127.0)[:, None, :, None],
            jnp.asarray(vq, jnp.float32)
            * (jnp.asarray(amax_v) / 127.0)[:, None, :, None], pages, pos)
        results[f"paged_attention/int8/L{L}"] = (_max_err(got, want),
                                                 paged_tol)

    # --- int8 matmul vs int8_dot: the MLP up-projection and the lm_head ---
    # Both accumulate exact bf16 x int8 products in f32; they differ by the
    # summation order and one bf16 output rounding (2^-8 relative), so the
    # bound is 1% of the largest output.
    for K, Nout in ((sz.embed, 4 * sz.embed), (sz.embed, sz.vocab)):
        x = jnp.asarray(rng.normal(size=(8, K)), jnp.bfloat16)
        w = jnp.asarray(rng.integers(-127, 128, size=(K, Nout)), jnp.int8)
        s = jnp.asarray(rng.uniform(0.5, 1.5, size=(1, Nout)) * 1e-2,
                        jnp.float32)
        got = int8_matmul(x, w, s, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = int8_dot(x, w, s)
        results[f"int8_matmul/{K}x{Nout}"] = (_scaled_err(got, want), 1e-2)

    # --- flash attention, causal, forward and backward vs impl="xla" ---
    # bf16 in, f32 accumulation in both. Forward: O(1) outputs, one bf16
    # rounding on each side -> 2e-2. Backward: the gradients of sum(out * w)
    # pass through two more bf16 contractions -> 3% of the largest gradient.
    Lf = sz.flash_len
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, Lf, H, D)), jnp.bfloat16)
                  for _ in range(4))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=False)

    def xla(q, k, v):
        return dot_product_attention(q, k, v, causal=True, impl="xla")

    got = jax.jit(flash)(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(xla)(q, k, v)
        want_g = jax.jit(jax.grad(loss(xla), argnums=(0, 1, 2)))(q, k, v)
    results["flash_attention/fwd"] = (_max_err(got, want), 2e-2)
    got_g = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", got_g, want_g):
        results[f"flash_attention/bwd/d{name}"] = (_scaled_err(a, b), 3e-2)

    bad = {k: v for k, v in results.items()
           if not (v[0] <= v[1])}  # NaN fails too
    row = {k: {"err": round(e, 6), "tol": t} for k, (e, t) in results.items()}
    if bad:
        emit(phase="kernels", ok=False, results=row)
        raise SystemExit(f"chip_smoke: kernel(s) off their oracle: {bad}")

    # what `auto` means on this backend must be something that just ran
    from kubeml_tpu.ops.paged_attention import resolve_paged_attn

    auto = {"paged_attn": resolve_paged_attn("auto")}
    emit(phase="kernels", ok=True, auto=auto, results=row)
    return row


# --------------------------------------------------------------------------
# the cluster: what `kubeml start` boots, in this process
# --------------------------------------------------------------------------

def start_cluster(data_root):
    from kubeml_tpu.api.config import Config, set_config
    from kubeml_tpu.cluster import LocalCluster
    from kubeml_tpu.controller.client import KubemlClient

    import socket
    from pathlib import Path

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # default configuration; only deployment settings (where the data root
    # is, which free ports to bind) are chosen here
    cfg = Config(data_root=Path(data_root), controller_port=free_port(),
                 scheduler_port=free_port(), ps_port=free_port(),
                 storage_port=free_port())
    cfg.ensure_dirs()
    set_config(cfg)
    cluster = LocalCluster(config=cfg, serve_http=True).start()
    client = KubemlClient(cluster.controller_url, timeout=600.0)
    if not client.health():
        raise SystemExit("chip_smoke: controller is not healthy")
    return cfg, cluster, client


def wait_job(client, cluster, job_id: str, timeout: float, watch=None):
    """Poll the task list like the CLI does until the job has left it and
    its history is written; ``watch(job)`` sees the live in-process job
    object on every poll (the four-chip placement check)."""
    from kubeml_tpu.api.errors import KubeMLError

    deadline = time.time() + timeout
    while time.time() < deadline:
        if watch is not None:
            rec = cluster.ps._jobs.get(job_id)
            if rec is not None and rec.job is not None:
                watch(rec.job)
        running = any(t.job_id == job_id for t in client.tasks().list())
        if not running:
            try:
                hist = client.histories().get(job_id)
            except KubeMLError:
                hist = None  # queued: not started yet, or not persisted yet
            if hist is not None:
                return hist
        time.sleep(0.25)
    raise SystemExit(f"chip_smoke: job {job_id} did not finish in {timeout}s")


# --------------------------------------------------------------------------
# phase: train
# --------------------------------------------------------------------------

RESNET_FN = '''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.resnet import ResNet18
from kubeml_tpu.runtime.model import KubeModel


class Cifar(KubeDataset):
    def __init__(self):
        super().__init__("smoke-cifar")


class Model(KubeModel):
    def __init__(self):
        super().__init__(Cifar())

    def build(self):
        return ResNet18(num_classes=10, dtype=jnp.bfloat16)

    def preprocess(self, x):
        # images cross host->HBM as uint8 and dequantize on device
        return x.astype(jnp.bfloat16) / 127.5 - 1.0

    def configure_optimizers(self):
        return optax.sgd(self.lr, momentum=0.9)
'''


def synthetic_cifar(n: int, shape, seed: int):
    """CIFAR-10-shaped uint8 images that can be learned: each class is a
    fixed random template under noise."""
    import numpy as np

    r = np.random.default_rng(1234)
    templates = r.integers(32, 224, size=(10, *shape)).astype(np.float32)
    r = np.random.default_rng(seed)
    y = r.integers(0, 10, size=(n,)).astype(np.int64)
    x = templates[y] * 0.5 + r.normal(64.0, 24.0, size=(n, *shape))
    return np.clip(x, 0, 255).astype(np.uint8), y


def bytes_in_use() -> list:
    import jax

    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


def phase_train(sz: Sizes, cluster, client, n_devices: int) -> dict:
    import gc

    import jax
    import numpy as np

    from kubeml_tpu.api.types import TrainOptions, TrainRequest
    from kubeml_tpu.storage.checkpoint import FINAL_TAG

    n_train = n_devices * sz.k * sz.batch * sz.rounds_per_epoch
    x, y = synthetic_cifar(n_train, sz.image, seed=0)
    xt, yt = synthetic_cifar(4 * sz.batch, sz.image, seed=1)
    summary = client.datasets().create("smoke-cifar", x, y, xt, yt)
    client.functions().create("smoke-resnet18", RESNET_FN)

    gc.collect()  # the kernels phase's arrays must not count as "before"
    before = bytes_in_use()
    placement = {}

    def watch(job):
        # one worker per chip: the stacked variables' leading (worker) axis
        # is sharded over the worker mesh
        stacked = getattr(job, "_stacked_vars", None)
        if stacked is None or placement:
            return
        leaf = jax.tree.leaves(stacked)[0]
        placement["devices"] = sorted(d.id for d in leaf.sharding.device_set)
        placement["shard_shape"] = list(
            leaf.sharding.shard_shape(leaf.shape))
        placement["shape"] = list(leaf.shape)
        placement["bytes_in_use"] = bytes_in_use()

    req = TrainRequest(
        model_type="smoke-resnet18", function_name="smoke-resnet18",
        dataset="smoke-cifar", batch_size=sz.batch, epochs=sz.epochs, lr=0.02,
        options=TrainOptions(
            default_parallelism=n_devices, static_parallelism=True, k=sz.k,
            validate_every=1, checkpoint_every=1, precision="bf16"))
    t0 = time.time()
    job_id = client.networks().train(req)
    hist = wait_job(client, cluster, job_id, timeout=900, watch=watch)
    wall = time.time() - t0

    err = (hist.task or {}).get("error")
    if err:
        raise SystemExit(f"chip_smoke: train job failed: {err}")
    losses = [float(v) for v in hist.train_loss]
    if len(losses) != sz.epochs or not np.all(np.isfinite(losses)):
        raise SystemExit(f"chip_smoke: train losses {losses}")
    if not hist.accuracy or not np.all(np.isfinite(hist.validation_loss)):
        raise SystemExit(f"chip_smoke: validation missing: "
                         f"{hist.validation_loss} {hist.accuracy}")
    if hist.parallelism != [n_devices] * sz.epochs:
        raise SystemExit(f"chip_smoke: parallelism {hist.parallelism}, "
                         f"wanted {n_devices} per epoch")
    # the templates are separable: a trainer that works gets under chance
    # (ln 10 = 2.303) within two epochs
    if not losses[-1] < 2.25:
        raise SystemExit(f"chip_smoke: loss did not leave chance: {losses}")
    tags = client.checkpoints().list(job_id)
    if FINAL_TAG not in tags or len(tags) < 2:
        raise SystemExit(f"chip_smoke: checkpoints {tags}")
    if not placement:
        raise SystemExit("chip_smoke: never saw the job's stacked variables")
    if len(placement["devices"]) != n_devices or \
            placement["shape"][0] != n_devices or \
            placement["shard_shape"][0] != 1:
        raise SystemExit(f"chip_smoke: workers not one per chip: {placement}")
    grew = [b > a for a, b in zip(before, placement["bytes_in_use"])]
    if not all(grew):
        raise SystemExit(f"chip_smoke: no worker state on some chip: "
                         f"before {before}, during {placement}")
    # the finished job answers /infer from its final checkpoint
    preds = np.asarray(client.networks().infer(job_id, xt[:16]))
    if preds.shape[0] != 16 or not np.all(np.isfinite(preds)):
        raise SystemExit(f"chip_smoke: /infer returned {preds!r}")
    row = {
        "job": job_id, "dataset_samples": int(summary.train_set_size),
        "batch": sz.batch, "k": sz.k, "parallelism": hist.parallelism,
        "train_loss": [round(v, 4) for v in losses],
        "validation_loss": [round(float(v), 4) for v in hist.validation_loss],
        "accuracy": [round(float(v), 2) for v in hist.accuracy],
        "checkpoints": tags, "worker_devices": placement["devices"],
        "infer_shape": list(preds.shape), "wall_seconds": round(wall, 1),
    }
    emit(phase="train", ok=True, **row)
    return row


# --------------------------------------------------------------------------
# phase: serve
# --------------------------------------------------------------------------

def gpt_fn_source(sz: Sizes, vocab: int, with_mesh: bool) -> str:
    mesh = "mesh=self.mesh, " if with_mesh else ""
    return f'''
import jax.numpy as jnp
import optax

from kubeml_tpu.data.dataset import KubeDataset
from kubeml_tpu.models.gpt import CausalTransformer
from kubeml_tpu.runtime.model import KubeModel


class Tokens(KubeDataset):
    def __init__(self):
        super().__init__("smoke-tokens")


class Model(KubeModel):
    def __init__(self):
        super().__init__(Tokens())

    def build(self):
        # GPT-2-small at its published widths (biases and eps as released)
        return CausalTransformer(
            vocab_size={vocab}, max_len={sz.context},
            embed_dim={sz.embed}, depth={sz.depth}, num_heads={sz.heads},
            {mesh}dtype=jnp.bfloat16, attn_bias=True, ln_eps=1e-5)

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.1)
'''


def phase_serve(sz: Sizes, cfg, cluster, client) -> dict:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.functions.registry import FunctionRegistry
    from kubeml_tpu.ops.paged_attention import resolve_paged_attn
    from kubeml_tpu.serving import PagedBatchingDecoder
    from kubeml_tpu.storage.checkpoint import FINAL_TAG, CheckpointStore

    # a servable "finished job": seeded weights exported as the final
    # checkpoint of a deployed LM function
    registry = FunctionRegistry(config=cfg)
    registry.create("smoke-gpt2", gpt_fn_source(sz, sz.vocab, False))
    module = registry.load("smoke-gpt2").module
    variables = nn.meta.unbox(jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    CheckpointStore(config=cfg).save(
        "smoke-gpt2-job", jax.tree.map(np.asarray, variables), epoch=1,
        tag=FINAL_TAG, meta={"request": {"function_name": "smoke-gpt2"}})

    r = np.random.default_rng(7)
    tok = lambda n: r.integers(1, sz.vocab, size=(n,)).astype(np.int32)
    shared = tok(sz.prefix)
    planter = np.concatenate([shared, tok(sz.warm_suffix)])
    prompts = [np.concatenate([shared, tok(n)]) for n in sz.pair_suffixes]
    n_pair = len(prompts)
    prompts += [tok(n) for n in sz.prompts]

    # the reference: a plain causal forward pass (no cache, no pages, XLA
    # attention), f32 logits at the last prompt position. Prompts are
    # right-padded to one length (one program): causal attention never lets
    # a position see the padding after it
    longest = max(len(p) for p in [planter] + prompts)

    # the weights are an argument: closed over, they would be baked into
    # the program as a constant and its compile-cache entry would be the
    # size of the model
    @jax.jit
    def last_logits(variables, ids, n):
        return module.apply(variables, ids)[0, n - 1].astype(jnp.float32)

    def reference(prompt):
        ids = np.zeros((1, longest), np.int32)
        ids[0, :len(prompt)] = prompt
        logits = np.asarray(
            last_logits(variables, jnp.asarray(ids), len(prompt)))
        top2 = np.sort(logits)[-2:]
        return int(logits.argmax()), float(top2[1] - top2[0])

    def generate(prompt):
        return client.networks().generate(
            "smoke-gpt2-job", [prompt.tolist()], max_new_tokens=sz.new_tokens)

    # 1: the planter runs alone — it compiles the first programs and leaves
    # the shared prefix's pages in the trie
    t0 = time.time()
    outs = [generate(planter)]
    t_first = time.time() - t0
    decoder = cluster.ps._decoders["smoke-gpt2-job"][0]
    # 2: eight concurrent requests, two of them on the planted prefix
    results = [None] * len(prompts)
    errors = []

    def one(i):
        try:
            results[i] = generate(prompts[i])
        except Exception as e:  # reported below; the phase fails on any
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    t0 = time.time()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_wave = time.time() - t0
    if errors:
        raise SystemExit(f"chip_smoke: /generate failed: {errors}")
    outs += results
    all_prompts = [planter] + prompts

    tel = decoder.telemetry()
    checks = {
        "engine": type(decoder).__name__,
        "same_decoder": cluster.ps._decoders["smoke-gpt2-job"][0] is decoder,
        "closed": decoder.closed,
        "paged_attn": decoder.paged_attn,
        "paged_attn_kernel": tel["paged_attn_kernel"],
        "requests_completed": tel["requests_completed"],
        "requests_failed": tel["requests_failed"],
        "snapshot_saved": tel.get("snapshot_saved", 0.0),
        "snapshot_replayed": tel.get("snapshot_replayed", 0.0),
        "snapshot_failed": tel.get("snapshot_failed", 0.0),
        "prefix_hits": tel["prefix_hits"],
        "compiled_programs": tel["compiled_programs"],
    }
    want_kernel = resolve_paged_attn("auto")
    # every request went through THIS paged decoder: it is the PS's only
    # decoder for the job, it never closed or got rebuilt, and it completed
    # exactly the requests sent — none fell to the slot engine or to the
    # one-shot generate tail of PS.generate
    if not (type(decoder) is PagedBatchingDecoder
            and checks["same_decoder"] and not checks["closed"]
            and len(cluster.ps._decoders) == 1
            and tel["requests_completed"] == len(all_prompts)
            and tel["requests_failed"] == 0):
        raise SystemExit(f"chip_smoke: not served by the paged engine: "
                         f"{checks}")
    if decoder.paged_attn != want_kernel or \
            tel["paged_attn_kernel"] != (1.0 if want_kernel == "pallas"
                                         else 0.0):
        raise SystemExit(f"chip_smoke: arena read path {decoder.paged_attn!r}"
                         f", auto resolves to {want_kernel!r}: {checks}")
    if checks["snapshot_saved"] or checks["snapshot_replayed"] or \
            checks["snapshot_failed"]:
        raise SystemExit(f"chip_smoke: engine faulted and replayed: {checks}")
    lengths = [o["lengths"][0] for o in outs]
    if lengths != [sz.new_tokens] * len(outs):
        raise SystemExit(f"chip_smoke: lengths {lengths}")
    for o in outs:
        toks = np.asarray(o["tokens"][0])
        if toks.shape != (sz.new_tokens,) or toks.min() < 0 \
                or toks.max() >= sz.vocab:
            raise SystemExit(f"chip_smoke: bad tokens {toks!r}")
    cached = [o["prefix_cached_tokens"] for o in outs]
    if not all(c >= sz.prefix for c in cached[1:1 + n_pair]) or \
            any(cached[1 + n_pair:]) or cached[0]:
        raise SystemExit(f"chip_smoke: prefix reuse {cached} (wanted >= "
                         f"{sz.prefix} on the sharing pair only)")
    # first tokens against the reference. Random weights give near-flat
    # logits, so a request only counts where the reference's own top-2
    # margin clears bf16 noise (0.05 on O(1) logits); at least half must
    firsts = []
    for prompt, o in zip(all_prompts, outs):
        top1, margin = reference(prompt)
        firsts.append({"served": int(o["tokens"][0][0]), "reference": top1,
                       "margin": round(margin, 4)})
    decided = [f for f in firsts if f["margin"] > 0.05]
    wrong = [f for f in decided if f["served"] != f["reference"]]
    if wrong or 2 * len(decided) < len(firsts):
        raise SystemExit(f"chip_smoke: first tokens off the reference: "
                         f"{firsts}")
    row = {**checks, "requests": len(outs), "concurrent": len(prompts),
           "prompt_lengths": [len(p) for p in all_prompts],
           "lengths": lengths, "prefix_cached_tokens": cached,
           "first_tokens_checked": len(decided),
           "first_request_seconds": round(t_first, 1),
           "wave_seconds": round(t_wave, 1),
           "serving_devices": 1}  # the PS serves on one device by default
    emit(phase="serve", ok=True, **row)
    return row


# --------------------------------------------------------------------------
# phase: spmd (four chips)
# --------------------------------------------------------------------------

def phase_spmd(sz: Sizes, cluster, client, n_devices: int) -> dict:
    import jax
    import numpy as np

    from kubeml_tpu.api.types import TrainOptions, TrainRequest

    if n_devices < 4:
        row = {"skipped": f"{n_devices} device(s); tp=2,dp=2 needs four"}
        emit(phase="spmd", ok=True, **row)
        return row
    # the model shards lm_head and the embedding along the vocabulary, which
    # the published 50257 (odd) cannot split two ways: pad it to a multiple
    # of 128, as tensor-parallel trainings of this model do
    vocab = -(-sz.vocab // 128) * 128
    r = np.random.default_rng(3)
    n = sz.spmd_batch * sz.spmd_steps
    x = r.integers(1, sz.vocab, size=(n, sz.spmd_seq)).astype(np.int32)
    y = np.zeros((n,), np.int64)  # the LM objective ignores stored labels
    client.datasets().create("smoke-tokens", x, y, x[:sz.spmd_batch],
                             y[:sz.spmd_batch])
    client.functions().create("smoke-gpt2-spmd",
                              gpt_fn_source(sz, vocab, True))
    seen = {}

    def watch(job):
        params = getattr(job.trainer, "params", None)
        if params is None or seen:
            return
        split = []
        for leaf in jax.tree.leaves(params):
            spec = tuple(leaf.sharding.spec)
            if "tp" in spec:
                ax = spec.index("tp")
                shard = leaf.sharding.shard_shape(leaf.shape)
                split.append(shard[ax] * 2 == leaf.shape[ax])
        seen["mesh"] = {k: int(v) for k, v in job.mesh.shape.items()}
        seen["tp_leaves"] = len(split)
        seen["tp_halved"] = bool(split) and all(split)
        seen["devices"] = sorted(
            d.id for d in jax.tree.leaves(params)[0].sharding.device_set)

    req = TrainRequest(
        model_type="smoke-gpt2-spmd", function_name="smoke-gpt2-spmd",
        dataset="smoke-tokens", batch_size=sz.spmd_batch, epochs=1, lr=3e-4,
        options=TrainOptions(engine="spmd", mesh_shape={"tp": 2, "dp": 2},
                             static_parallelism=True, validate_every=0,
                             save_model=False, precision="bf16"))
    t0 = time.time()
    job_id = client.networks().train(req)
    hist = wait_job(client, cluster, job_id, timeout=900, watch=watch)
    err = (hist.task or {}).get("error")
    if err:
        raise SystemExit(f"chip_smoke: spmd job failed: {err}")
    losses = [float(v) for v in hist.train_loss]
    if len(losses) != 1 or not np.all(np.isfinite(losses)):
        raise SystemExit(f"chip_smoke: spmd losses {losses}")
    if not (seen.get("tp_halved") and seen["mesh"].get("tp") == 2
            and seen["mesh"].get("dp") == 2 and len(seen["devices"]) == 4):
        raise SystemExit(f"chip_smoke: weights not split over tp: {seen}")
    row = {"job": job_id, "steps": sz.spmd_steps, "vocab": vocab,
           "train_loss": losses,
           "wall_seconds": round(time.time() - t0, 1), **seen}
    emit(phase="spmd", ok=True, **row)
    return row


# --------------------------------------------------------------------------
# one pass (a child process: it owns the chip)
# --------------------------------------------------------------------------

def run_pass(name: str, sz: Sizes = Sizes()) -> int:
    import shutil
    import tempfile

    from kubeml_tpu.api.config import enable_compilation_cache

    from kubeml_tpu.utils.tracing import compile_clock

    cache_dir = enable_compilation_cache()
    clock = compile_clock()
    marks = [("startup", clock.totals())]   # (phase, the clock at its start)

    def enter(phase: str) -> None:
        marks.append((phase, clock.totals()))

    t0 = time.time()
    enter("device")
    device = phase_device(cache_dir)
    enter("kernels")
    phase_kernels(sz)
    data_root = tempfile.mkdtemp(prefix="kubeml-smoke-")
    cfg, cluster, client = start_cluster(data_root)
    try:
        enter("train")
        phase_train(sz, cluster, client, device["count"])
        enter("serve")
        phase_serve(sz, cfg, cluster, client)
        enter("spmd")
        phase_spmd(sz, cluster, client, device["count"])
    finally:
        enter("shutdown")
        cluster.stop()
        shutil.rmtree(data_root, ignore_errors=True)
    enter("done")
    rows = {phase: compile_row(a, b)
            for (phase, a), (_, b) in zip(marks, marks[1:])}
    emit(phase="pass", name=name, ok=True,
         device={"platform": device["platform"],
                 "kind": device["device_kind"], "count": device["count"]},
         wall_seconds=round(time.time() - t0, 1),
         compile=compile_row(marks[0][1], marks[-1][1]),
         # a phase in which nothing compiled has no row
         compile_by_phase={p: r for p, r in rows.items() if any(r.values())})
    return 0


# --------------------------------------------------------------------------
# the command: two sequential passes, this process never touches jax
# --------------------------------------------------------------------------

def main(argv) -> int:
    if len(argv) == 3 and argv[1] == "--pass":
        return run_pass(argv[2])
    if len(argv) != 1:
        print("usage: python3 chip_smoke.py", file=sys.stderr)
        return 2
    import signal

    # a terminated command must take its child (and the chip) down with it
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    passes = {}
    deadline = time.monotonic() + BUDGET_SECONDS
    for name in PASSES:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--pass", name],
            stdout=subprocess.PIPE, text=True)
        watchdog = threading.Timer(max(deadline - time.monotonic(), 1.0),
                                   proc.kill)
        watchdog.start()
        last = None
        try:
            for line in proc.stdout:
                sys.stdout.write(line)
                sys.stdout.flush()
                last = line
            rc = proc.wait()
        except BaseException:
            proc.kill()
            proc.wait()
            raise
        finally:
            watchdog.cancel()
        if rc != 0:
            print(f"chip_smoke: pass {name!r} failed (exit {rc})",
                  file=sys.stderr)
            return rc if rc > 0 else 1
        passes[name] = json.loads(last) if last else {}
        if not (passes[name].get("phase") == "pass" and passes[name]["ok"]):
            print(f"chip_smoke: pass {name!r} ended without its summary",
                  file=sys.stderr)
            return 1
    cold, warm = passes["cold"]["compile"], passes["warm"]["compile"]
    # the second process must have read the first one's programs back. If the
    # machine came with a populated cache the first pass was warm too, so the
    # seconds are only compared when the first one mostly compiled.
    cold_compiled = cold["cache_hits"] < 0.1 * cold["programs"]
    if warm["cache_hits"] <= 0 or (
            cold_compiled
            and warm["compile_seconds"] > 0.5 * cold["compile_seconds"]):
        print(f"chip_smoke: the compile cache did not carry: cold {cold}, "
              f"warm {warm}", file=sys.stderr)
        return 1
    emit(phase="cache", ok=True, cold=cold, warm=warm,
         cold_wall_seconds=passes["cold"]["wall_seconds"],
         warm_wall_seconds=passes["warm"]["wall_seconds"], claim=None)
    # the result, last: exactly these keys, the device as jax reports it
    emit(ok=True, device=passes["warm"]["device"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
