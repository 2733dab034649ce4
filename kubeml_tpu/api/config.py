"""One typed configuration layer for the whole framework.

The reference scatters configuration across env vars (DEBUG_ENV, LIMIT_PARALLELISM,
STANDALONE_JOBS, REDIS_URL, MONGO_IP, ...), hardcoded cluster DNS constants
(reference: ml/pkg/api/const.go:4-30) and Helm values. Here a single ``Config``
dataclass owns every knob, reads the environment once, and is passed (or defaulted)
everywhere. Service addresses default to loopback so the full control plane runs
in-process for tests — generalizing the reference's DEBUG_ENV/threaded-PS pattern
(reference: ml/pkg/util/utils.go:26-37, ml/pkg/ps/api.go:211-217).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional


# Persistent XLA compilation cache: elastic re-meshes recompile per worker
# count, and standalone job runners and every chip-tool call are fresh
# processes — all of them read this disk cache instead of recompiling. The
# directory is part of the cache key, so it is ONE fixed path inside the
# checkout (git-ignored), never derived from data_root, a pid or a temp name.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "xla"


def enable_compilation_cache() -> Path:
    """Turn jax's persistent compilation cache on for this process and
    return its directory (idempotent; every entry point that compiles calls
    it before its first jit). ``JAX_COMPILATION_CACHE_DIR`` places the cache
    from outside: jax reads that variable itself, so when it is set no
    directory is set here."""
    import jax

    # cache every program, not only those over jax's one-second floor: on a
    # TPU even a one-op program costs ~0.2 s to compile, and a warm process
    # spent 87 s recompiling the 382 of its 409 programs under that floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return Path(placed)
    COMPILE_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return COMPILE_CACHE_DIR


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() not in ("0", "false", "no", "")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v else default


@dataclass
class Config:
    # --- data root: datasets, function registry, history, checkpoints ---
    data_root: Path = field(
        default_factory=lambda: Path(os.environ.get("KUBEML_DATA_ROOT", "~/.kubeml")).expanduser()
    )

    # --- service ports (reference cluster DNS const.go:4-14 -> local ports) ---
    # bind/connect address for the four services; 0.0.0.0 exposes them to
    # remote clients (the containerized single-host mode, deploy/docker)
    host: str = field(default_factory=lambda: os.environ.get("KUBEML_HOST", "127.0.0.1"))
    controller_port: int = field(default_factory=lambda: _env_int("KUBEML_CONTROLLER_PORT", 9090))
    scheduler_port: int = field(default_factory=lambda: _env_int("KUBEML_SCHEDULER_PORT", 9091))
    ps_port: int = field(default_factory=lambda: _env_int("KUBEML_PS_PORT", 9092))
    storage_port: int = field(default_factory=lambda: _env_int("KUBEML_STORAGE_PORT", 9093))
    metrics_port: int = field(default_factory=lambda: _env_int("KUBEML_METRICS_PORT", 8080))

    # --- behavior flags (reference: util/utils.go:10-50, ps main.go:117-129) ---
    debug: bool = field(default_factory=lambda: _env_bool("KUBEML_DEBUG"))
    # limit_parallelism freezes scale-up like LIMIT_PARALLELISM (train/job.go:210-213)
    limit_parallelism: bool = field(default_factory=lambda: _env_bool("LIMIT_PARALLELISM"))
    # standalone_jobs: run each TrainJob in its own process (reference: dedicated pod,
    # ps/job_pod.go) vs in-process thread (ps/api.go:211-217). Default threaded.
    standalone_jobs: bool = field(default_factory=lambda: _env_bool("STANDALONE_JOBS"))

    # --- TPU execution ---
    # max workers the scheduler may allocate; None -> len(jax.devices())
    max_parallelism: Optional[int] = field(
        default_factory=lambda: (
            int(os.environ["KUBEML_MAX_PARALLELISM"]) if os.environ.get("KUBEML_MAX_PARALLELISM") else None
        )
    )
    use_native_loader: bool = field(default_factory=lambda: _env_bool("KUBEML_NATIVE_LOADER", True))
    # multi-host: seconds the PS waits for every follower's job-start ack
    # before aborting the job (a follower missing the function/dataset must
    # fail the start, not hang the first collective)
    dist_ack_timeout: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_DIST_ACK_TIMEOUT", "120"))
    )
    # standalone runners publish per-epoch weights into a socket-served native
    # TensorStore so the PS serves live /infer without HTTP-JSON round-trips
    # (KUBEML_TENSOR_SOCKETS=0 disables; auto-off when the native lib is absent)
    tensor_sockets: bool = field(
        default_factory=lambda: _env_bool("KUBEML_TENSOR_SOCKETS", True)
    )

    # --- control-plane resilience (utils.resilience) ---
    # seconds a job thread waits for the scheduler's epoch-end parallelism
    # answer before keeping its current parallelism (the reference blocks
    # forever on schedulerCh; a timeout keeps a dead scheduler from wedging
    # training)
    update_timeout: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_UPDATE_TIMEOUT", "30"))
    )
    # connect-phase timeout for every internal hop: a peer that can't even
    # be reached must fail in seconds, not hang for the full read timeout
    http_connect_timeout: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_CONNECT_TIMEOUT", "3.05"))
    )
    # bounded retries for idempotent / idempotency-keyed internal calls:
    # total attempts, exponential backoff base and cap (seconds, jittered)
    retry_attempts: int = field(default_factory=lambda: _env_int("KUBEML_RETRY_ATTEMPTS", 3))
    retry_backoff: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_RETRY_BACKOFF", "0.1"))
    )
    retry_backoff_max: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_RETRY_BACKOFF_MAX", "2.0"))
    )
    # per-destination retry budget: retries are throttled to ~this fraction
    # of live traffic, so a hard outage degrades instead of amplifying
    retry_budget: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_RETRY_BUDGET", "0.2"))
    )
    # circuit breaker: consecutive transport failures that open a
    # destination's circuit, and the open-state cooldown before the
    # half-open probe
    breaker_threshold: int = field(
        default_factory=lambda: _env_int("KUBEML_BREAKER_THRESHOLD", 5))
    breaker_cooldown: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_BREAKER_COOLDOWN", "5.0"))
    )

    # --- multi-tenant preemption (scheduler/preemption.py + ps.preempt_task) ---
    # seconds a preempted job gets to checkpoint-and-yield cooperatively
    # before the hard-kill escalation (safe: checkpoint publish is atomic, so
    # a SIGKILL mid-yield costs at most the epochs since the newest
    # checkpoint — the same guarantee the chaos suite proves for crashes)
    preempt_grace: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_PREEMPT_GRACE", "60"))
    )
    # run the preemption controller (watches the serving overload signals and
    # reclaims capacity from the lowest-priority running job); off by default
    # — colocating serving and training is an explicit deployment decision
    preempt_monitor: bool = field(
        default_factory=lambda: _env_bool("KUBEML_PREEMPT_MONITOR"))
    # controller poll period (seconds)
    preempt_interval: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_PREEMPT_INTERVAL", "1.0"))
    )
    # overload signal thresholds (any crossing counts as serving pressure):
    # queued decode rows (the serving queue-depth gauge)...
    preempt_queue_depth: int = field(
        default_factory=lambda: _env_int("KUBEML_PREEMPT_QUEUE_DEPTH", 8))
    # ...429s/sec over the controller's sliding window (requests_overload rate)...
    preempt_overload_rate: float = field(
        default_factory=lambda: float(
            os.environ.get("KUBEML_PREEMPT_OVERLOAD_RATE", "1.0"))
    )
    # ...and serving request p99 seconds (kubeml_serving_request_seconds
    # quantile source; 0 disables the latency signal)
    preempt_p99: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_PREEMPT_P99", "0"))
    )
    # consecutive overloaded polls before reclaiming, and consecutive calm
    # polls before a preempted job is requeued (hysteresis: one noisy sample
    # must neither kill a training run nor thrash it back into the burst)
    preempt_sustain: int = field(
        default_factory=lambda: _env_int("KUBEML_PREEMPT_SUSTAIN", 3))
    preempt_resume_sustain: int = field(
        default_factory=lambda: _env_int("KUBEML_PREEMPT_RESUME_SUSTAIN", 5))
    # seconds between successive preemptions (one reclaim must get the chance
    # to relieve pressure before the next victim is chosen)
    preempt_cooldown: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_PREEMPT_COOLDOWN", "30"))
    )

    # --- serving SLO observability (utils/timeseries.py + ps/slo.py) ---
    # embedded time-series store: the PS samples its metrics registry into
    # bounded per-series rings (served at GET /metrics/history; the SLO
    # engine and `kubeml top` read it). KUBEML_TSDB=0 disables sampling.
    tsdb_enable: bool = field(default_factory=lambda: _env_bool("KUBEML_TSDB", True))
    # seconds between registry samples
    tsdb_interval: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_TSDB_INTERVAL", "1.0"))
    )
    # samples kept per series (600 x 1s = ~10 min of history)
    tsdb_samples: int = field(
        default_factory=lambda: _env_int("KUBEML_TSDB_SAMPLES", 600))
    # distinct series kept (oldest-evicted past the cap)
    tsdb_series: int = field(
        default_factory=lambda: _env_int("KUBEML_TSDB_SERIES", 1024))
    # declarative SLOs: semicolon-separated objectives `[name:]signal<=target`
    # (or >=). Signals: availability, overload_rate, error_rate, ttft_p99,
    # request_p99, queue_depth. Burn threshold defaults to 1.0; append @N to
    # override (e.g. "availability>=0.99@6"). Empty string disables the
    # engine entirely.
    slo_spec: str = field(
        default_factory=lambda: os.environ.get(
            "KUBEML_SLOS",
            "availability>=0.99;overload_rate<=5.0;ttft_p99<=2.5"))
    # multi-window burn rates (Google SRE Workbook shape): the fast window
    # catches "burning now", the slow window proves it is sustained — an
    # alert needs BOTH above the objective's burn threshold
    slo_fast_window: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_SLO_FAST_WINDOW", "60"))
    )
    slo_slow_window: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_SLO_SLOW_WINDOW", "300"))
    )
    # alert state machine hysteresis: seconds the burn condition must hold
    # before pending escalates to firing, and seconds it must stay clear
    # before firing resolves
    slo_for: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_SLO_FOR", "5"))
    )
    slo_resolve_for: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_SLO_RESOLVE_FOR", "15"))
    )
    # `kubeml top` refresh interval and the window its rates/quantiles are
    # computed over
    top_interval: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_TOP_INTERVAL", "2.0"))
    )
    top_window: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_TOP_WINDOW", "30"))
    )

    # --- elastic-training decision observability (scheduler/decisions.py +
    # engine/kavg.py round statistics) ---
    # scale-decision audit trail retention: newest decisions kept per job,
    # and distinct jobs kept (oldest-recorded job evicted past the cap)
    decision_log_size: int = field(
        default_factory=lambda: _env_int("KUBEML_DECISION_LOG_SIZE", 64))
    decision_log_jobs: int = field(
        default_factory=lambda: _env_int("KUBEML_DECISION_LOG_JOBS", 256))
    # statistical-efficiency signals from the K-AVG round program: per-round
    # worker-loss spread and pre-merge weight divergence, computed as cheap
    # on-chip reductions inside the jitted sync round. KUBEML_ROUND_STATS=0
    # restores the exact pre-instrumentation round program (bit-identical).
    round_stats: bool = field(
        default_factory=lambda: _env_bool("KUBEML_ROUND_STATS", True))

    # --- function execution guardrails (reference cmd/function.go:234-262:
    # per-function concurrency 50, execution timeout 1000s) ---
    # seconds a user-code call (function load, traced user module, a job
    # round with no progress) may run before being abandoned/failed; <= 0
    # disables
    function_timeout: float = field(
        default_factory=lambda: float(os.environ.get("KUBEML_FUNCTION_TIMEOUT", "1000"))
    )
    # simultaneous in-process user-function loads/invocations
    function_concurrency: int = field(
        default_factory=lambda: _env_int("KUBEML_FUNCTION_CONCURRENCY", 50)
    )

    # --- /generate serving (kubeml_tpu.serving.BatchingDecoder) ---
    # continuous batching coalesces concurrent decode requests into one
    # slot-based batched loop (decode is HBM-bound: batch is ~free throughput)
    serving_batcher: bool = field(
        default_factory=lambda: _env_bool("KUBEML_SERVING_BATCHER", True)
    )
    # resident decode slots (KV-cache HBM scales linearly with this)
    serving_slots: int = field(default_factory=lambda: _env_int("KUBEML_SERVING_SLOTS", 8))
    # decode steps per device program: larger amortizes dispatch overhead,
    # smaller tightens admission latency for newly arriving requests
    serving_chunk_steps: int = field(default_factory=lambda: _env_int("KUBEML_SERVING_CHUNK", 16))
    # weight-only int8 decode ("int8"; empty = off): halves the per-step
    # weight HBM traffic and the weight footprint (serving/quant.py;
    # round-5 builder-measured +4-11% decode at batch 1 for 124M-774M
    # classes, ~neutral at batch >= 8). Composes with
    # the serving mesh: flat-checkpoint loads quantize BEFORE placement
    # (int8-sized per-device peak), q/scales shard with the tp specs.
    serving_quantize: str = field(
        default_factory=lambda: os.environ.get("KUBEML_SERVING_QUANTIZE", ""))
    # the type the parameter server HOLDS a served checkpoint's floating
    # leaves in on the device ("bfloat16" | "float32"; empty = as the
    # checkpoint has them). A model published in bfloat16 is served from
    # half the bytes of its float32 checkpoint files: the leaves are cast
    # one by one as they reach the device (serving.quant.cast_tree), so the
    # wide tree is never resident. Int8 leaves (serving_quantize) are left.
    # Empty, a leaf the module only ever casts to a narrower floating type
    # is held in that type, every other as restored (docs/design.md §26).
    serving_param_dtype: str = field(
        default_factory=lambda: os.environ.get(
            "KUBEML_SERVING_PARAM_DTYPE", ""))
    # NATIVE int8 decode matmuls (with serving_quantize=int8): contract the
    # activations against the int8 weights directly and fold the per-channel
    # scale into the f32 accumulator AFTER the contraction
    # (serving.quant.quantized_dot -> ops/int8_matmul.py) — no dense W~ is
    # rebuilt inside the step program, which is what kept the round-5
    # dequantize path at +4-11% of the 2x byte cut. Off (default) keeps the
    # dequantize-then-matmul path.
    int8_matmul: bool = field(
        default_factory=lambda: _env_bool("KUBEML_INT8_MATMUL"))
    # which quantized-matmul implementation quantized_dot dispatches to:
    # "auto" (Pallas kernel on TPU, XLA dot_general fallback elsewhere),
    # "pallas" (force the kernel; interpret mode off-TPU — the test path),
    # "dot" (force the fallback)
    int8_matmul_impl: str = field(
        default_factory=lambda: os.environ.get("KUBEML_INT8_MATMUL_IMPL",
                                               "auto"))
    # dispatch-chain depth: the CEILING on the programs a serving engine
    # keeps in flight ahead of the host's processed state. The paged engine
    # picks its depth under it from the host's turnaround and a decode
    # program's time (batcher.run_ahead_depth: 2 at a 50 ms step on a local
    # chip), because every place past that only delays a new request's
    # prefill and the detection of completions; the slot engine runs at it.
    serving_pipeline: int = field(
        default_factory=lambda: _env_int("KUBEML_SERVING_PIPELINE", 6))
    # serving overload protection: queued decode rows past this depth are
    # refused at admission with 429 + Retry-After (0 = unbounded). The
    # serving path must shed load under a burst, never queue unboundedly.
    serving_queue_limit: int = field(
        default_factory=lambda: _env_int("KUBEML_SERVING_QUEUE_LIMIT", 256))
    # what happens at the limit: "reject" 429s the NEW request;
    # "oldest" sheds the longest-queued request instead (its waiter gets the
    # 429) and admits the new one — freshest-work-wins under sustained
    # overload, bounding queue wait instead of queue depth alone
    serving_shed_policy: str = field(
        default_factory=lambda: os.environ.get("KUBEML_SERVING_SHED", "reject"))
    # compile-storm threshold for the serving engine's compile tracker
    # (serving/stats.py): a warning logs and kubeml_serving_compile_storm
    # flips to 1 while the 60s compile rate exceeds this many compiles per
    # minute — sustained compiles in steady state mean shape churn (the
    # PR-15 +14% regression's signature). 0 disables the warning; the
    # counters/histograms record regardless.
    compile_storm_per_min: float = field(
        default_factory=lambda: _env_float("KUBEML_COMPILE_STORM_PER_MIN",
                                           6.0))
    # SHARDED serving: axis spec like "tp=2" — finished (sharded) checkpoints
    # restore straight onto this mesh and the batcher runs one SPMD decode
    # program over it, so a model too big for one chip still serves. Empty
    # (default) = single-device serving.
    serving_mesh: str = field(
        default_factory=lambda: os.environ.get("KUBEML_SERVING_MESH", ""))
    # --- paged KV-cache serving (serving/kvpool.py + PagedBatchingDecoder) ---
    # serve capable causal-LM models through the paged engine: block
    # allocator over a shared KV arena, page-budget admission at every
    # chunk edge, shared-prefix reuse. Models without a paged decode path
    # (MoE-interleaved, non-CausalTransformer) and meshed serving fall back
    # to the dense slot engine automatically.
    serving_paged: bool = field(
        default_factory=lambda: _env_bool("KUBEML_SERVING_PAGED", True))
    # tokens per physical KV page (power of two). Smaller = finer-grained
    # memory + more prefix-sharing opportunities, larger = smaller page
    # tables and fewer scatter indices per program.
    serving_page_tokens: int = field(
        default_factory=lambda: _env_int("KUBEML_SERVING_PAGE_TOKENS", 16))
    # total pages in the device arena (including the reserved trash page).
    # 0 (default) derives slots x ceil(max_len / page_tokens) + 1 — the slot
    # engine's worst case, so the default never admission-regresses; size it
    # DOWN for the memory win on short-request traffic.
    serving_pages: int = field(
        default_factory=lambda: _env_int("KUBEML_SERVING_PAGES", 0))
    # shared-prefix KV reuse: identical leading prompt blocks (system
    # prompts, few-shot headers) map to the same refcounted pages and
    # prefill runs only on the unshared suffix
    serving_prefix_cache: bool = field(
        default_factory=lambda: _env_bool("KUBEML_SERVING_PREFIX_CACHE", True))
    # chunked prefill (Sarathi-style): a cold prompt whose unshared suffix
    # exceeds this many tokens prefills in page-aligned chunks interleaved
    # with decode steps, one chunk per engine-loop iteration, so a long
    # prompt no longer stalls every decoding row behind one monolithic
    # prefill program. The cap pow2-buckets down to a multiple of
    # serving_page_tokens (bounded program set; chunk boundaries stay
    # page-aligned). 0 (default) = monolithic prefill — today's behavior
    # and the chunked path's parity oracle.
    prefill_chunk_tokens: int = field(
        default_factory=lambda: _env_int("KUBEML_PREFILL_CHUNK_TOKENS", 0))
    # graceful serving drain (ISSUE 20): seconds live rows get to run out
    # after POST /serving/drain (or SIGTERM) before the engine snapshots
    # stragglers into portable KMS1 frames and fails their waiters 503
    drain_grace: float = field(
        default_factory=lambda: float(
            os.environ.get("KUBEML_DRAIN_GRACE", "20")))
    # where drained request snapshots land (one <model>-<request>.kms per
    # straggler) and where the PS looks for them on next boot to replay —
    # empty (default) disables the cross-process snapshot hop entirely
    snap_dir: str = field(
        default_factory=lambda: os.environ.get("KUBEML_SNAP_DIR", ""))
    # KVPool invariant watchdog: the paged engine runs kvpool.check()
    # every this-many seconds under the engine lock; a tripped invariant
    # fires the errorhook and routes through fault recovery instead of
    # decoding through corrupted page accounting. 0 (default) = off
    pool_audit_interval: float = field(
        default_factory=lambda: float(
            os.environ.get("KUBEML_POOL_AUDIT_INTERVAL", "0")))
    # how the paged engine READS the KV arena (ops/paged_attention.py):
    # "pallas" attends straight through the page table with the streaming
    # Pallas kernel (KV traffic scales with each row's actual depth, no
    # contiguous gather copy in HBM), "gather" keeps the
    # gather-then-attend path (the parity oracle and the off-TPU serving
    # path), "auto" (default) = pallas on TPU, gather elsewhere. The impl
    # is cloned onto the served module, so it is part of every jit-cache
    # key — toggling can never serve a stale compiled program.
    paged_attn: str = field(
        default_factory=lambda: os.environ.get("KUBEML_PAGED_ATTN", "auto"))
    # paged-arena STORAGE dtype (ops/paged_attention.resolve_kv_quant):
    # "int8" stores K/V pages int8 with per-page-per-head scale arenas —
    # the kernel dequantizes in VMEM, arena sizing re-derives the page
    # count from the unquantized byte budget (~2x capacity at bf16, ~4x
    # at f32), and kv_read_bytes accounting models the storage bytes.
    # "off" (default) keeps the compute dtype; "auto" reserves TPU
    # auto-enable for when on-device parity evidence lands (today: off).
    kv_quant: str = field(
        default_factory=lambda: os.environ.get("KUBEML_KV_QUANT", "off"))
    # --- speculative decoding (paged engine only; serving/batcher.py
    # spec mode + models/generation.py acceptance math) ---
    # drafter backend: "off" (default), "self" (early-exit logits from a
    # truncated layer stack of the target — no second model), or "draft"
    # (a separate small model named by KUBEML_SPEC_DRAFT_MODEL). Greedy
    # spec decode is bit-identical to the baseline; sampled decode
    # preserves the target distribution exactly (accept min(1, p/q),
    # resample the residual).
    serving_spec: str = field(
        default_factory=lambda: os.environ.get("KUBEML_SERVING_SPEC", "off"))
    # tokens the drafter proposes per verify step (the adaptive controller
    # walks k down/up a pow2 ladder bounded by this; also the worst-case
    # page-reservation lookahead, so it is a capacity knob too)
    spec_k: int = field(default_factory=lambda: _env_int("KUBEML_SPEC_K", 4))
    # adapt k to the measured acceptance rate (shrink on low acceptance,
    # grow on high; self-drafting retreats to plain decode entirely and
    # re-probes). 0 pins k at KUBEML_SPEC_K.
    spec_adaptive: bool = field(
        default_factory=lambda: _env_bool("KUBEML_SPEC_ADAPTIVE", True))
    # the draft model for spec=draft: a finished job id whose final
    # checkpoint (preferring the final-int8 tag under int8 serving — the
    # drafter rides the quantized-checkpoint store) loads as the drafter
    spec_draft_model: str = field(
        default_factory=lambda: os.environ.get("KUBEML_SPEC_DRAFT_MODEL", ""))
    # early-exit depth for spec=self (blocks run before ln_f + lm_head);
    # 0 derives depth // 2
    spec_exit_layer: int = field(
        default_factory=lambda: _env_int("KUBEML_SPEC_EXIT_LAYER", 0))
    # draft-backend acceptance floor (serving/spec.py): sustained EWMA
    # acceptance below this permanently disables drafting for the served
    # model (one warning + kubeml_serving_spec_disabled=1) — the draft
    # backend cannot suspend/re-probe, so a mismatched checkpoint would
    # otherwise pay a full drafter forward per step forever. 0 disables
    # the guard. Applies to spec=draft only; spec=self retreats via the
    # adaptive controller's suspend path instead.
    spec_min_accept: float = field(
        default_factory=lambda: float(
            os.environ.get("KUBEML_SPEC_MIN_ACCEPT", "0.10")))

    def serving_mesh_axes(self) -> dict:
        """Parsed ``serving_mesh`` ({} when disabled); same ``ax=n`` comma
        syntax as the CLI's ``--mesh`` (parallel.mesh.parse_mesh_spec)."""
        from ..parallel.mesh import parse_mesh_spec

        return parse_mesh_spec(self.serving_mesh)

    def job_socket_path(self, job_id: str):
        """Unix-socket path for a standalone job's tensor server. Lives under
        the system tmpdir (unix socket paths cap at ~107 bytes — a deep
        data_root would overflow), namespaced by a digest of the data root so
        concurrent clusters (e.g. parallel test runs) can't collide.

        The namespace DIRECTORY is created mode 0700 and its ownership is
        verified — on a multi-user host another user must not be able to
        pre-bind the predictable socket name and spoof model weights at the
        PS (native/weights.py carries no authentication by design; the
        directory permissions are the trust boundary)."""
        import hashlib
        import os
        import tempfile

        ns = hashlib.md5(str(self.data_root).encode()).hexdigest()[:8]
        d = Path(tempfile.gettempdir()) / f"kubeml-{ns}"
        d.mkdir(mode=0o700, exist_ok=True)
        st = d.stat()
        if st.st_uid != os.getuid():
            raise PermissionError(
                f"socket directory {d} is owned by uid {st.st_uid}, not us "
                f"({os.getuid()}); refusing to exchange weights through it"
            )
        os.chmod(d, 0o700)  # exist_ok path: enforce even if created looser
        return d / f"{job_id}.sock"
    # --- weight-movement data plane (engine/dataplane.py) ---
    # wire codec for the PS<->runner weight exchange: "raw" (full binary
    # snapshots), "delta" (lossless — only changed leaves ship), or
    # "delta-int8" (int8-quantized round deltas with an error-feedback
    # residual, per-channel scales per ops/int8_matmul.py — ~4x on the
    # dominant f32 leaves at bounded, non-accumulating reconstruction error)
    dataplane_codec: str = field(
        default_factory=lambda: os.environ.get("KUBEML_DATAPLANE_CODEC",
                                               "delta"))
    # rounds the training loop stages ahead of the one computing (host->HBM
    # slab prefetch, engine/kavg.RoundPrefetcher): 1 = double buffering (the
    # default), 0 = stage synchronously per round, >1 deepens the pipeline
    # for links whose transfer time exceeds a round's compute
    dataplane_prefetch: int = field(
        default_factory=lambda: _env_int("KUBEML_DATAPLANE_PREFETCH", 1))

    @property
    def datasets_dir(self) -> Path:
        return self.data_root / "datasets"

    @property
    def functions_dir(self) -> Path:
        return self.data_root / "functions"

    @property
    def history_path(self) -> Path:
        return self.data_root / "history"

    @property
    def checkpoints_dir(self) -> Path:
        return self.data_root / "checkpoints"

    @property
    def advertise_host(self) -> str:
        """The address CLIENTS dial: a wildcard bind (0.0.0.0/::) is not a
        dialable address, so in-process clients use loopback while the
        services stay bound wide (the containerized mode)."""
        return "127.0.0.1" if self.host in ("0.0.0.0", "::") else self.host

    @property
    def controller_url(self) -> str:
        return f"http://{self.advertise_host}:{self.controller_port}"

    @property
    def scheduler_url(self) -> str:
        return f"http://{self.advertise_host}:{self.scheduler_port}"

    @property
    def ps_url(self) -> str:
        return f"http://{self.advertise_host}:{self.ps_port}"

    @property
    def storage_url(self) -> str:
        return f"http://{self.advertise_host}:{self.storage_port}"

    def ensure_dirs(self) -> None:
        for d in (self.datasets_dir, self.functions_dir, self.history_path, self.checkpoints_dir):
            d.mkdir(parents=True, exist_ok=True)


_default_config: Optional[Config] = None


def get_config() -> Config:
    """Process-wide default config (lazily constructed from the environment)."""
    global _default_config
    if _default_config is None:
        _default_config = Config()
    return _default_config


def set_config(cfg: Config) -> None:
    global _default_config
    _default_config = cfg
