"""Driver for the serving mixes (``open_loop``, ``closed_loop``): one set-up,
then any number of measured windows on it.

Set-up is what a user does once: start the all-in-one cluster with the
configuration's deployment settings, deploy the model function, hand the
seeded weights over as a finished job's final checkpoint, and send the mix's
warm-up requests through ``/generate`` until every program the mix can reach
exists. A window starts the load generator (a child process) against the
controller and reads the program's counters at its edges. After the window
the program is shut down and freed, and the plain reference decides
``correct`` from the tokens the window served."""

from __future__ import annotations

import gc
import json
import re
import shutil
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import export, reduce, spec, traffic

JOB_ID = "bench-serving-job"
TRACE_SECONDS = 4.0       # a traced run's profiler window, inside the window
TRACE_OFFSET = 0.25       # of the window, before the profiler starts


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _JaxPhases:
    """Where jax says a set-up's compiling went: seconds tracing, lowering
    and in the backend compiler (a persistent-cache hit lands there as its
    read time), from jax.monitoring. Logged with the set-up, judged by
    nothing."""

    _KEYS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "backend"}

    def __init__(self):
        import jax

        self.seconds = {v: 0.0 for v in self._KEYS.values()}
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._add)

    def _add(self, event: str, secs: float, **_):
        key = self._KEYS.get(event)
        if key and self.on:
            self.seconds[key] += secs

    def stop(self) -> dict:
        self.on = False
        return self.seconds


@dataclass
class Window:
    """What one measured window left behind."""

    seconds: float
    t_open: float                 # wall clock
    records: list                 # the load generator's, one per request
    counters: tuple               # the decoder's telemetry at open and close
    memory_peak_bytes: int
    engine: dict                  # the "served by this paged decoder" checks
    spans: list = field(default_factory=list)   # program spans (traced run)
    trace_dir: Path | None = None
    trace_wall: tuple | None = None              # profiler start, stop
    trace_mark_wall: float | None = None         # wall time of reduce.MARK


class ServingSystem:
    def __init__(self, cell: spec.Cell, seed: int):
        self.cell, self.seed = cell, int(seed)
        self.cfg_model = cell.config
        self.builder = spec.plugin("models", cell.config["builder"])
        self.root = spec.ROOT / ".cache" / "benchmark" / cell.name
        self.cluster = self.client = self.decoder = None

    # -- set-up ------------------------------------------------------------
    def setup(self) -> dict:
        import jax

        from kubeml_tpu.api.config import Config, set_config
        from kubeml_tpu.cluster import LocalCluster
        from kubeml_tpu.controller.client import KubemlClient

        notes = {}
        phases = _JaxPhases()
        shutil.rmtree(self.root, ignore_errors=True)
        self.root.mkdir(parents=True)
        deploy = {k: v for k, v in self.cfg_model["deployment"].items()
                  if k in Config.__dataclass_fields__}
        cfg = Config(data_root=self.root / "data",
                     controller_port=_free_port(),
                     scheduler_port=_free_port(), ps_port=_free_port(),
                     storage_port=_free_port(), **deploy)
        cfg.ensure_dirs()
        set_config(cfg)
        self.cluster = LocalCluster(config=cfg, serve_http=True).start()
        self.client = KubemlClient(self.cluster.controller_url, timeout=600.0)
        if not self.client.health():
            raise SystemExit("benchmark: controller is not healthy")
        self.client.functions().create(
            self.builder.FUNCTION_NAME,
            self.builder.function_source(self.cfg_model))
        t0 = time.time()
        weights = self.builder.init_weights(self.cfg_model, self.seed)
        jax.block_until_ready(weights)
        notes["weights_s"] = time.time() - t0
        t0 = time.time()
        notes["export"] = export.write_final(
            cfg.checkpoints_dir, JOB_ID,
            self.builder.program_leaves(self.cfg_model, weights),
            self.builder.FUNCTION_NAME)
        del weights
        notes["export_s"] = time.time() - t0
        t0 = time.time()
        warm = traffic.warmup_requests(self.cell.traffic, self.seed,
                                       self.cfg_model["vocab_size"])
        recs = self._drive({"kind": "closed_loop", "clients": 1,
                            "requests": warm, "seconds": 3600.0,
                            "drain_seconds": 0.0, "request_timeout": 1150.0},
                           delay=0.0)()
        bad = [r for r in recs if r["error"]]
        if bad or len(recs) != len(warm):
            raise SystemExit(f"benchmark: warm-up failed: {bad or recs}")
        notes["warmup_s"] = time.time() - t0
        decoders = self.cluster.ps._decoders
        if JOB_ID not in decoders:
            raise SystemExit("benchmark: the parameter server built no "
                             "decoder for the job")
        self.decoder = decoders[JOB_ID][0]
        notes["compiled_programs"] = self.decoder.telemetry()[
            "compiled_programs"]
        notes["jax_seconds"] = phases.stop()
        return notes

    def _drive(self, plan: dict, delay: float):
        """Start the load generator on ``plan``; the window opens ``delay``
        seconds from now (``self._t_open``). Returns the call that waits
        for its end and gives its records."""
        plan = {"url": self.cluster.controller_url, "model_id": JOB_ID,
                "t_open": time.time() + delay, **plan}
        plan_path = self.root / "plan.json"
        out_path = self.root / "records.json"
        plan_path.write_text(json.dumps(plan))
        out_path.unlink(missing_ok=True)
        self._t_open = plan["t_open"]
        child = subprocess.Popen(
            [sys.executable, str(Path(__file__).with_name("loadgen.py")),
             str(plan_path),
             str(out_path)], stdout=subprocess.DEVNULL)
        return lambda: self._collect(child, out_path)

    def _collect(self, child, out_path: Path) -> list:
        try:
            rc = child.wait()
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if rc != 0:
            raise SystemExit(f"benchmark: load generator exited {rc}")
        return json.loads(out_path.read_text())

    # -- one window --------------------------------------------------------
    def window(self, seconds: float, seed: int, trace: bool,
               rate_per_s: float | None = None) -> Window:
        import jax

        mix = dict(self.cell.traffic)
        if rate_per_s is not None:
            mix["rate_per_s"] = rate_per_s
        reqs = traffic.requests(mix, seed, seconds,
                                self.cfg_model["vocab_size"])
        self.last_requests = reqs
        tracer = None
        if trace:
            from kubeml_tpu.utils import tracing

            tracer = tracing.get_tracer()
            tracer.clear()
            tracer.enable()
        dev = jax.local_devices()[0]
        finish = self._drive(
            {"kind": mix["kind"], "clients": mix.get("clients", 0),
             "requests": reqs, "seconds": seconds,
             "drain_seconds": mix["drain_seconds"],
             "request_timeout": mix["drain_seconds"] + 5.0}, delay=1.5)
        t_open = self._t_open
        trace_dir = trace_wall = mark_wall = None
        try:
            time.sleep(max(0.0, t_open - time.time()))
            c0 = self.decoder.telemetry()
            if trace:
                trace_dir = self.root / "trace"
                shutil.rmtree(trace_dir, ignore_errors=True)
                span = min(TRACE_SECONDS, 0.5 * seconds)
                time.sleep(max(0.0, t_open + TRACE_OFFSET * seconds
                               - time.time()))
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
                t_a = time.time()
                try:
                    mark_wall = time.time()
                    with jax.profiler.TraceAnnotation(reduce.MARK):
                        time.sleep(0.001)
                    time.sleep(span)
                finally:
                    # stopping takes many seconds (it collects the trace):
                    # the traced window ends where the call begins
                    t_b = time.time()
                    jax.profiler.stop_trace()
                trace_wall = (t_a, t_b)
            time.sleep(max(0.0, t_open + seconds - time.time()))
            c1 = self.decoder.telemetry()
            peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        finally:
            records = finish()
            if tracer is not None:
                tracer.disable()
        spans = []
        if tracer is not None:
            spans = [s.to_dict() for s in tracer.spans()]
            tracer.clear()
        return Window(seconds=seconds, t_open=t_open, records=records,
                      counters=(c0, c1), memory_peak_bytes=peak,
                      engine=self._engine_checks(), spans=spans,
                      trace_dir=trace_dir, trace_wall=trace_wall,
                      trace_mark_wall=mark_wall)

    def _engine_checks(self) -> dict:
        """The smoke's checks that every request went through the one paged
        decoder the parameter server built, reading the arena through the
        page-walk kernel."""
        from kubeml_tpu.serving import PagedBatchingDecoder

        decoders = self.cluster.ps._decoders
        tel = self.decoder.telemetry()
        return {
            "engine": type(self.decoder).__name__,
            "paged": type(self.decoder) is PagedBatchingDecoder,
            "only_decoder": (len(decoders) == 1 and
                             decoders.get(JOB_ID, (None,))[0] is self.decoder),
            "open": not self.decoder.closed,
            "page_walk_kernel": tel.get("paged_attn_kernel") == 1.0,
            "requests_completed": tel["requests_completed"],
            "requests_failed": tel["requests_failed"],
            "snapshot_replayed": tel.get("snapshot_replayed", 0.0),
        }

    # -- shut down and free --------------------------------------------------
    def teardown(self) -> int:
        """Stop the program and free its device state. Returns the bytes of
        device arrays still alive afterwards (the reference's weights have
        to fit beside whatever is left)."""
        import jax

        decoder, self.decoder = self.decoder, None
        if self.cluster is not None:
            self.cluster.stop()
            self.cluster.ps._decoders.clear()
            self.cluster.ps._serving_cache.clear()
        if decoder is not None:
            decoder.close()
            # the engine's thread owns the arena until it has left its loop
            thread = getattr(decoder, "_thread", None)
            if thread is not None:
                thread.join(30.0)
        self.cluster = self.client = decoder = thread = None
        jax.clear_caches()
        gc.collect()
        gc.collect()
        shutil.rmtree(self.root / "data", ignore_errors=True)
        return int(sum(a.nbytes for a in jax.live_arrays()))

    def cleanup(self) -> None:
        shutil.rmtree(self.root, ignore_errors=True)


# --------------------------------------------------------------------------
# end-to-end metrics, from the load generator's records alone
# --------------------------------------------------------------------------

def _missed(win: Window, mix: dict) -> float:
    # a failed request misses every limit: it counts as the longest wait
    # the run could have seen
    return 1000.0 * (win.seconds + mix["drain_seconds"])


def _percentile_of(per_request, q: int):
    def metric(win: Window, mix: dict) -> float:
        return float(np.percentile(
            [per_request(r, win, mix) for r in win.records], q))
    return metric


def _ttft_ms(r: dict, win: Window, mix: dict) -> float:
    if r["error"] or r["first"] is None:
        return _missed(win, mix)
    return 1000.0 * (r["first"] - r["due"])


def _tpot_ms(r: dict, win: Window, mix: dict) -> float:
    if r["error"] or len(r["tokens"]) < 2:
        return _missed(win, mix)
    return 1000.0 * (r["last"] - r["first"]) / (len(r["tokens"]) - 1)


def output_tokens_per_s(win: Window, mix: dict) -> float:
    """Tokens that reached their clients inside the window, of requests that
    did not fail, over the window."""
    return sum(r["in_window"] for r in win.records
               if not r["error"]) / win.seconds


def tpot_mean_ms(win: Window, mix: dict) -> float:
    """Time per output token over all the window's requests: their decode
    time (first delta to last) over the tokens it produced. A failed
    request counts as the longest wait the run could have seen."""
    wall = steps = 0.0
    for r in win.records:
        n = max(len(r["tokens"]) - 1, 1)
        wall += _tpot_ms(r, win, mix) * n
        steps += n
    return wall / steps


_TAILS = {"ttft": _ttft_ms, "tpot": _tpot_ms}


def end_to_end(name: str):
    """The function for an end-to-end metric's name: ``ttft_p<q>_ms`` and
    ``tpot_p<q>_ms`` for any whole percentile, ``tpot_mean_ms``,
    ``output_tokens_per_s``."""
    if name in ("output_tokens_per_s", "tpot_mean_ms"):
        return globals()[name]
    m = re.fullmatch(r"(ttft|tpot)_p(\d+)_ms", name)
    if not m:
        raise spec.SpecError(f"no end-to-end metric {name!r} for a serving "
                             f"cell")
    return _percentile_of(_TAILS[m.group(1)], int(m.group(2)))
