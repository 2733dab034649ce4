"""chip_smoke.py's contract where there is no chip: it fails, says which
platform it found, and prints no result line."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(script, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_smoke_fails_without_an_accelerator_and_names_the_platform():
    p = _run(SMOKE, REPO)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert '"ok": true' not in p.stdout  # no phase passed, no result line


def test_smoke_fails_alone_in_a_directory(tmp_path):
    """The script proves the PROGRAM runs: without the repo around it there
    is nothing to prove, and it must not pass."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert p.returncode != 0
    assert "kubeml_tpu" in p.stderr
    assert '"ok": true' not in p.stdout


def test_result_line_has_exactly_the_contract_keys(monkeypatch, capsys):
    """The driver parses the LAST stdout line: ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) and nothing else; the summary with
    ``"claim": null`` is the line before it."""
    import json

    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    class FakePass:
        def __init__(self, argv, **_):
            name = argv[-1]
            hits = 0 if name == "cold" else 10
            row = {"phase": "pass", "name": name, "ok": True,
                   "device": device, "wall_seconds": 1.0,
                   "compile": {"compile_seconds": 10.0 - hits, "programs": 10,
                               "cache_hits": hits, "cache_writes": 10 - hits}}
            self.stdout = iter([json.dumps(row) + "\n"])

        def wait(self):
            return 0

        def kill(self):
            pass

    monkeypatch.setattr(chip_smoke.subprocess, "Popen", FakePass)
    monkeypatch.setattr("signal.signal", lambda *_: None)  # keep pytest's
    assert chip_smoke.main(["chip_smoke.py"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    summary = json.loads(lines[-2])
    assert summary["phase"] == "cache" and lines[-2].endswith('"claim": null}')


def test_a_phases_compile_row_is_the_programs_clock_between_two_readings():
    """``run_pass`` keeps no meter of its own: a phase's row is the program's
    compile clock (``utils/tracing.py``), its sum over all threads, read where
    the phase begins and where it ends, under the keys the summary had."""
    import threading

    from kubeml_tpu.utils import tracing

    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)

    assert not hasattr(chip_smoke, "CompileMeter")
    clock = tracing.CompileClock()   # fed by hand
    backend = "/jax/core/compile/backend_compile_duration"
    clock.span(backend, 1.0, 1.25)   # before the phase: not in its row
    before = clock.totals()

    def cluster_thread():
        clock.span(backend, 2.0, 3.5)
        clock.event("/jax/compilation_cache/cache_misses")
        clock.span("/jax/core/compile/jaxpr_trace_duration", 3.5, 4.0)

    t = threading.Thread(target=cluster_thread)
    t.start()
    t.join(10.0)
    clock.span(backend, 2.5, 2.625)  # meanwhile, on this thread
    clock.event("/jax/compilation_cache/cache_hits")
    assert chip_smoke.compile_row(before, clock.totals()) == {
        "compile_seconds": 1.62, "programs": 2, "cache_hits": 1,
        "cache_writes": 1}
