"""``bench/run.py`` prints no result where it cannot measure: without a
TPU, and in a directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

from bench import run as harness

ROOT = harness.ROOT
ARGS = ["--workload", "sweep.paper-table2.numbers", "--seed",
        str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except json.JSONDecodeError:
            pass


def test_cpu_only_run_is_refused():
    proc = _run(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_benchmark_files_alone_cannot_run_past_the_device(tmp_path):
    """Even where a device is found, the benchmark has no system to run
    without the rest of the checkout."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            f"run.run({ARGS[1]!r}, 1, 1.0, False, require_tpu=False)")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env={**env, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "ModuleNotFoundError" in proc.stderr
