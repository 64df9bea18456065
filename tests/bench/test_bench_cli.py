"""``bench/run.py`` refuses a host without a TPU, and a checkout that holds
only the benchmark's own files, and prints no result line either way."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "uvit_h8.r32.b32", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd,
        env={k: v for k, v in dict(os.environ, **env).items()
             if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        return "correct" not in json.loads(last)
    except ValueError:
        return True


def test_exits_nonzero_on_a_cpu():
    proc = _run(ROOT, JAX_PLATFORMS="cpu")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert _no_result(proc)


def test_exits_nonzero_with_only_the_benchmarks_files(tmp_path):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert _no_result(proc)
