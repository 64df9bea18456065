"""A configuration, a cell, a traffic mix and a per-layer metric dropped
into a copy of ``bench/`` as files are found by name and run, with no
change to the harness's code."""
import json
import os
import subprocess
import sys

from conftest import make_root, tiny_cell

RUN = """
import json, sys, time
t0 = time.perf_counter()
root, workload, trace = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
sys.path[:0] = [root, root + "/src"]
from bench import harness
res = harness.run(root, workload, 2147483647 + 11, 0.5, trace, t0=t0,
                  require_tpu=False)
print(json.dumps(res))
"""


def run_cell(root, workload, trace=False, extra_env=None, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "-c", RUN, root, workload,
                           "1" if trace else "0"], cwd=root, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_new_files_are_found_by_name(tmp_path):
    cell = tiny_cell("uvit_tiny.dropin")
    root = make_root(tmp_path, [cell])
    with open(os.path.join(root, "bench", "metrics", "window_steps.py"),
              "w") as f:
        f.write("def read(run):\n    return run.window_steps\n")
    mpath = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(mpath))
    manifest["per_layer"].append(
        {"name": "window_steps", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "training loop",
         "moves": "samples_per_s", "workloads": [cell["name"]]})
    json.dump(manifest, open(mpath, "w"))

    res, err = run_cell(root, cell["name"], trace=True)
    assert res["correct"] is True, err[-3000:]
    assert set(res["metrics"]) == {"window_steps"}
    assert res["metrics"]["window_steps"]["value"] >= 1
    assert list(res)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("compared change_gap")

    res, _ = run_cell(root, cell["name"])
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}
    assert res["metrics"]["samples_per_s"]["value"] > 0
