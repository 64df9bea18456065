"""The training loop writes its host spans on the profiler's clock: one
``train`` step annotation a step, inside it the benchmark harness's spans
(``batch``, ``place``, ``dispatch``, ``loss_read``), and ``ckpt_save``
around each checkpoint save.

Runs in a subprocess: ``launch/train.py::run`` turns on the persistent
compilation cache for its whole process."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACED = """
import collections, glob, json, sys
import jax
from jax.profiler import ProfileData
from repro.launch.train import _parse_args, run

trace_dir, ckpt_dir = sys.argv[1], sys.argv[2]
with jax.profiler.trace(trace_dir):
    run(_parse_args(["--arch", "uvit", "--steps", "3", "--global-batch",
                     "4", "--ckpt-dir", ckpt_dir, "--ckpt-every", "2"]))
path, = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
names = ("train", "batch", "place", "dispatch", "loss_read", "ckpt_save")
spans, steps = collections.Counter(), []
for plane in ProfileData.from_file(path).planes:
    if not plane.name.startswith("/host:"):
        continue
    for line in plane.lines:
        for ev in line.events:
            if ev.name in names:
                spans[ev.name] += 1
                if ev.name == "train":
                    steps.append(dict(ev.stats)["step_num"])
print("SPANS " + json.dumps({"spans": spans, "steps": sorted(steps)}))
"""


def test_training_loop_spans_on_the_host_plane(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c", TRACED, str(tmp_path / "trace"),
         str(tmp_path / "ckpt")], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SPANS ")][-1]
    found = json.loads(line[len("SPANS "):])
    assert found["steps"] == [0, 1, 2]
    for name in ("train", "batch", "place", "dispatch", "loss_read"):
        assert found["spans"][name] == 3, (name, found)
    # a save after step 2 and the final one after step 3
    assert found["spans"]["ckpt_save"] == 2
