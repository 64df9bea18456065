"""A copy of the benchmark with one tiny UViT cell, for runs on the CPU."""
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

TINY_CONFIG = {
    "name": "uvit_tiny", "family": "uvit", "source": "test",
    "d_model": 64, "n_layers": 4, "n_heads": 4, "head_dim": 16,
    "d_ff": 128, "patch": 2, "in_ch": 4, "n_classes": 10, "norm_eps": 1e-6,
    "dtype": "bfloat16", "param_dtype": "bfloat16",
    "optimizer": {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-8, "weight_decay": 0.01, "clip_norm": 1.0,
                  "moment_dtype": "float32"},
}
#: limits of the tiny cell, set between its program's readings (largest
#: over six seeds on the CPU: loss 1.4e-3, grad 3.2e-3, change 3.2e-3) and
#: its float8 control's (smallest over three: 4.6e-3, 2.7e-2, 1.1e-2)
TINY_LIMITS = {"loss_gap": 3e-3, "grad_gap": 1e-2, "change_gap": 6e-3}


def tiny_cell(name="uvit_tiny.r8.b8", pp=1, microbatches=2, **extra):
    return {"name": name, "config": "uvit_tiny", "traffic": "r8.b8",
            "chips": pp, "plan": {"dp": 1, "pp": pp, "zero_stage": 0,
                                  "microbatches": microbatches},
            "limits": dict(TINY_LIMITS),
            "why": "tiny CPU cell", **extra}


def make_root(dest, cells=(), metrics=("samples_per_s", "setup_s")):
    """``dest`` holding ``bench/`` (copied), the tiny configuration,
    traffic and ``cells``, and a ``BENCHMARK.json`` naming them; ``src``
    is linked, not copied."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dest, "src"))
    b = os.path.join(dest, "bench")
    with open(os.path.join(b, "configs", "uvit_tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(b, "traffic", "r8.b8.json"), "w") as f:
        json.dump({"name": "r8.b8", "latent_size": 8, "global_batch": 8}, f)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell in cells:
        with open(os.path.join(b, "workloads", cell["name"] + ".json"),
                  "w") as f:
            json.dump(cell, f)
        manifest["workloads"].append(
            {"name": cell["name"], "config": cell["config"],
             "traffic": cell["traffic"], "chips": cell["chips"],
             "why": cell["why"]})
    manifest["end_to_end"] = [m for m in manifest["end_to_end"]
                              if m["name"] in metrics]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(dest)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path, [tiny_cell()])
