"""The model-FLOP counter against hand counts of the cells' steps."""
import json
import os

import pytest

from bench.families import uvit as fam
from conftest import ROOT


def _config(name):
    return json.load(open(os.path.join(ROOT, "bench", "configs",
                                       name + ".json")))


#: (configuration, latent size, global batch) of the cells, and of the
#: 64x64 cell that waits for its first chip run
SHAPES = {"uvit_h8.r32.b32": ("uvit_h8", 32, 32),
          "uvit_h32.p4.r32.b32": ("uvit_h32", 32, 32),
          "uvit_h8.r64.b8": ("uvit_h8", 64, 8)}


def _load(cell):
    name, latent, batch = SHAPES[cell]
    return _config(name), {"latent_size": latent, "global_batch": batch}


@pytest.mark.parametrize("cell,tflop", [
    ("uvit_h8.r32.b32", 34.29),      # 8 layers, B=32, 258 tokens
    ("uvit_h32.p4.r32.b32", 137.1),  # 32 layers, B=32, 258 tokens
    ("uvit_h8.r64.b8", 35.6),        # 8 layers, B=8, 1026 tokens
])
def test_model_flops_per_step(cell, tflop):
    cfg, c = _load(cell)
    # the hand counts carry three to four significant figures
    assert fam.model_flops(cfg, c) / 1e12 == pytest.approx(tflop, rel=2e-3)


def test_attention_share_grows_with_the_latent():
    def share(cell):
        cfg, c = _load(cell)
        n = (c["latent_size"] // cfg["patch"]) ** 2 + 2
        attn = 3 * c["global_batch"] * cfg["n_layers"] * 4 * n * n * cfg["d_model"]
        return attn / fam.model_flops(cfg, c)
    assert share("uvit_h8.r32.b32") == pytest.approx(0.0153, abs=5e-4)
    assert share("uvit_h8.r64.b8") == pytest.approx(0.058, abs=5e-4)


def test_parameter_counts_of_the_configurations():
    import jax
    from bench.reference.uvit import init
    for name in ("uvit_h8", "uvit_h32"):
        cfg = _config(name)
        shapes = jax.eval_shape(lambda k: init(k, cfg, 32),
                                jax.random.PRNGKey(0))
        assert sum(x.size for x in jax.tree.leaves(shapes)) == \
            cfg["parameters"]
