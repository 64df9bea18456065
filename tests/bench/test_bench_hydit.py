"""The Hunyuan-DiT cell: its model-FLOP count against the hand count, its
reference against the program at a tiny width, its traffic and its
entries in BENCHMARK.json."""
import json
import os

import jax
import numpy as np
import pytest

from bench.families import hydit as fam
from bench.reference import hydit as reference
from conftest import ROOT

CELL = "hydit_g2.p4.t2i.r128.b8"


def _config(name="hydit_g2"):
    return json.load(open(os.path.join(ROOT, "bench", "configs",
                                       name + ".json")))


def _cell():
    cell = json.load(open(os.path.join(ROOT, "bench", "workloads",
                                       CELL + ".json")))
    return {**json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          cell["traffic"] + ".json"))),
            **cell}


def test_model_flops_per_step():
    """3 x the forward's products of the 40 blocks at 4,096 tokens, B=8:
    per sample 14,347 GFLOP (self-attention scores and PV 3,780, cross-
    attention 1,683, MLP 5,669, the rest projections and skip-ins), so
    344.3 TFLOP a step."""
    cfg, cell = _config(), _cell()
    assert fam.model_flops(cfg, cell) / 1e12 == pytest.approx(344.33,
                                                               rel=1e-3)
    n, d, L = 4096, 1408, 40
    per_sample = fam.model_flops(cfg, cell) / 3 / 8
    assert per_sample / 1e9 == pytest.approx(14_347, rel=1e-3)
    assert L * 4 * n * n * d / per_sample == pytest.approx(0.263, abs=1e-3)
    cross = L * (4 * n * d * d + 4 * 333 * 1024 * d + 4 * n * 333 * d)
    assert cross / 1e9 == pytest.approx(1_683, rel=1e-3)
    assert L * 4 * n * d * 6144 / 1e9 == pytest.approx(5_669, rel=1e-3)


def test_parameter_count_of_the_configuration():
    cfg = _config()
    shapes = jax.eval_shape(lambda k: reference.init(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == \
        cfg["parameters"] == 1_499_952_032


TINY = dict(name="hydit_tiny", d_model=32, n_layers=8, n_heads=4,
            head_dim=8, d_ff=64, text_dim=16, clip_len=5, t5_dim=24,
            t5_len=7, t5_hidden=32, pool_heads=2, pool_dim=16, freq_dim=16,
            rope_base=2, attn_q_block=8, dtype="float32",
            param_dtype="float32")


def test_reference_run_against_the_program():
    """At a tiny width in float32 the family's reference (first step's
    loss) and the program's ``hunyuan_loss`` on the same weights, batch
    and key agree; the reference's starting point is the program's."""
    from repro.models.diffusion import hunyuan_loss, init_hunyuan

    cfg = {**_config(), **TINY}
    cell = {"latent_size": 8, "global_batch": 4, "t5_typical": 4,
            "t5_sigma": 0.5, "plan": {"pp": 2}}
    seed = 2 ** 31 + 12345
    key = jax.random.PRNGKey(seed)
    ref = fam.reference_run(cfg, cell, seed, 1, jax.devices()[:1])
    mc = fam.model_config(cfg, cell)
    params = init_hunyuan(key, mc)
    for a, b in zip(jax.tree.leaves(reference.init(key, cfg)),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    batch = fam.batches(cfg, cell, seed)(0)
    with jax.default_matmul_precision("highest"):
        loss = hunyuan_loss(params, batch, jax.random.fold_in(key, 0), mc)
    np.testing.assert_allclose(float(loss), ref["losses"][0], rtol=1e-4)
    assert set(ref["grad"]) == set(params)


def test_traffic_is_the_cells_and_made_from_the_seed():
    cfg, cell = _config(), _cell()
    make = fam.batches(cfg, cell, 2 ** 31 + 7)
    a, b = make(3), make(3)
    assert a["latents"].shape == (8, 128, 128, 4)
    assert a["clip"].shape == (8, 77, 1024)
    assert a["t5"].shape == (8, 256, 2048)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    counts = np.concatenate([make(s)["t5_len"] for s in range(20)])
    assert counts.min() >= 8 and counts.max() <= 256
    assert 70 <= np.median(counts) <= 125
    np.testing.assert_array_equal(a["clip_len"], np.minimum(a["t5_len"], 77))


def test_the_new_entries():
    """One configuration and one four-chip cell, appended; the cell is
    appended to the ``workloads`` of every per-layer metric, which it
    reports through the same readers as the UViT cells, and no metric is
    added under a second name."""
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [c["name"] for c in m["configs"]][-1] == "hydit_g2"
    w = m["workloads"][-1]
    assert (w["name"], w["config"], w["chips"]) == (CELL, "hydit_g2", 4)
    names = [e["name"] for e in m["per_layer"]]
    assert names == ["plan_s", "compile_s", "host_gap_ms",
                     "device_idle_share", "mfu", "collective_exposed_ms"]
    for e in m["per_layer"]:
        assert e["workloads"][-1] == CELL
        assert CELL not in e["workloads"][:-1]
    assert not [f for f in os.listdir(os.path.join(ROOT, "bench", "metrics"))
                if f.startswith("hydit")]
