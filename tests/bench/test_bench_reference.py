"""The benchmark's float32 reference against the program's own UViT,
``uvit_loss`` and AdamW, at a tiny width on the CPU.

The reference imports nothing of the program; this test is where the two
meet, so that a reference that drifts from the configuration shows here
and not as a refused benchmark run.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import uvit as fam
from bench.reference import uvit as ref
from conftest import TINY_CONFIG

LATENT = 8


def program_cfg(dtype=jnp.float32):
    from repro.models.diffusion import UViTConfig
    c = TINY_CONFIG
    return UViTConfig("tiny", img_size=LATENT, in_ch=c["in_ch"],
                      patch=c["patch"], d_model=c["d_model"],
                      n_layers=c["n_layers"], n_heads=c["n_heads"],
                      d_ff=c["d_ff"], n_classes=c["n_classes"],
                      dtype=dtype, param_dtype=jnp.bfloat16)


def test_init_is_the_programs_bit_for_bit():
    from repro.models.diffusion import init_uvit
    key = jax.random.PRNGKey(2**31 + 3)
    mine = ref.init(key, TINY_CONFIG, LATENT)
    theirs = init_uvit(key, program_cfg())
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_placed_init_is_the_stacked_init():
    key = jax.random.PRNGKey(5)
    r = ref.Reference(TINY_CONFIG, LATENT, jax.devices()[:1])
    placed = r.init(key)
    stacked = ref.init(key, TINY_CONFIG, LATENT)
    for i in range(r.half):
        for part, name in (("enc", "enc_blocks"), ("dec", "dec_blocks")):
            for a, b in zip(jax.tree.leaves(placed[part][i]),
                            jax.tree.leaves(stacked[name])):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b[i], np.float32))


def _batch(seed=9, B=4):
    return fam.Batches(LATENT, 4, TINY_CONFIG["n_classes"], B, seed)(0)


def test_loss_and_grads_match_uvit_loss_in_float32():
    from repro.models.diffusion import uvit_loss
    key = jax.random.PRNGKey(1)
    b = _batch()
    rng = jax.random.PRNGKey(77)
    r = ref.Reference(TINY_CONFIG, LATENT, jax.devices()[:1])
    P = r.init(key)
    loss, g = r.loss_and_grads(P, b["latents"], b["labels"], rng)
    params = ref.init(key, TINY_CONFIG, LATENT)
    cfg = program_cfg(jnp.float32)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(
            lambda p: uvit_loss(p, {k: jnp.asarray(v) for k, v in b.items()},
                                rng, dataclasses.replace(
                                    cfg, param_dtype=jnp.float32)))(f32)
    assert math.isclose(loss, float(want), rel_tol=1e-5)
    got = ref._model_space(g)
    for (path, a), b_ in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                             jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_adamw_matches_the_programs():
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    opt = TINY_CONFIG["optimizer"]
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    p = {"w": jax.random.normal(k[0], (64, 32)).astype(jnp.bfloat16)}
    g = {"w": 3.0 * jax.random.normal(k[1], (64, 32))}
    cfg = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                      eps=opt["eps"], weight_decay=opt["weight_decay"],
                      clip_norm=opt["clip_norm"])
    want, st = adamw_update(p, g, adamw_init(p), cfg)
    gn = float(jnp.sqrt(jnp.sum(g["w"] ** 2)))
    scale = min(1.0, opt["clip_norm"] / (gn + 1e-9))
    hyper = tuple(opt[n] for n in ("lr", "b1", "b2", "eps", "weight_decay"))
    z = jnp.zeros((64, 32), jnp.float32)
    got, m, v = ref._adamw_leaf(p["w"], g["w"], z, z, scale, 1.0, hyper)
    np.testing.assert_allclose(np.asarray(m), np.asarray(st["m"]["w"]),
                               rtol=1e-6, atol=1e-9)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want["w"], np.float32))


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange"])
def test_planted_faults_change_the_readings(fault):
    cell = {"latent_size": LATENT, "global_batch": 4,
            "plan": {"pp": 2}}
    devs = jax.devices()[:1]
    good = fam.reference_run(TINY_CONFIG, cell, 3, 1, devs)
    bad = fam.reference_run(TINY_CONFIG, cell, 3, 1, devs, fault=fault)
    from bench.harness import compare
    gaps = compare(bad, good)
    assert max(gaps["loss_gap"], gaps["grad_gap"]) > 0.02, gaps
