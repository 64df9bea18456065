"""Diffusion backbones + DDPM objective + block-graph exports."""
import jax
import pytest
import jax.numpy as jnp

from repro.models.diffusion import (UViTConfig, init_uvit, uvit_loss,
                                    uvit_apply, uvit_block_graph,
                                    init_hunyuan,
                                    hunyuan_loss, hunyuan_block_graph,
                                    UNetConfig, init_unet, unet_loss,
                                    unet_block_graph, cosine_alpha_bar)

KEY = jax.random.PRNGKey(2)


def test_cosine_schedule_bounds():
    t = jnp.linspace(0, 1, 11)
    ab = cosine_alpha_bar(t)
    assert float(ab[0]) > 0.99
    assert float(ab[-1]) < 0.01
    assert bool(jnp.all(ab[:-1] >= ab[1:]))


@pytest.mark.slow
def test_uvit_loss_and_shapes():
    cfg = UViTConfig("t", img_size=8, in_ch=4, patch=2, d_model=32,
                     n_layers=4, n_heads=4, d_ff=64, n_classes=10)
    p = init_uvit(KEY, cfg)
    batch = {"latents": jax.random.normal(KEY, (2, 8, 8, 4)),
             "labels": jnp.array([1, 2])}
    pred = uvit_apply(p, batch["latents"], jnp.array([0.1, 0.9]), batch, cfg)
    assert pred.shape == (2, 8, 8, 4)
    loss = uvit_loss(p, batch, KEY, cfg)
    assert jnp.isfinite(loss)
    g = jax.grad(lambda p: uvit_loss(p, batch, KEY, cfg))(p)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


def test_skip_kernel_differential_uvit():
    """use_skip_kernel routes every decoder skip-in through the fused
    Pallas skip_concat_matmul (interpret mode on CPU); forward and grads
    must match the jnp.concatenate(...) @ skip_proj reference."""
    import dataclasses
    import numpy as np
    cfg = UViTConfig("t", img_size=8, in_ch=4, patch=2, d_model=32,
                     n_layers=4, n_heads=4, d_ff=64, n_classes=10)
    cfg_k = dataclasses.replace(cfg, use_skip_kernel=True)
    p = init_uvit(KEY, cfg)
    batch = {"latents": jax.random.normal(KEY, (2, 8, 8, 4)),
             "labels": jnp.array([1, 2])}
    t = jnp.array([0.1, 0.9])
    ref = uvit_apply(p, batch["latents"], t, batch, cfg)
    ker = uvit_apply(p, batch["latents"], t, batch, cfg_k)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    gr = jax.grad(lambda p: uvit_loss(p, batch, KEY, cfg))(p)
    gk = jax.grad(lambda p: uvit_loss(p, batch, KEY, cfg_k))(p)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_skip_kernel_differential_hunyuan():
    """Same flag on the Hunyuan-DiT blocks that take a skip: the LayerNorm
    over ``[x | skip]`` is taken over the two halves apart and the fused
    kernel does the projection; forward and grads match the concat."""
    import dataclasses
    import numpy as np
    from repro.configs.smoke import hunyuan_tiny, hunyuan_tiny_batch
    from repro.models.diffusion import hunyuan_apply
    cfg = hunyuan_tiny("t", n_layers=4)
    cfg_k = dataclasses.replace(cfg, use_skip_kernel=True)
    p = init_hunyuan(KEY, cfg)
    batch = hunyuan_tiny_batch(KEY, cfg)
    t = jnp.array([0.1, 0.9])
    ref = hunyuan_apply(p, batch["latents"], t, batch, cfg)
    ker = hunyuan_apply(p, batch["latents"], t, batch, cfg_k)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    gr = jax.grad(lambda p: hunyuan_loss(p, batch, KEY, cfg))(p)
    gk = jax.grad(lambda p: hunyuan_loss(p, batch, KEY, cfg_k))(p)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_uvit_graph_nested_symmetric():
    cfg = UViTConfig("t", img_size=8, d_model=32, n_layers=8, n_heads=4,
                     d_ff=64)
    g = uvit_block_graph(cfg, 2)
    assert g.is_nested()
    assert len(g.skips) == cfg.half
    for e in g.skips:
        assert g.blocks[e.src].name.startswith("enc")
        assert g.blocks[e.dst].name.startswith("dec")


@pytest.mark.slow
def test_hunyuan_loss():
    from repro.configs.smoke import hunyuan_tiny, hunyuan_tiny_batch
    from repro.models.diffusion import hunyuan_apply
    cfg = hunyuan_tiny("t", n_layers=8)
    p = init_hunyuan(KEY, cfg)
    batch = hunyuan_tiny_batch(KEY, cfg)
    pred = hunyuan_apply(p, batch["latents"], jnp.array([0.1, 0.9]), batch,
                         cfg)
    assert pred.shape == (2, 8, 8, cfg.out_ch)
    loss = hunyuan_loss(p, batch, KEY, cfg)
    assert jnp.isfinite(loss)
    g = hunyuan_block_graph(cfg, 2)
    assert g.is_nested() and len(g.skips) == cfg.n_skips == 3


@pytest.mark.slow
def test_unet_loss_and_heterogeneous_graph():
    cfg = UNetConfig("t", img_size=16, in_ch=4, base_ch=16, ch_mults=(1, 2),
                     blocks_per_level=2, attn_levels=(1,), ctx_dim=16,
                     n_heads=4)
    p = init_unet(KEY, cfg)
    batch = {"latents": jax.random.normal(KEY, (2, 16, 16, 4)),
             "text_embeds": jax.random.normal(KEY, (2, 7, 16))}
    loss = unet_loss(p, batch, KEY, cfg)
    assert jnp.isfinite(loss)
    g = unet_block_graph(cfg, 2)
    assert g.is_nested()
    times = [b.fwd_time for b in g.blocks]
    assert max(times) / (sum(times) / len(times)) > 1.5  # Fig. 6 heavy tail
