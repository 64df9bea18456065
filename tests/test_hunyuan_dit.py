"""Hunyuan-DiT DiT-g/2: the fold's turnaround on the stack boundary, 2D
RoPE and cross-attention's query-only RoPE against hand rotations, the
published parameter count, and the plans of the other models unchanged."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.smoke import hunyuan_tiny
from repro.core.partition import partition, partition_reference
from repro.models import layers as L
from repro.models.diffusion import hunyuan_pipeline_graph, init_hunyuan
from repro.runtime.compile import StageLayout

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("depth,devices", [(8, 2), (16, 4)],
                         ids=["depth8-P2", "depth16-P4"])
def test_turnaround_on_the_stack_boundary(depth, devices):
    """Depth 8 (5 blocks without skip parameters, 3 with, blocks 3-4
    unpaired) and depth 16 (9 + 7, blocks 7-8 unpaired), shaped like
    DiT-g/2's 21 + 19: the DP turns the fold at the boundary, which the
    unpinned graph would not, and the layout splits the two stacks."""
    cfg = hunyuan_tiny(n_layers=depth)
    graph = hunyuan_pipeline_graph(cfg)
    assert graph.turnaround == cfg.n_enc == depth // 2 + 1
    ends = {b for e in graph.skips for b in (e.src, e.dst)}
    assert sorted(set(range(depth)) - ends) == [depth // 2 - 1, depth // 2]
    part = partition(graph, devices, lam=0.0)
    S = part.num_stages
    assert part.cuts[S // 2] == cfg.n_enc
    assert part.validate_collocation(graph)
    free = partition(dataclasses.replace(graph, turnaround=None), devices,
                     lam=0.0)
    assert free.cuts[S // 2] != cfg.n_enc
    def split(layout):
        def f(key):
            p = init_hunyuan(key, cfg)
            return layout.split((p["enc_blocks"], p["dec_blocks"]))
        return jax.eval_shape(f, jax.random.PRNGKey(0))

    layout = StageLayout.from_partition(part, graph)
    enc, dec = split(layout)
    assert jax.tree.leaves(enc)[0].shape[:3] == (devices, 1, layout.enc_pad)
    assert jax.tree.leaves(dec)[0].shape[:3] == (devices, 1, layout.dec_pad)
    with pytest.raises(ValueError, match="stack boundary"):
        split(StageLayout.from_partition(free, graph))


def test_pinned_dp_matches_the_exact_reference():
    """The brute-force partitioner under the same pin finds the same
    cuts (depth 8, four stages)."""
    graph = hunyuan_pipeline_graph(hunyuan_tiny(n_layers=8))
    assert partition(graph, 2, lam=0.0).cuts == \
        partition_reference(graph, 4, lam=0.0).cuts


def _hand_rotate(x, angle):
    """Turn each pair (2i, 2i+1) of the last axis by ``angle[..., i]``."""
    out = np.array(x, np.float64)
    for i in range(x.shape[-1] // 2):
        c, s = np.cos(angle[..., i]), np.sin(angle[..., i])
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        out[..., 2 * i], out[..., 2 * i + 1] = a * c - b * s, a * s + b * c
    return out


def test_rope_2d_against_a_hand_rotation():
    """Token (r, c) of a 4 x 4 grid at base 2: pairs 0..hd/4-1 turn by
    ``c * 2/4 * theta^(-2k/(hd/2))``, the rest by the row alike."""
    g, base, hd, theta = 4, 2, 8, 10000.0
    ang = L.rope_2d_angles(g, base, hd, theta)
    assert ang.shape == (g * g, hd // 2)
    freq = theta ** (-2.0 * np.arange(hd // 4) / (hd // 2))
    want = np.zeros((g * g, hd // 2))
    for r in range(g):
        for c in range(g):
            want[r * g + c] = np.concatenate(
                [c * base / g * freq, r * base / g * freq])
    np.testing.assert_allclose(ang, want, rtol=1e-6)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, g * g, 3, hd)))
    got = L.apply_rope_interleaved(jnp.asarray(x), jnp.asarray(ang))
    np.testing.assert_allclose(np.asarray(got),
                               _hand_rotate(x, want[None, :, None, :]),
                               rtol=1e-5, atol=1e-6)


def test_cross_attention_turns_the_queries_only():
    """Cross-attention with ``rope_cross_q``: the queries turn by their
    grid positions, the text keys do not; against attention written out
    by hand on the rotated queries."""
    cfg = hunyuan_tiny()
    acfg = cfg.cross_attn_cfg()
    p = L.init_attention(jax.random.PRNGKey(2), acfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.n_tokens, 32))
    text = jax.random.normal(jax.random.PRNGKey(4), (2, 6, cfg.text_dim))
    out, _ = L.apply_attention(p, x, acfg, context=text)
    H, hd = acfg.n_heads, acfg.head_dim
    ln = lambda y, n: np.asarray(L.layer_norm(y, p[n]["scale"],
                                              p[n]["bias"], 1e-6))
    q = ln((x @ p["wq"] + p["bq"]).reshape(2, -1, H, hd), "q_norm")
    k = ln((text @ p["wk"] + p["bk"]).reshape(2, 6, H, hd), "k_norm")
    v = np.asarray((text @ p["wv"] + p["bv"]).reshape(2, 6, H, hd))
    ang = L.rope_2d_angles(*acfg.rope_2d, hd, acfg.rope_theta)
    q = _hand_rotate(q, ang[None, :, None, :])
    logits = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(hd)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    o = np.einsum("bhst,bthd->bshd", w, v).reshape(2, -1, H * hd)
    want = o @ np.asarray(p["wo"]) + np.asarray(p["bo"])
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)


def test_query_blocked_attention_is_the_same_attention():
    """``q_block`` splits the queries into blocks; values and gradients
    are those of the whole attention."""
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    q, kk, v = (jax.random.normal(a, (2, 16, 4, 8)) for a in k)
    f = lambda q, b: jnp.sum(jnp.sin(L.attention(q, kk, v, causal=False,
                                                 q_block=b)))
    np.testing.assert_allclose(f(q, 4), f(q, None), rtol=1e-5)
    np.testing.assert_allclose(jax.grad(f)(q, 4), jax.grad(f)(q, None),
                               rtol=1e-4, atol=1e-6)


def test_unrotated_key_biases_drop_out_of_the_softmax():
    """Cross-attention's k-norm bias and the attention pool's key bias add
    one logit to every key of a query: any value gives the same loss, and
    the program states their gradient as exactly zero."""
    from repro.configs.smoke import hunyuan_tiny_batch
    from repro.models.diffusion import hunyuan_loss

    cfg = hunyuan_tiny(n_layers=8)
    params = init_hunyuan(jax.random.PRNGKey(3), cfg)
    batch = hunyuan_tiny_batch(jax.random.PRNGKey(4), cfg, 2)
    loss = lambda p: hunyuan_loss(p, batch, jax.random.PRNGKey(6), cfg)

    def shifted(p):
        k = iter(jax.random.split(jax.random.PRNGKey(9), 3))
        p = jax.tree.map(lambda x: x, p)
        for stack in ("enc_blocks", "dec_blocks"):
            b = p[stack]["attn2"]["k_norm"]["bias"]
            p[stack]["attn2"]["k_norm"]["bias"] = jax.random.normal(
                next(k), b.shape)
        b = p["pooler"]["k"]["b"]
        p["pooler"]["k"]["b"] = jax.random.normal(next(k), b.shape)
        return p

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(float(loss(shifted(params))),
                                   float(loss(params)), rtol=1e-5)
        g = jax.grad(loss)(params)
    for stack in ("enc_blocks", "dec_blocks"):
        assert not np.any(np.asarray(g[stack]["attn2"]["k_norm"]["bias"]))
        assert np.any(np.asarray(g[stack]["attn1"]["k_norm"]["bias"]))
    assert not np.any(np.asarray(g["pooler"]["k"]["b"]))


def test_published_parameter_count():
    """``jax.eval_shape`` of DiT-g/2 at its published widths gives the
    configuration file's count: 1,439,135,872 in the blocks, 60,816,160
    outside them."""
    from repro.configs.hunyuan_dit import CFG
    shapes = jax.eval_shape(lambda k: init_hunyuan(k, CFG),
                            jax.random.PRNGKey(0))
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))
    blocks = count(shapes["enc_blocks"]) + count(shapes["dec_blocks"])
    assert blocks == 1_439_135_872
    assert count(shapes) - blocks == 60_816_160
    doc = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "hydit_g2.json")))
    assert count(shapes) == doc["parameters"] == CFG.param_count() \
        == 1_499_952_032


#: cuts of plans every other model's graph gave before the turnaround
#: pin existed (UViT, SkipViT, LM; the configs of auto_pipeline_equiv)
_PLANS = [
    ("uvit-uniform-P2", (0, 2, 4, 6, 8)),
    ("uvit-uneven-P2", (0, 1, 4, 7, 8)),
    ("skipvit-asym-P2", (0, 2, 3, 6, 8)),
    ("skipvit-sparse-P2", (0, 2, 3, 6, 8)),
    ("skipvit-interleaved-V2", (0, 1, 2, 3, 4, 7, 8, 9, 10)),
    ("lm-uneven-P4", (0, 1, 3, 7, 8)),
]


def _graph(name):
    from repro.core.graph import BlockGraph
    from repro.models.diffusion import (SkipViTConfig, UViTConfig,
                                        skipvit_pipeline_graph,
                                        uvit_pipeline_graph)
    uvit = UViTConfig("t", img_size=8, in_ch=4, patch=2, d_model=32,
                      n_layers=8, n_heads=4, d_ff=64, n_classes=10)
    uneven = [3, 1, 1, 1, 1, 1, 1, 3]
    if name == "uvit-uniform-P2":
        return uvit_pipeline_graph(uvit), dict(pipeline_devices=2)
    if name == "uvit-uneven-P2":
        return uvit_pipeline_graph(uvit, fwd_times=uneven), \
            dict(pipeline_devices=2)
    asym = [1, 1, 4, 0.5, 0.5, 0.5, 1, 1]
    if name == "skipvit-asym-P2":
        return skipvit_pipeline_graph(
            SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3),
            fwd_times=asym), dict(pipeline_devices=2)
    if name == "skipvit-sparse-P2":
        return skipvit_pipeline_graph(
            SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3,
                          skip_pairs=((0, 7), (2, 5))),
            fwd_times=asym), dict(pipeline_devices=2)
    if name == "skipvit-interleaved-V2":
        return skipvit_pipeline_graph(
            SkipViTConfig("t", n_enc=4, n_mid=2, n_dec=4),
            fwd_times=[1, 1, 2, 4, 0.5, 0.5, 0.5, 1, 1, 2]), \
            dict(pipeline_devices=2, interleave=2)
    from repro.models.layers import AttnConfig
    from repro.models.lm import LMConfig, lm_pipeline_graph
    lm = LMConfig(name="t", vocab=64, d_model=32, n_layers=8,
                  attn=AttnConfig(32, 4, 2, 8), d_ff=64,
                  tied_embeddings=True)
    g = lm_pipeline_graph(lm, fwd_times=[4, 1, 1, 1, 1, 1, 1, 4])
    assert isinstance(g, BlockGraph)
    return g, dict(pipeline_devices=4)


@pytest.mark.parametrize("name,cuts", _PLANS, ids=[n for n, _ in _PLANS])
def test_plans_of_the_other_models_are_unchanged(name, cuts):
    graph, kw = _graph(name)
    assert graph.turnaround is None
    part = partition(graph, kw["pipeline_devices"], lam=0.0,
                     interleave=kw.get("interleave", 1))
    assert part.cuts == cuts


def test_training_cli_runs_hunyuan_through_auto_pipeline(tmp_path):
    """``launch/train.py --arch hunyuan --pipeline`` (DiT's block at drill
    widths) plans with the fold on the stack boundary and trains on two
    CPU devices with the text-conditioned loader; the loss falls."""
    out = tmp_path / "losses.json"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "hunyuan",
         "--pipeline", "--devices", "2", "--steps", "8", "--global-batch",
         "4", "--microbatches", "4", "--lr", "3e-3", "--log-every", "4",
         "--out-json", str(out)], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "turnaround at block 5 (pinned" in proc.stdout
    losses = json.load(open(out))["losses"]
    assert len(losses) == 8
    assert losses["7"] < losses["0"]
