"""Diffusion backbones from the paper: UViT, Hunyuan-DiT, SDv2-style UNet.

All three share the latent-diffusion training objective (DDPM noise
prediction; VAE/text encoders are preprocessing per paper §VII and enter as
precomputed latents / embeddings).

Structure is deliberately pipeline-aligned:
- UViT / Hunyuan-DiT: ``enc_blocks`` (stacked [L/2,...]) and ``dec_blocks``
  (stacked, with an extra ``skip_proj``) — exactly the two parameter groups
  the folded wave executor shards over devices.
- SDv2 UNet: heterogeneous conv/attention blocks at four resolutions;
  exported to a BlockGraph whose per-block costs reproduce the paper's
  Fig. 6 heavy-tail imbalance.

``to_block_graph`` exports each model for the PULSE planner.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.graph import Block, BlockGraph, SkipEdge
from repro.core.hw import Hardware, TPU_V5E
from repro.kernels.skip_matmul import (skip_concat_matmul,
                                       skip_concat_matmul_supported)
from repro.models import layers as L
from repro.models.layers import AttnConfig, Params, Array
from repro.runtime import scopes


# --------------------------------------------------------------------------
# DDPM objective
# --------------------------------------------------------------------------

def cosine_alpha_bar(t: Array, s: float = 0.008) -> Array:
    """t in [0,1] -> cumulative alpha (Nichol & Dhariwal cosine schedule)."""
    f = jnp.cos((t + s) / (1 + s) * jnp.pi / 2) ** 2
    f0 = math.cos(s / (1 + s) * math.pi / 2) ** 2
    return jnp.clip(f / f0, 1e-5, 1.0)


def ddpm_loss(apply_fn, params: Params, batch: dict, rng: Array) -> Array:
    """batch: {"latents": (B,H,W,C), ...conditioning...}."""
    x0 = batch["latents"]
    B = x0.shape[0]
    rt, rn = jax.random.split(rng)
    t = jax.random.uniform(rt, (B,))
    ab = cosine_alpha_bar(t)[:, None, None, None]
    noise = jax.random.normal(rn, x0.shape, x0.dtype)
    xt = jnp.sqrt(ab) * x0 + jnp.sqrt(1 - ab) * noise
    pred = apply_fn(params, xt, t, batch)
    return jnp.mean(jnp.square(pred.astype(jnp.float32)
                               - noise.astype(jnp.float32)))


def timestep_embedding(t: Array, dim: int) -> Array:
    """t in [0,1] -> (B, dim) sinusoidal features."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t[:, None] * 1000.0 * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


# --------------------------------------------------------------------------
# UViT (paper [8]): ViT with symmetric long skips
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UViTConfig:
    name: str
    img_size: int = 32
    in_ch: int = 4
    patch: int = 2
    d_model: int = 512
    n_layers: int = 12            # even: L/2 enc + L/2 dec
    n_heads: int = 8
    d_ff: int = 2048
    n_classes: int = 1001         # class-conditional (UViT on ImageNet)
    norm_eps: float = 1e-6
    use_skip_kernel: bool = False  # fused Pallas skip-in (see _skip_project)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def n_tokens(self) -> int:
        return (self.img_size // self.patch) ** 2 + 2  # + time + class tokens

    @property
    def half(self) -> int:
        return self.n_layers // 2

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_heads,
                          self.d_model // self.n_heads, rope_theta=0.0,
                          causal=False)

    def param_count(self) -> int:
        d = self.d_model
        per = 4 * d * d + 2 * d * self.d_ff
        skip = d * 2 * d
        return (self.n_layers * per + self.half * skip
                + self.n_classes * d + self.patch ** 2 * self.in_ch * d * 2)


def _init_vit_block(key, cfg, d_ff: int, with_skip: bool,
                    cross_dim: int = 0, ada: bool = False) -> Params:
    ks = jax.random.split(key, 6)
    d, pd = cfg.d_model, cfg.param_dtype
    p: Params = {
        "ln1": jnp.ones((d,), pd),
        "attn": L.init_attention(ks[0], cfg.attn_cfg(), pd),
        "ln2": jnp.ones((d,), pd),
        "mlp": L.init_gelu_mlp(ks[1], d, d_ff, pd),
    }
    if with_skip:
        p["skip_proj"] = L.dense_init(ks[2], 2 * d, d, pd)
    if cross_dim:
        p["lnx"] = jnp.ones((d,), pd)
        p["xattn"] = L.init_attention(ks[3], cfg.attn_cfg(), pd)
        p["ctx_kv"] = L.dense_init(ks[4], cross_dim, 2 * d, pd)
    if ada:
        p["ada"] = (jax.random.normal(ks[5], (d, 6 * d)) * 0.02 / math.sqrt(d)
                    ).astype(pd)
    return p


@jax.named_scope(scopes.SKIP_PROJ)
def _skip_project(p: Params, x: Array, skip: Array, cfg) -> Array:
    """Decoder skip-in projection: ``y = [x | skip] @ skip_proj``.

    With ``cfg.use_skip_kernel`` the fused Pallas kernel
    (``h @ W1 + s @ W2``, f32 accumulation; interpret mode on the CPU)
    replaces the concat matmul — the concat materialises the ``(.., 2D)``
    activation in HBM just to read it back once.  Operand shapes that do
    not tile the kernel's 128-square MXU blocks raise: a caller that asks
    for the kernel gets it, never a silent switch to the reference.
    """
    w = p["skip_proj"].astype(x.dtype)
    if getattr(cfg, "use_skip_kernel", False):
        rows = math.prod(x.shape[:-1])
        if not skip_concat_matmul_supported(rows, x.shape[-1], w.shape[1]):
            raise ValueError(
                f"use_skip_kernel: skip-in operands ({rows}, {x.shape[-1]}) "
                f"x ({w.shape[0]}, {w.shape[1]}) do not tile the fused "
                "kernel's blocks (rows = tokens x batch must be a multiple "
                "of 128 or at most 128)")
        return skip_concat_matmul(x, skip.astype(x.dtype), w)
    return jnp.concatenate([x, skip], axis=-1) @ w


def _apply_vit_block(p: Params, x: Array, cfg, *, skip: Array | None = None,
                     ctx: Array | None = None, temb: Array | None = None
                     ) -> Array:
    if skip is not None:
        x = _skip_project(p, x, skip, cfg)
    if temb is not None and "ada" in p:
        mods = (jax.nn.silu(temb) @ p["ada"].astype(temb.dtype))[:, None]
        s1, b1, g1, s2, b2, g2 = jnp.split(mods, 6, axis=-1)
    else:
        s1 = b1 = s2 = b2 = 0.0
        g1 = g2 = 1.0
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps) * (1 + s1) + b1
    a, _ = L.apply_attention(p["attn"], h, cfg.attn_cfg())
    x = x + g1 * a
    if ctx is not None and "xattn" in p:
        h = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        kv = ctx @ p["ctx_kv"].astype(ctx.dtype)
        d = cfg.d_model
        B, T = ctx.shape[0], ctx.shape[1]
        hd = cfg.attn_cfg().head_dim
        kx = kv[..., :d].reshape(B, T, cfg.n_heads, hd)
        vx = kv[..., d:].reshape(B, T, cfg.n_heads, hd)
        a, _ = L.apply_attention(p["xattn"], h, cfg.attn_cfg(),
                                 cross_kv=(kx, vx))
        x = x + a
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps) * (1 + s2) + b2
    return x + g2 * L.apply_gelu_mlp(p["mlp"], h)


def init_uvit(key, cfg: UViTConfig) -> Params:
    ks = jax.random.split(key, 8)
    d, pd = cfg.d_model, cfg.param_dtype
    pp = cfg.patch ** 2 * cfg.in_ch
    ek = jax.random.split(ks[0], cfg.half)
    dk = jax.random.split(ks[1], cfg.half)
    return {
        "patch_embed": L.dense_init(ks[2], pp, d, pd),
        "pos_embed": (jax.random.normal(ks[3], (cfg.n_tokens, d)) * 0.02
                      ).astype(pd),
        "time_mlp": L.init_gelu_mlp(ks[4], d, 4 * d, pd),
        "class_embed": L.dense_init(ks[5], cfg.n_classes, d, pd),
        "enc_blocks": jax.vmap(
            lambda k: _init_vit_block(k, cfg, cfg.d_ff, False))(ek),
        "dec_blocks": jax.vmap(
            lambda k: _init_vit_block(k, cfg, cfg.d_ff, True))(dk),
        "out_norm": jnp.ones((d,), pd),
        "out_proj": L.dense_init(ks[6], d, pp, pd),
    }


def _patchify(x: Array, patch: int) -> Array:
    B, H, W, C = x.shape
    x = x.reshape(B, H // patch, patch, W // patch, patch, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, (H // patch) * (W // patch), patch * patch * C)


def _unpatchify(x: Array, patch: int, img: int, ch: int) -> Array:
    B = x.shape[0]
    g = img // patch
    x = x.reshape(B, g, g, patch, patch, ch)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, img, img, ch)


def uvit_embed(params: Params, xt: Array, t: Array, batch: dict,
               cfg: UViTConfig) -> Array:
    tok = _patchify(xt.astype(cfg.dtype), cfg.patch) @ params["patch_embed"].astype(cfg.dtype)
    temb = L.apply_gelu_mlp(params["time_mlp"],
                            timestep_embedding(t, cfg.d_model).astype(cfg.dtype))
    cemb = params["class_embed"][batch["labels"]].astype(cfg.dtype)
    x = jnp.concatenate([temb[:, None], cemb[:, None], tok], axis=1)
    return x + params["pos_embed"].astype(cfg.dtype)[None]


def uvit_output(params: Params, x: Array, cfg: UViTConfig) -> Array:
    x = L.rms_norm(x, params["out_norm"], cfg.norm_eps)
    pix = x[:, 2:] @ params["out_proj"].astype(x.dtype)
    return _unpatchify(pix, cfg.patch, cfg.img_size, cfg.in_ch)


def uvit_apply(params: Params, xt: Array, t: Array, batch: dict,
               cfg: UViTConfig) -> Array:
    """Reference (non-pipelined) forward; the wave executor replicates this
    computation distributed over stages and is tested for exact agreement."""
    x = uvit_embed(params, xt, t, batch, cfg)

    def enc(x, bp):
        x = _apply_vit_block(bp, x, cfg)
        return x, x                       # ys = skip activations

    x, skips = jax.lax.scan(enc, x, params["enc_blocks"])

    def dec(x, inp):
        bp, skip = inp
        return _apply_vit_block(bp, x, cfg, skip=skip), None

    # decoder block j consumes the skip of encoder block half-1-j
    x, _ = jax.lax.scan(dec, x, (params["dec_blocks"], skips[::-1]))
    return uvit_output(params, x, cfg)


def uvit_loss(params: Params, batch: dict, rng: Array, cfg: UViTConfig) -> Array:
    return ddpm_loss(lambda p, xt, t, b: uvit_apply(p, xt, t, b, cfg),
                     params, batch, rng)


def uvit_block_graph(cfg: UViTConfig, batch: int,
                     hw: Hardware = TPU_V5E) -> BlockGraph:
    d, n, ff = cfg.d_model, cfg.n_tokens, cfg.d_ff
    act = batch * n * d * 2                     # bf16 activation bytes
    attn_fl = 2 * batch * (4 * n * d * d + 2 * n * n * d)
    mlp_fl = 2 * batch * (2 * n * d * ff)
    blk_fl = attn_fl + mlp_fl
    per_param = (4 * d * d + 2 * d * ff) * 2
    blocks = [Block("embed", 0.0, cfg.n_classes * d * 2, act, 0,
                    2 * batch * n * (cfg.patch ** 2 * cfg.in_ch) * d)]
    for i in range(cfg.half):
        blocks.append(Block(f"enc{i}", 0.0, per_param, act, act, blk_fl))
    for i in range(cfg.half):
        blocks.append(Block(f"dec{i}", 0.0, per_param + 2 * d * d * 2, act, 0,
                            blk_fl + 2 * batch * n * 2 * d * d))
    blocks.append(Block("out", 0.0, d * cfg.patch ** 2 * cfg.in_ch * 2, act, 0,
                        2 * batch * n * d * (cfg.patch ** 2 * cfg.in_ch)))
    total = len(blocks)
    skips = tuple(SkipEdge(1 + i, total - 2 - i, act) for i in range(cfg.half))
    from repro.core.profiler import analytic_block_costs
    return BlockGraph(analytic_block_costs(blocks, hw), skips)


# --------------------------------------------------------------------------
# Hunyuan-DiT (paper [7]): DiT with adaLN + text cross-attention + skips
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HunyuanDiTConfig:
    name: str
    img_size: int = 64
    in_ch: int = 4
    patch: int = 2
    d_model: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    d_ff: int = 4096
    ctx_dim: int = 1024           # CLIP+T5 text embedding dim (stub input)
    ctx_len: int = 77
    norm_eps: float = 1e-6
    use_skip_kernel: bool = False  # fused Pallas skip-in (see _skip_project)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def n_tokens(self) -> int:
        return (self.img_size // self.patch) ** 2

    @property
    def half(self) -> int:
        return self.n_layers // 2

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_heads,
                          self.d_model // self.n_heads, rope_theta=0.0,
                          causal=False)

    def param_count(self) -> int:
        d = self.d_model
        per = 4 * d * d + 2 * d * self.d_ff + 4 * d * d + 6 * d * d \
            + self.ctx_dim * 2 * d
        return self.n_layers * per + self.half * 2 * d * d


def init_hunyuan(key, cfg: HunyuanDiTConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, pd = cfg.d_model, cfg.param_dtype
    pp = cfg.patch ** 2 * cfg.in_ch
    ek = jax.random.split(ks[0], cfg.half)
    dk = jax.random.split(ks[1], cfg.half)
    mk = lambda k, skip: _init_vit_block(k, cfg, cfg.d_ff, skip,
                                         cross_dim=cfg.ctx_dim, ada=True)
    return {
        "patch_embed": L.dense_init(ks[2], pp, d, pd),
        "pos_embed": (jax.random.normal(ks[3], (cfg.n_tokens, d)) * 0.02
                      ).astype(pd),
        "time_mlp": L.init_gelu_mlp(ks[4], d, 4 * d, pd),
        "enc_blocks": jax.vmap(lambda k: mk(k, False))(ek),
        "dec_blocks": jax.vmap(lambda k: mk(k, True))(dk),
        "out_norm": jnp.ones((d,), pd),
        "out_proj": L.dense_init(ks[5], d, pp, pd),
    }


def hunyuan_apply(params: Params, xt: Array, t: Array, batch: dict,
                  cfg: HunyuanDiTConfig) -> Array:
    tok = _patchify(xt.astype(cfg.dtype), cfg.patch) @ params["patch_embed"].astype(cfg.dtype)
    x = tok + params["pos_embed"].astype(cfg.dtype)[None]
    temb = L.apply_gelu_mlp(params["time_mlp"],
                            timestep_embedding(t, cfg.d_model).astype(cfg.dtype))
    ctx = batch["text_embeds"].astype(cfg.dtype)

    def enc(x, bp):
        x = _apply_vit_block(bp, x, cfg, ctx=ctx, temb=temb)
        return x, x

    x, skips = jax.lax.scan(enc, x, params["enc_blocks"])

    def dec(x, inp):
        bp, skip = inp
        return _apply_vit_block(bp, x, cfg, skip=skip, ctx=ctx, temb=temb), None

    x, _ = jax.lax.scan(dec, x, (params["dec_blocks"], skips[::-1]))
    x = L.rms_norm(x, params["out_norm"], cfg.norm_eps)
    pix = x @ params["out_proj"].astype(x.dtype)
    return _unpatchify(pix, cfg.patch, cfg.img_size, cfg.in_ch)


def hunyuan_loss(params: Params, batch: dict, rng: Array,
                 cfg: HunyuanDiTConfig) -> Array:
    return ddpm_loss(lambda p, xt, t, b: hunyuan_apply(p, xt, t, b, cfg),
                     params, batch, rng)


def hunyuan_block_graph(cfg: HunyuanDiTConfig, batch: int,
                        hw: Hardware = TPU_V5E) -> BlockGraph:
    d, n, ff, lt = cfg.d_model, cfg.n_tokens, cfg.d_ff, cfg.ctx_len
    act = batch * n * d * 2
    blk_fl = 2 * batch * (4 * n * d * d + 2 * n * n * d + 2 * n * d * ff
                          + 2 * n * d * d + cfg.ctx_dim * 2 * d * lt
                          + 2 * n * lt * d + 6 * n * d * d // n)
    per_param = (4 * d * d + 2 * d * ff + 2 * d * d + cfg.ctx_dim * 2 * d
                 + 6 * d * d) * 2
    blocks = [Block("embed", 0.0, d * 8, act, 0, 2 * batch * n * 16 * d)]
    for i in range(cfg.half):
        blocks.append(Block(f"enc{i}", 0.0, per_param, act, act, blk_fl))
    for i in range(cfg.half):
        blocks.append(Block(f"dec{i}", 0.0, per_param + 8 * d * d, act, 0,
                            blk_fl + 2 * batch * n * 2 * d * d))
    blocks.append(Block("out", 0.0, d * 16 * 2, act, 0, 2 * batch * n * d * 16))
    total = len(blocks)
    skips = tuple(SkipEdge(1 + i, total - 2 - i, act) for i in range(cfg.half))
    from repro.core.profiler import analytic_block_costs
    return BlockGraph(analytic_block_costs(blocks, hw), skips)


# --------------------------------------------------------------------------
# SDv2-style UNet (heterogeneous conv + attention blocks)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UNetConfig:
    name: str
    img_size: int = 32
    in_ch: int = 4
    base_ch: int = 128
    ch_mults: tuple[int, ...] = (1, 2, 4, 4)
    blocks_per_level: int = 2
    attn_levels: tuple[int, ...] = (1, 2, 3)
    ctx_dim: int = 512            # CLIP text embedding dim
    ctx_len: int = 77
    n_heads: int = 8
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def level_ch(self, lvl: int) -> int:
        return self.base_ch * self.ch_mults[lvl]

    def param_count(self) -> int:
        total = 0
        for lvl, m in enumerate(self.ch_mults):
            c = self.base_ch * m
            total += self.blocks_per_level * (2 * 9 * c * c + c * c)
            if lvl in self.attn_levels:
                total += self.blocks_per_level * (4 * c * c + self.ctx_dim * 2 * c
                                                  + 8 * c * c)
        return 2 * total + 10 * self.base_ch ** 2 * self.ch_mults[-1] ** 2


def _conv_init(key, kh, kw, cin, cout, dtype):
    scale = 1.0 / math.sqrt(kh * kw * cin)
    return (jax.random.normal(key, (kh, kw, cin, cout)) * scale).astype(dtype)


def conv2d(x: Array, w: Array, stride: int = 1) -> Array:
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def group_norm(x: Array, scale: Array, bias: Array, groups: int = 8,
               eps: float = 1e-5) -> Array:
    B, H, W, C = x.shape
    xg = x.reshape(B, H, W, groups, C // groups).astype(jnp.float32)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    out = xg.reshape(B, H, W, C) * scale + bias
    return out.astype(x.dtype)


def _init_resblock(key, cin, cout, temb_dim, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "gn1": jnp.ones((cin,)), "gb1": jnp.zeros((cin,)),
        "conv1": _conv_init(ks[0], 3, 3, cin, cout, dtype),
        "temb": L.dense_init(ks[1], temb_dim, cout, dtype),
        "gn2": jnp.ones((cout,)), "gb2": jnp.zeros((cout,)),
        "conv2": _conv_init(ks[2], 3, 3, cout, cout, dtype),
    }
    if cin != cout:
        p["skip_conv"] = _conv_init(ks[3], 1, 1, cin, cout, dtype)
    return p


def _apply_resblock(p: Params, x: Array, temb: Array, cfg: UNetConfig) -> Array:
    h = jax.nn.silu(group_norm(x, p["gn1"], p["gb1"], eps=cfg.norm_eps))
    h = conv2d(h, p["conv1"])
    h = h + (jax.nn.silu(temb) @ p["temb"].astype(temb.dtype))[:, None, None]
    h = jax.nn.silu(group_norm(h, p["gn2"], p["gb2"], eps=cfg.norm_eps))
    h = conv2d(h, p["conv2"])
    if "skip_conv" in p:
        x = conv2d(x, p["skip_conv"])
    return x + h


def _init_attnblock(key, c, cfg: UNetConfig) -> Params:
    ks = jax.random.split(key, 4)
    d = c
    acfg = AttnConfig(d, cfg.n_heads, cfg.n_heads, d // cfg.n_heads,
                      rope_theta=0.0, causal=False)
    return {
        "gn": jnp.ones((c,)), "gb": jnp.zeros((c,)),
        "attn": L.init_attention(ks[0], acfg, cfg.param_dtype),
        "lnx": jnp.ones((c,)),
        "ctx_kv": L.dense_init(ks[1], cfg.ctx_dim, 2 * c, cfg.param_dtype),
        "xattn": L.init_attention(ks[2], acfg, cfg.param_dtype),
        "ln2": jnp.ones((c,)),
        "mlp": L.init_gelu_mlp(ks[3], c, 4 * c, cfg.param_dtype),
    }


def _apply_attnblock(p: Params, x: Array, ctx: Array, cfg: UNetConfig) -> Array:
    B, H, W, C = x.shape
    acfg = AttnConfig(C, cfg.n_heads, cfg.n_heads, C // cfg.n_heads,
                      rope_theta=0.0, causal=False)
    t = group_norm(x, p["gn"], p["gb"], eps=cfg.norm_eps).reshape(B, H * W, C)
    a, _ = L.apply_attention(p["attn"], t, acfg)
    t = x.reshape(B, H * W, C) + a
    h = L.rms_norm(t, p["lnx"], cfg.norm_eps)
    kv = ctx @ p["ctx_kv"].astype(ctx.dtype)
    hd = C // cfg.n_heads
    kx = kv[..., :C].reshape(B, -1, cfg.n_heads, hd)
    vx = kv[..., C:].reshape(B, -1, cfg.n_heads, hd)
    a, _ = L.apply_attention(p["xattn"], h, acfg, cross_kv=(kx, vx))
    t = t + a
    h = L.rms_norm(t, p["ln2"], cfg.norm_eps)
    t = t + L.apply_gelu_mlp(p["mlp"], h)
    return t.reshape(B, H, W, C)


def init_unet(key, cfg: UNetConfig) -> Params:
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 256))
    temb_dim = 4 * cfg.base_ch
    k1, k2 = jax.random.split(next(keys))
    p: Params = {
        "time_mlp": {"w1": L.dense_init(k1, cfg.base_ch, temb_dim, pd),
                     "b1": jnp.zeros((temb_dim,), pd),
                     "w2": L.dense_init(k2, temb_dim, temb_dim, pd),
                     "b2": jnp.zeros((temb_dim,), pd)},
        "in_conv": _conv_init(next(keys), 3, 3, cfg.in_ch, cfg.base_ch, pd),
        "down": [], "up": [],
    }
    c = cfg.base_ch
    chans = [c]
    for lvl, m in enumerate(cfg.ch_mults):
        cout = cfg.base_ch * m
        level = []
        for _ in range(cfg.blocks_per_level):
            blk = {"res": _init_resblock(next(keys), c, cout, temb_dim, pd)}
            if lvl in cfg.attn_levels:
                blk["attn"] = _init_attnblock(next(keys), cout, cfg)
            level.append(blk)
            c = cout
            chans.append(c)
        if lvl < len(cfg.ch_mults) - 1:
            level.append({"downsample": _conv_init(next(keys), 3, 3, c, c, pd)})
            chans.append(c)
        p["down"].append(level)
    p["mid"] = {
        "res1": _init_resblock(next(keys), c, c, temb_dim, pd),
        "attn": _init_attnblock(next(keys), c, cfg),
        "res2": _init_resblock(next(keys), c, c, temb_dim, pd),
    }
    for lvl in reversed(range(len(cfg.ch_mults))):
        cout = cfg.base_ch * cfg.ch_mults[lvl]
        level = []
        for _ in range(cfg.blocks_per_level + 1):
            cskip = chans.pop()
            blk = {"res": _init_resblock(next(keys), c + cskip, cout, temb_dim, pd)}
            if lvl in cfg.attn_levels:
                blk["attn"] = _init_attnblock(next(keys), cout, cfg)
            level.append(blk)
            c = cout
        if lvl > 0:
            level.append({"upsample": _conv_init(next(keys), 3, 3, c, c, pd)})
        p["up"].append(level)
    p["out_gn"] = jnp.ones((c,))
    p["out_gb"] = jnp.zeros((c,))
    p["out_conv"] = _conv_init(next(keys), 3, 3, c, cfg.in_ch, pd)
    return p


def unet_apply(params: Params, xt: Array, t: Array, batch: dict,
               cfg: UNetConfig) -> Array:
    ctx = batch["text_embeds"].astype(cfg.dtype)
    tm = params["time_mlp"]
    temb = timestep_embedding(t, cfg.base_ch).astype(cfg.dtype)
    temb = jax.nn.gelu(temb @ tm["w1"].astype(cfg.dtype) + tm["b1"])
    temb = temb @ tm["w2"].astype(cfg.dtype) + tm["b2"]
    x = conv2d(xt.astype(cfg.dtype), params["in_conv"])
    skips = [x]
    for lvl, level in enumerate(params["down"]):
        for blk in level:
            if "downsample" in blk:
                x = conv2d(x, blk["downsample"], stride=2)
            else:
                x = _apply_resblock(blk["res"], x, temb, cfg)
                if "attn" in blk:
                    x = _apply_attnblock(blk["attn"], x, ctx, cfg)
            skips.append(x)
    x = _apply_resblock(params["mid"]["res1"], x, temb, cfg)
    x = _apply_attnblock(params["mid"]["attn"], x, ctx, cfg)
    x = _apply_resblock(params["mid"]["res2"], x, temb, cfg)
    for level in params["up"]:
        for blk in level:
            if "upsample" in blk:
                B, H, W, C = x.shape
                x = jax.image.resize(x, (B, 2 * H, 2 * W, C), "nearest")
                x = conv2d(x, blk["upsample"])
            else:
                x = jnp.concatenate([x, skips.pop()], axis=-1)
                x = _apply_resblock(blk["res"], x, temb, cfg)
                if "attn" in blk:
                    x = _apply_attnblock(blk["attn"], x, ctx, cfg)
    x = jax.nn.silu(group_norm(x, params["out_gn"], params["out_gb"],
                               eps=cfg.norm_eps))
    return conv2d(x, params["out_conv"])


def unet_loss(params: Params, batch: dict, rng: Array, cfg: UNetConfig) -> Array:
    return ddpm_loss(lambda p, xt, t, b: unet_apply(p, xt, t, b, cfg),
                     params, batch, rng)


def unet_block_graph(cfg: UNetConfig, batch: int,
                     hw: Hardware = TPU_V5E) -> BlockGraph:
    """Exports the UNet as a heterogeneous BlockGraph (paper Fig. 6: per-block
    cost varies ~3x across resolutions)."""
    blocks: list[Block] = []
    skip_meta: list[tuple[int, int]] = []   # (blk_index, bytes)
    res = cfg.img_size

    def res_cost(cin, cout, r):
        fl = 2 * batch * r * r * 9 * cin * cout + 2 * batch * r * r * 9 * cout * cout
        return fl, batch * r * r * cout * 2

    def attn_cost(c, r):
        n = r * r
        fl = 2 * batch * (8 * n * c * c + 4 * n * n * c + 8 * n * c * c
                          + cfg.ctx_len * n * c * 2)
        return fl

    c = cfg.base_ch
    fl, act = res_cost(cfg.in_ch, c, res)
    blocks.append(Block("in_conv", 0.0, 9 * cfg.in_ch * c * 2, act, act, fl))
    skip_meta.append((0, act))
    for lvl, m in enumerate(cfg.ch_mults):
        cout = cfg.base_ch * m
        for b in range(cfg.blocks_per_level):
            fl, act = res_cost(c, cout, res)
            pbytes = (9 * c * cout + 9 * cout * cout) * 2
            if lvl in cfg.attn_levels:
                fl += attn_cost(cout, res)
                pbytes += (16 * cout * cout + cfg.ctx_dim * 2 * cout) * 2
            blocks.append(Block(f"d{lvl}b{b}", 0.0, pbytes, act, act, fl))
            skip_meta.append((len(blocks) - 1, act))
            c = cout
        if lvl < len(cfg.ch_mults) - 1:
            fl = 2 * batch * (res // 2) ** 2 * 9 * c * c
            act = batch * (res // 2) ** 2 * c * 2
            blocks.append(Block(f"down{lvl}", 0.0, 9 * c * c * 2, act, act, fl))
            skip_meta.append((len(blocks) - 1, act))
            res //= 2
    fl, act = res_cost(c, c, res)
    blocks.append(Block("mid", 0.0, (18 * c * c + 16 * c * c) * 2, act, 0,
                        2 * fl + attn_cost(c, res)))
    for lvl in reversed(range(len(cfg.ch_mults))):
        cout = cfg.base_ch * cfg.ch_mults[lvl]
        for b in range(cfg.blocks_per_level + 1):
            src, sbytes = skip_meta.pop()
            cin = c + sbytes // (batch * res * res * 2)
            fl, act = res_cost(cin, cout, res)
            pbytes = (9 * cin * cout + 9 * cout * cout) * 2
            if lvl in cfg.attn_levels:
                fl += attn_cost(cout, res)
                pbytes += (16 * cout * cout + cfg.ctx_dim * 2 * cout) * 2
            blocks.append(Block(f"u{lvl}b{b}", 0.0, pbytes, act, 0, fl))
            c = cout
        if lvl > 0:
            res *= 2
            fl = 2 * batch * res * res * 9 * c * c
            act = batch * res * res * c * 2
            blocks.append(Block(f"up{lvl}", 0.0, 9 * c * c * 2, act, 0, fl))
    blocks.append(Block("out_conv", 0.0, 9 * c * cfg.in_ch * 2,
                        batch * cfg.img_size ** 2 * cfg.in_ch * 2, 0,
                        2 * batch * cfg.img_size ** 2 * 9 * c * cfg.in_ch))
    # Skip edges follow the UNet's LIFO stack discipline (nested by
    # construction): producers are the down-path blocks with skip_bytes > 0,
    # consumers are the up-path res blocks, popping in reverse order.
    producers = [i for i, b in enumerate(blocks) if b.skip_bytes > 0]
    consumers = [i for i, b in enumerate(blocks)
                 if b.name.startswith("u") and not b.name.startswith("up")]
    edges = []
    stack = list(producers)
    for cons in consumers:
        if stack:
            src = stack.pop()
            edges.append(SkipEdge(src, cons, blocks[src].skip_bytes))
    from repro.core.profiler import analytic_block_costs
    return BlockGraph(analytic_block_costs(blocks, hw),
                      tuple(sorted(edges, key=lambda e: e.src)))


def uvit_pipeline_graph(cfg: UViTConfig, batch: int = 1,
                        fwd_times=None, hw: Hardware = TPU_V5E) -> BlockGraph:
    """Runtime-aligned UViT graph for the auto-pipeline compile path.

    Unlike :func:`uvit_block_graph` (which models embed/out as blocks for
    the analytic comm studies), this graph has exactly one block per
    enc/dec transformer block — matching ``params["enc_blocks"]`` /
    ``params["dec_blocks"]`` rows — with the fully-paired skip edges
    (enc i -> dec mirror) the partitioner collocates.  ``fwd_times``
    (length 2*half) injects profiled per-block times.
    """
    d, n, ff = cfg.d_model, cfg.n_tokens, cfg.d_ff
    act = batch * n * d * 2
    attn_fl = 2 * batch * (4 * n * d * d + 2 * n * n * d)
    mlp_fl = 2 * batch * (2 * n * d * ff)
    per_param = (4 * d * d + 2 * d * ff) * 2
    blocks = []
    for i in range(cfg.half):
        blocks.append(Block(f"enc{i}", 0.0, per_param, act, act,
                            attn_fl + mlp_fl))
    for i in range(cfg.half):
        blocks.append(Block(f"dec{i}", 0.0, per_param + 2 * d * d * 2, act, 0,
                            attn_fl + mlp_fl + 2 * batch * n * 2 * d * d))
    return _runtime_graph(blocks,
                          _paired_skips(2 * cfg.half, cfg.half, act),
                          fwd_times, hw)


def _runtime_graph(blocks, skip_edges, fwd_times, hw) -> BlockGraph:
    """Shared tail of the ``*_pipeline_graph`` builders: analytic block
    costs, optional profiled fwd-time injection, skip-edge attachment."""
    from repro.core.profiler import analytic_block_costs
    blocks = list(analytic_block_costs(tuple(blocks), hw))
    if fwd_times is not None:
        if len(fwd_times) != len(blocks):
            raise ValueError("fwd_times must have one entry per block")
        blocks = [dataclasses.replace(b, fwd_time=float(t))
                  for b, t in zip(blocks, fwd_times)]
    return BlockGraph(tuple(blocks), tuple(skip_edges))


def _paired_skips(n_total: int, n_pairs: int, act: int
                  ) -> tuple[SkipEdge, ...]:
    """Fully-paired UNet edges: block i -> its mirror ``n_total-1-i``."""
    return tuple(SkipEdge(i, n_total - 1 - i, act) for i in range(n_pairs))


def hunyuan_pipeline_graph(cfg: HunyuanDiTConfig, batch: int = 1,
                           fwd_times=None,
                           hw: Hardware = TPU_V5E) -> BlockGraph:
    """Runtime-aligned Hunyuan-DiT graph for the auto-pipeline compile path.

    Like :func:`uvit_pipeline_graph`: exactly one block per
    ``enc_blocks``/``dec_blocks`` row (embed/out live in edge params), with
    the fully-paired skip edges enc i -> dec mirror.  ``fwd_times``
    (length 2*half) injects profiled per-block times.
    """
    d, n, ff, lt = cfg.d_model, cfg.n_tokens, cfg.d_ff, cfg.ctx_len
    act = batch * n * d * 2
    blk_fl = 2 * batch * (4 * n * d * d + 2 * n * n * d + 2 * n * d * ff
                          + 2 * n * d * d + cfg.ctx_dim * 2 * d * lt
                          + 2 * n * lt * d)
    per_param = (4 * d * d + 2 * d * ff + 2 * d * d + cfg.ctx_dim * 2 * d
                 + 6 * d * d) * 2
    blocks = []
    for i in range(cfg.half):
        blocks.append(Block(f"enc{i}", 0.0, per_param, act, act, blk_fl))
    for i in range(cfg.half):
        blocks.append(Block(f"dec{i}", 0.0, per_param + 2 * d * d * 2, act,
                            0, blk_fl + 2 * batch * n * 2 * d * d))
    return _runtime_graph(blocks,
                          _paired_skips(2 * cfg.half, cfg.half, act),
                          fwd_times, hw)


# --------------------------------------------------------------------------
# SkipViT: homogeneous ViT stack with an arbitrary (possibly sparse) skip
# topology — the asymmetric-fold workload (mid-block bottlenecks, sparse
# skips, odd block counts) the generalized layout/lowering stack runs
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SkipViTConfig:
    """UNet-shaped ViT over ONE homogeneous block stack.

    ``n_enc`` skip-emitting blocks, ``n_mid`` bottleneck blocks (no skip
    endpoints), ``n_dec`` blocks that may consume a skip.  ``skip_pairs``
    (block-index ``(src, dst)`` tuples) defaults to full pairing
    ``(i, n-1-i)``; pass a subset for sparse-skip variants.  Every block
    carries a ``skip_in`` projection and consumes *additively*
    (``x + skip @ skip_in``), so blocks without an incoming skip see zeros
    and reduce to a plain ViT block — one scan body covers emitters,
    bottlenecks and consumers, which is what lets the fold's turnaround cut
    land anywhere the partitioner puts it.
    """

    name: str
    img_size: int = 8
    in_ch: int = 4
    patch: int = 2
    d_model: int = 32
    n_heads: int = 4
    d_ff: int = 64
    n_classes: int = 10
    n_enc: int = 3
    n_mid: int = 2
    n_dec: int = 3
    skip_pairs: tuple[tuple[int, int], ...] | None = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def n_blocks(self) -> int:
        return self.n_enc + self.n_mid + self.n_dec

    @property
    def n_tokens(self) -> int:
        return (self.img_size // self.patch) ** 2 + 2  # + time/class tokens

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_heads,
                          self.d_model // self.n_heads, rope_theta=0.0,
                          causal=False)

    def skip_edges(self) -> tuple[tuple[int, int], ...]:
        if self.skip_pairs is not None:
            return self.skip_pairs
        k = min(self.n_enc, self.n_dec)
        return tuple((i, self.n_blocks - 1 - i) for i in range(k))


def init_skipvit(key, cfg: SkipViTConfig) -> Params:
    ks = jax.random.split(key, 8)
    d, pd = cfg.d_model, cfg.param_dtype
    pp = cfg.patch ** 2 * cfg.in_ch
    bk = jax.random.split(ks[0], cfg.n_blocks)

    def mk(k):
        k1, k2 = jax.random.split(k)
        p = _init_vit_block(k1, cfg, cfg.d_ff, False)
        p["skip_in"] = L.dense_init(k2, d, d, pd)
        return p

    return {
        "patch_embed": L.dense_init(ks[2], pp, d, pd),
        "pos_embed": (jax.random.normal(ks[3], (cfg.n_tokens, d)) * 0.02
                      ).astype(pd),
        "time_mlp": L.init_gelu_mlp(ks[4], d, 4 * d, pd),
        "class_embed": L.dense_init(ks[5], cfg.n_classes, d, pd),
        "blocks": jax.vmap(mk)(bk),
        "out_norm": jnp.ones((d,), pd),
        "out_proj": L.dense_init(ks[6], d, pp, pd),
    }


def skipvit_apply(params: Params, xt: Array, t: Array, batch: dict,
                  cfg: SkipViTConfig) -> Array:
    """Single-device reference; the pipeline executors must match it for
    every legal partition, mirror-symmetric or not."""
    x = uvit_embed(params, xt, t, batch, cfg)
    consumes = {dst: src for src, dst in cfg.skip_edges()}
    stash: dict[int, Array] = {}
    for b in range(cfg.n_blocks):
        bp = jax.tree.map(lambda a: a[b], params["blocks"])
        if b in consumes:
            x = x + stash[consumes[b]] @ bp["skip_in"].astype(x.dtype)
        x = _apply_vit_block(bp, x, cfg)
        stash[b] = x
    return uvit_output(params, x, cfg)


def skipvit_loss(params: Params, batch: dict, rng: Array,
                 cfg: SkipViTConfig) -> Array:
    return ddpm_loss(lambda p, xt, t, b: skipvit_apply(p, xt, t, b, cfg),
                     params, batch, rng)


def skipvit_pipeline_graph(cfg: SkipViTConfig, batch: int = 1,
                           fwd_times=None,
                           hw: Hardware = TPU_V5E) -> BlockGraph:
    """Runtime-aligned SkipViT graph: one block per ``params['blocks']``
    row with the config's (possibly sparse / mid-block) skip edges."""
    d, n, ff = cfg.d_model, cfg.n_tokens, cfg.d_ff
    act = batch * n * d * 2
    blk_fl = 2 * batch * (4 * n * d * d + 2 * n * n * d + 2 * n * d * ff)
    per_param = (4 * d * d + 2 * d * ff + d * d) * 2
    edges = cfg.skip_edges()
    srcs = {s for s, _ in edges}
    blocks = [Block(f"blk{i}", 0.0, per_param, act,
                    act if i in srcs else 0, blk_fl)
              for i in range(cfg.n_blocks)]
    return _runtime_graph(blocks,
                          (SkipEdge(s, t, act) for s, t in edges),
                          fwd_times, hw)
