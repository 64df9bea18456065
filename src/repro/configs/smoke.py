"""Reduced smoke variants of every assigned architecture family.

Same code paths as the full configs (family, attention flavour, MoE
dispatch, skip structure) at CPU-runnable sizes.  Each entry returns
``(loss_fn, init_fn, make_batch, cfg)`` where ``loss_fn(params, batch, rng)``
is a scalar; tests run one forward/train step and assert finiteness and
output shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import lm as lm_mod
from repro.models import whisper as wh
from repro.models import xlstm as xm
from repro.models import mamba as zm
from repro.models import diffusion as dm
from repro.models.lm import LMConfig
from repro.models.layers import AttnConfig, MLAConfig, MoEConfig
from repro.models.whisper import WhisperConfig
from repro.models.xlstm import XLSTMConfig
from repro.models.mamba import Zamba2Config, Mamba2Config
from repro.models.diffusion import UViTConfig, HunyuanDiTConfig, UNetConfig


def _lm(cfg: LMConfig, seq: int = 32, batch: int = 2, prefix=None):
    def make_batch(key):
        b = {"tokens": jax.random.randint(key, (batch, seq), 0, cfg.vocab)}
        if prefix:
            b["prefix_embeds"] = jax.random.normal(
                key, (batch, prefix, cfg.d_model), cfg.dtype)
        return b
    return (lambda p, b, r: lm_mod.lm_loss(p, b, cfg),
            lambda k: lm_mod.init_lm(k, cfg), make_batch, cfg)


def smoke_smollm():
    cfg = LMConfig("smollm-smoke", vocab=256, d_model=64, n_layers=4,
                   attn=AttnConfig(64, 4, 2, 16), d_ff=128,
                   tied_embeddings=True)
    return _lm(cfg)


def smoke_danube():
    cfg = LMConfig("danube-smoke", vocab=256, d_model=64, n_layers=4,
                   attn=AttnConfig(64, 4, 2, 16, window=8), d_ff=128)
    return _lm(cfg)


def smoke_internlm2():
    cfg = LMConfig("internlm2-smoke", vocab=256, d_model=64, n_layers=4,
                   attn=AttnConfig(64, 4, 2, 16), d_ff=128)
    return _lm(cfg)


def smoke_granite():
    cfg = LMConfig("granite-smoke", vocab=256, d_model=64, n_layers=6,
                   attn=AttnConfig(64, 4, 1, 16), d_ff=192)   # MQA
    return _lm(cfg)


def smoke_internvl2():
    cfg = LMConfig("internvl2-smoke", vocab=256, d_model=64, n_layers=3,
                   attn=AttnConfig(64, 4, 2, 16), d_ff=128, vision_prefix=8)
    return _lm(cfg, prefix=8)


def smoke_qwen3_moe():
    cfg = LMConfig("qwen3-smoke", vocab=256, d_model=64, n_layers=3,
                   attn=AttnConfig(64, 4, 2, 16, qk_norm=True),
                   moe=MoEConfig(64, 32, n_experts=8, top_k=2,
                                 capacity_factor=2.0),
                   moe_dispatch="scatter")
    return _lm(cfg)


def smoke_deepseek():
    cfg = LMConfig("deepseek-smoke", vocab=256, d_model=64, n_layers=4,
                   mla=MLAConfig(64, 4, q_lora_rank=32, kv_lora_rank=16,
                                 qk_nope_dim=16, qk_rope_dim=8,
                                 v_head_dim=16),
                   d_ff=128,
                   moe=MoEConfig(64, 32, n_experts=4, top_k=2, n_shared=1,
                                 capacity_factor=2.0),
                   moe_dispatch="scatter", n_dense_layers=1, mtp=True)
    return _lm(cfg)


def smoke_whisper():
    cfg = WhisperConfig("whisper-smoke", vocab=256, d_model=32,
                        n_enc_layers=2, n_dec_layers=2, n_heads=4, d_ff=64)

    def make_batch(key):
        return {"frames": jax.random.normal(key, (2, 12, 32)),
                "tokens": jax.random.randint(key, (2, 10), 0, 256)}
    return (lambda p, b, r: wh.whisper_loss(p, b, cfg),
            lambda k: wh.init_whisper(k, cfg), make_batch, cfg)


def smoke_xlstm():
    cfg = XLSTMConfig("xlstm-smoke", vocab=256, d_model=32, n_layers=4,
                      n_heads=2, slstm_every=3)

    def make_batch(key):
        return {"tokens": jax.random.randint(key, (2, 16), 0, 256)}
    return (lambda p, b, r: xm.xlstm_loss(p, b, cfg),
            lambda k: xm.init_xlstm(k, cfg), make_batch, cfg)


def smoke_zamba2():
    cfg = Zamba2Config("zamba2-smoke", vocab=256, d_model=32, n_layers=6,
                       mamba=Mamba2Config(d_model=32, d_state=8, head_dim=8,
                                          chunk=4),
                       shared_attn=AttnConfig(32, 4, 4, 8), shared_d_ff=64,
                       shared_every=3, n_shared_blocks=2)

    def make_batch(key):
        return {"tokens": jax.random.randint(key, (2, 16), 0, 256)}
    return (lambda p, b, r: zm.zamba2_loss(p, b, cfg),
            lambda k: zm.init_zamba2(k, cfg), make_batch, cfg)


def smoke_uvit():
    cfg = UViTConfig("uvit-smoke", img_size=8, in_ch=4, patch=2, d_model=32,
                     n_layers=4, n_heads=4, d_ff=64, n_classes=10)

    def make_batch(key):
        return {"latents": jax.random.normal(key, (2, 8, 8, 4)),
                "labels": jax.random.randint(key, (2,), 0, 10)}
    return (lambda p, b, r: dm.uvit_loss(p, b, r, cfg),
            lambda k: dm.init_uvit(k, cfg), make_batch, cfg)


def hunyuan_tiny(name: str = "hunyuan-smoke", n_layers: int = 4,
                 **kw) -> HunyuanDiTConfig:
    """Hunyuan-DiT's block at CPU-test widths: 8x8 latents (16 tokens),
    d 32 in 4 heads of 8, text of 5 CLIP + 7 mT5 tokens."""
    return HunyuanDiTConfig(name, img_size=8, in_ch=4, out_ch=8, patch=2,
                            d_model=32, n_layers=n_layers, n_heads=4,
                            d_ff=64, text_dim=16, clip_len=5, t5_dim=24,
                            t5_len=7, t5_hidden=32, pool_heads=2,
                            pool_dim=16, freq_dim=16, rope_base=2, **kw)


def hunyuan_tiny_batch(key, cfg: HunyuanDiTConfig, B: int = 2) -> dict:
    """Latents, CLIP and mT5 states and valid counts for ``hunyuan_tiny``
    (the counts leave padded slots in both parts)."""
    k = jax.random.split(key, 3)
    n5 = jnp.arange(B, dtype=jnp.int32) % cfg.t5_len + max(1, cfg.t5_len // 2)
    return {"latents": jax.random.normal(k[0], (B, cfg.img_size,
                                                cfg.img_size, cfg.in_ch)),
            "clip": jax.random.normal(k[1], (B, cfg.clip_len, cfg.text_dim)),
            "t5": jax.random.normal(k[2], (B, cfg.t5_len, cfg.t5_dim)),
            "clip_len": jnp.minimum(n5 - 1, cfg.clip_len),
            "t5_len": jnp.minimum(n5, cfg.t5_len)}


def smoke_hunyuan():
    cfg = hunyuan_tiny()
    return (lambda p, b, r: dm.hunyuan_loss(p, b, r, cfg),
            lambda k: dm.init_hunyuan(k, cfg),
            lambda key: hunyuan_tiny_batch(key, cfg), cfg)


def smoke_sdv2():
    cfg = UNetConfig("sdv2-smoke", img_size=16, in_ch=4, base_ch=16,
                     ch_mults=(1, 2), blocks_per_level=2, attn_levels=(1,),
                     ctx_dim=16, n_heads=4)

    def make_batch(key):
        return {"latents": jax.random.normal(key, (2, 16, 16, 4)),
                "text_embeds": jax.random.normal(key, (2, 7, 16))}
    return (lambda p, b, r: dm.unet_loss(p, b, r, cfg),
            lambda k: dm.init_unet(k, cfg), make_batch, cfg)


SMOKE_FACTORIES = {
    "smollm-360m": smoke_smollm,
    "h2o-danube-1.8b": smoke_danube,
    "internlm2-20b": smoke_internlm2,
    "granite-34b": smoke_granite,
    "whisper-base": smoke_whisper,
    "xlstm-125m": smoke_xlstm,
    "internvl2-2b": smoke_internvl2,
    "qwen3-moe-30b-a3b": smoke_qwen3_moe,
    "deepseek-v3-671b": smoke_deepseek,
    "zamba2-2.7b": smoke_zamba2,
    "uvit-h": smoke_uvit,
    "sdv2-unet": smoke_sdv2,
    "hunyuan-dit": smoke_hunyuan,
}
