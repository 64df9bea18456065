"""Hunyuan-DiT DiT-g/2 (Tencent, arXiv:2405.08748; ``HUNYUAN_DIT_CONFIG
['DiT-g/2']`` of github.com/Tencent/HunyuanDiT, ``hydit/modules``) at its
published widths: 40 blocks of d 1408, 16 heads of 88, MLP 6144, patch 2,
4 latent channels in and 8 out, text cross-attention over 77 CLIP + 256
mT5 tokens of width 1024; 1,499,952,032 parameters.  Trained at 1024 px:
128x128x4 latents, 4,096 tokens.

The PULSE path is ``python -m repro.launch.train --arch hunyuan-dit
--pipeline``: ``pipeline_graph`` (the 21 + 19 blocks with their 19 long
skips and the fold pinned to the stack boundary) and
``pipeline_model_fns`` through ``auto_pipeline``.

What the program assumes (``bench/configs/hydit_g2.json`` lists the
same):

- the objective is the program's ``ddpm_loss`` (cosine schedule, eps-MSE)
  on the first 4 of the 8 output channels; the published schedule, its
  prediction target and its variance term are not used, so the variance
  channels get no gradient;
- the v1.0 image-size and style conditions are left out (``size_cond``
  and ``use_style_cond`` off), so the extra embedder reads the pooled mT5
  states alone;
- RoPE pairs adjacent head dims, the first half of the pairs turning with
  the token's column and the second with its row, at positions
  ``i * 32 / 64`` (a 512-px base grid); the mT5 MLP's hidden width is
  8192 (4 x 2048): none of these changes a shape or a cost;
- weights normal over sqrt(fan-in) and biases zero (not DiT's zero-init
  of the modulation and output layers, so every leaf has a gradient at
  the first step); the q/k/v projection is three matrices, the same map
  as the published fused one.
"""
import jax
import jax.numpy as jnp
from repro.configs.base import ArchBundle, ShapeSpec
from repro.models import diffusion as dm
from repro.models.diffusion import HunyuanDiTConfig
from repro.train.steps import ParallelPlan

#: at 4,096 tokens a P=4 stage's backward fits a v5e only with query-
#: blocked attention and per-block remat (PERF.md, section 4)
CFG = HunyuanDiTConfig(name="hunyuan-dit", q_block=1024,
                       dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)

PLANS = {
    "train_4k": ParallelPlan(tp_axis=None, fsdp_axes=("model", "data"),
                             batch_axes=("pod", "data")),
}
SUPPORT = {"train_4k": "ok",
           "prefill_32k": "n/a: diffusion training arch",
           "decode_32k": "n/a: diffusion training arch",
           "long_500k": "n/a: diffusion training arch"}


def batch_struct(shape: ShapeSpec, plan=None):
    B = shape.global_batch
    sds = jax.ShapeDtypeStruct
    return {
        "latents": sds((B, CFG.img_size, CFG.img_size, CFG.in_ch),
                       jnp.bfloat16),
        "clip": sds((B, CFG.clip_len, CFG.text_dim), jnp.bfloat16),
        "t5": sds((B, CFG.t5_len, CFG.t5_dim), jnp.bfloat16),
        "clip_len": sds((B,), jnp.int32),
        "t5_len": sds((B,), jnp.int32),
    }


def loss_fn(params, batch, rng):
    return dm.hunyuan_loss(params, batch, rng, CFG)


def pipeline_graph(batch: int = 1, fwd_times=None, cfg=CFG):
    """Runtime-aligned block graph for the auto-pipeline compile path."""
    return dm.hunyuan_pipeline_graph(cfg, batch, fwd_times=fwd_times)


def pipeline_model_fns(cfg=CFG):
    """Block-level compile-path callables for this config's model."""
    from repro.runtime.adapters import hunyuan_model_fns
    return hunyuan_model_fns(cfg)


def auto_plan(N: int, **kwargs):
    """Plan + lower this config through the full compile path
    (graph -> skip-aware partition -> validated schedule -> executor).

    ``N`` is the total device budget; keyword arguments forward to
    :func:`repro.runtime.compile.auto_pipeline` (e.g. ``pipeline_devices``
    to pin the pipeline degree, ``microbatches``, ``use_ilp``).
    """
    from repro.runtime.compile import auto_pipeline
    return auto_pipeline(pipeline_graph(), pipeline_model_fns(), N, **kwargs)


def get_bundle():
    return ArchBundle(
        name="hunyuan-dit", family="diffusion", cfg=CFG,
        init_fn=lambda key: dm.init_hunyuan(key, CFG),
        loss_fn=loss_fn, batch_struct=batch_struct, plans=PLANS,
        shape_support=SUPPORT, param_count=CFG.param_count(),
        active_param_count=CFG.param_count(),
        notes="paper model; pipelined through auto_pipeline (21+19 "
              "stacks, fold pinned to their boundary)")
