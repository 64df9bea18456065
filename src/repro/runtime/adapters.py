"""Model -> pipeline adapters.

These functions reshape a model's stacked parameters into per-device stage
stacks and provide the embed/stage/loss callbacks for the executors in
``runtime.pipeline``.  The stage grouping follows the PULSE partitioner's
output; for homogeneous transformer stacks the partition is the even split,
which the bidirectional DP returns for uniform costs (validated in tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import lm as lm_mod
from repro.models import diffusion as diff_mod
from repro.models.lm import LMConfig
from repro.runtime.compat import tree_to_host
from repro.runtime.pipeline import (PipelineConfig, make_linear_pipeline,
                                    make_wave_pipeline,
                                    make_skip_carry_pipeline)

Pytree = Any


def _regroup(stack: Pytree, D: int, reverse: bool = False) -> Pytree:
    """[L, ...] stacked params -> [D, L/D, ...]; optionally flip device order
    (decoder stacks execute in reverse device order under the fold)."""

    def f(x):
        L = x.shape[0]
        assert L % D == 0, f"layer count {L} not divisible by {D} stages"
        y = x.reshape(D, L // D, *x.shape[1:])
        return y[::-1] if reverse else y

    return jax.tree.map(f, stack)


def _ungroup(stack: Pytree, reverse: bool = False) -> Pytree:
    def f(x):
        y = x[::-1] if reverse else x
        return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])
    return jax.tree.map(f, stack)


# ===========================================================================
# LM family
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class LMPipelineAdapter:
    """Linear (1F1B) or folded-wave pipeline for the unified LM family."""

    cfg: LMConfig
    pcfg: PipelineConfig
    wave: bool = False       # True: fold layers symmetrically (S = 2D)

    def init_pipeline_params(self, key) -> tuple:
        return self.split_params(lm_mod.init_lm(key, self.cfg))

    def split_params(self, params: Pytree) -> tuple:
        """-> (stacks..., edge_params) for the pipeline fn."""
        D = self.pcfg.num_devices
        layers = params["layers"]
        edge = {k: v for k, v in params.items() if k != "layers"}
        if not self.wave:
            return (_regroup(layers, D),), edge
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], layers)
        rest = jax.tree.map(lambda x: x[x.shape[0] // 2:], layers)
        return (_regroup(half, D), _regroup(rest, D, reverse=True)), edge

    def merge_params(self, stacks: tuple, edge: Pytree) -> Pytree:
        stacks = tree_to_host(stacks)   # sharded stacks: see tree_to_host
        if not self.wave:
            layers = _ungroup(stacks[0])
        else:
            enc = _ungroup(stacks[0])
            dec = _ungroup(stacks[1], reverse=True)
            layers = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), enc, dec)
        return {**edge, "layers": layers}

    # ---- callbacks ----
    def embed_fn(self, edge_p, mb, aux=None):
        return lm_mod.embed_tokens(edge_p, mb["tokens"], self.cfg)

    def _run_layers(self, stage_p, x):
        cfg = self.cfg
        positions = jnp.arange(x.shape[1])[None, :]

        def body(x, lp):
            x, _, _ = lm_mod.apply_layer(lp, x, cfg, dense_ffn=False,
                                         positions=positions)
            return x, None

        x, _ = jax.lax.scan(body, x, stage_p)
        return x

    def stage_fn(self, stage_p, x):
        return self._run_layers(stage_p, x)

    def enc_stage_fn(self, stage_p, x, aux):
        return self._run_layers(stage_p, x), {}

    def dec_stage_fn(self, stage_p, x, skips, aux):
        return self._run_layers(stage_p, x)

    def loss_fn(self, edge_p, x, mb, aux=None):
        logits = lm_mod.unembed(edge_p, x[:, :-1], self.cfg)
        return lm_mod.softmax_xent(logits, mb["tokens"][:, 1:])

    # ---- builders ----
    def build(self) -> Callable:
        if self.wave:
            wave = make_wave_pipeline(
                self.pcfg,
                embed_fn=lambda e, mb, aux: self.embed_fn(e, mb),
                enc_stage_fn=self.enc_stage_fn,
                dec_stage_fn=self.dec_stage_fn,
                loss_fn=lambda e, x, mb, aux: self.loss_fn(e, x, mb))
            # LM graphs have no skip tensors: aux rides along empty.
            return lambda enc, dec, edge, mbs: wave(enc, dec, edge, mbs, {})
        fn = make_linear_pipeline(
            self.pcfg, embed_fn=self.embed_fn, stage_fn=self.stage_fn,
            loss_fn=self.loss_fn)
        return fn


# ===========================================================================
# UViT / Hunyuan-DiT (wave with real skip tensors)
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class DiffusionPipelineAdapter:
    """Folded wave pipeline for UViT / Hunyuan-DiT.

    Microbatch inputs (all stacked [M, b, ...]):
      mb:  {"xt", "noise", plus model conditioning ("labels" | nothing)}
      aux: {"t"} for UViT (time token built in embed); Hunyuan additionally
           carries {"temb", "ctx"} to every stage.
    """

    cfg: Any                     # UViTConfig | HunyuanDiTConfig
    pcfg: PipelineConfig | None  # None: callbacks-only (diffusion_model_fns
    kind: str = "uvit"           # borrows embed/loss/_blk_kwargs; build/
                                 # split_params need a real PipelineConfig).
                                 # kind: "uvit" | "hunyuan"

    def init_pipeline_params(self, key) -> tuple:
        init = (diff_mod.init_uvit if self.kind == "uvit"
                else diff_mod.init_hunyuan)
        return self.split_params(init(key, self.cfg))

    def split_params(self, params: Pytree) -> tuple:
        D = self.pcfg.num_devices
        enc = _regroup(params["enc_blocks"], D)
        dec = _regroup(params["dec_blocks"], D, reverse=True)
        edge = {k: v for k, v in params.items()
                if k not in ("enc_blocks", "dec_blocks")}
        return (enc, dec), edge

    def merge_params(self, stacks: tuple, edge: Pytree) -> Pytree:
        stacks = tree_to_host(stacks)   # sharded stacks: see tree_to_host
        return {**edge,
                "enc_blocks": _ungroup(stacks[0]),
                "dec_blocks": _ungroup(stacks[1], reverse=True)}

    def embed_fn(self, edge_p, mb, aux):
        if self.kind == "uvit":
            return diff_mod.uvit_embed(edge_p, mb["xt"], aux["t"], mb, self.cfg)
        tok = diff_mod._patchify(mb["xt"].astype(self.cfg.dtype),
                                 self.cfg.patch) @ edge_p["patch_embed"].astype(self.cfg.dtype)
        return tok + edge_p["pos_embed"].astype(self.cfg.dtype)[None]

    def _blk_kwargs(self, aux):
        if self.kind == "uvit":
            return {}
        return {"ctx": aux["ctx"], "temb": aux["temb"]}

    def enc_stage_fn(self, stage_p, x, aux):
        kw = self._blk_kwargs(aux)

        def body(x, bp):
            x = diff_mod._apply_vit_block(bp, x, self.cfg, **kw)
            return x, x

        x, skips = jax.lax.scan(body, x, stage_p)
        return x, skips

    def dec_stage_fn(self, stage_p, x, skips, aux):
        kw = self._blk_kwargs(aux)

        def body(x, inp):
            bp, skip = inp
            return diff_mod._apply_vit_block(bp, x, self.cfg, skip=skip, **kw), None

        x, _ = jax.lax.scan(body, x, (stage_p, skips[::-1]))
        return x

    def loss_fn(self, edge_p, x, mb, aux):
        if self.kind == "uvit":
            pred = diff_mod.uvit_output(edge_p, x, self.cfg)
        else:
            from repro.models.layers import rms_norm
            h = rms_norm(x, edge_p["out_norm"], self.cfg.norm_eps)
            pix = h @ edge_p["out_proj"].astype(h.dtype)
            pred = diff_mod._unpatchify(pix, self.cfg.patch,
                                        self.cfg.img_size, self.cfg.in_ch)
        return jnp.mean(jnp.square(pred.astype(jnp.float32)
                                   - mb["noise"].astype(jnp.float32)))

    def build(self) -> Callable:
        return make_wave_pipeline(
            self.pcfg, embed_fn=self.embed_fn,
            enc_stage_fn=self.enc_stage_fn, dec_stage_fn=self.dec_stage_fn,
            loss_fn=self.loss_fn)

    def build_skip_carry_baseline(self) -> Callable:
        """Paper-baseline executor: sequential partition + skip payload."""
        D = self.pcfg.num_devices
        half = self.cfg.half
        assert half % (D // 2) == 0
        k = half // (D // 2)
        return make_skip_carry_pipeline(
            self.pcfg, n_skip_slots=half,
            embed_fn=self.embed_fn,
            enc_stage_fn=self.enc_stage_fn, dec_stage_fn=self.dec_stage_fn,
            loss_fn=self.loss_fn, skips_per_stage=k)

    def split_params_skip_carry(self, params: Pytree) -> tuple:
        """Sequential layout for the baseline: devices 0..D/2-1 hold enc
        stages, D/2..D-1 hold dec stages; stacks are padded to D rows."""
        D = self.pcfg.num_devices
        enc = _regroup(params["enc_blocks"], D // 2)
        dec = _regroup(params["dec_blocks"], D // 2)
        pad = lambda t: jax.tree.map(
            lambda x: jnp.concatenate([x, jnp.zeros_like(x)], 0), t)
        enc_padded = pad(enc)                       # rows D/2.. unused
        dec_padded = jax.tree.map(
            lambda x: jnp.concatenate([jnp.zeros_like(x), x], 0), dec)
        edge = {k: v for k, v in params.items()
                if k not in ("enc_blocks", "dec_blocks")}
        return (enc_padded, dec_padded), edge


def make_diffusion_microbatches(batch: dict, rng, M: int, cfg,
                                kind: str = "uvit",
                                params: Pytree | None = None
                                ) -> tuple[dict, dict]:
    """Sample DDPM (t, noise) per microbatch and reshape [B,...] ->
    [M, B/M, ...] stacked microbatches + aux conditioning.

    For Hunyuan the per-stage adaLN conditioning ``temb`` is computed once
    here from the (replicated) ``time_mlp`` params and broadcast down the
    pipeline as aux; its gradient psums across stages via the shard_map
    transpose."""
    B = batch["latents"].shape[0]
    b = B // M
    rt, rn = jax.random.split(rng)
    t = jax.random.uniform(rt, (B,))
    ab = diff_mod.cosine_alpha_bar(t)[:, None, None, None]
    noise = jax.random.normal(rn, batch["latents"].shape,
                              batch["latents"].dtype)
    xt = jnp.sqrt(ab) * batch["latents"] + jnp.sqrt(1 - ab) * noise
    split = lambda x: x.reshape(M, b, *x.shape[1:])
    mb = {"xt": split(xt), "noise": split(noise)}
    aux = {"t": split(t)}
    if kind == "uvit":
        mb["labels"] = split(batch["labels"])
    else:
        from repro.models.layers import apply_gelu_mlp
        temb = apply_gelu_mlp(
            params["time_mlp"],
            diff_mod.timestep_embedding(t, cfg.d_model).astype(cfg.dtype))
        aux["ctx"] = split(batch["text_embeds"].astype(cfg.dtype))
        aux["temb"] = split(temb)
    return mb, aux


# ===========================================================================
# Block-level model fns for the auto-compile path (runtime.compile)
# ===========================================================================

def lm_model_fns(cfg: LMConfig):
    """Unified-LM family as block-level compile-path callables.

    Pairs with :func:`repro.models.lm.lm_pipeline_graph` (skip-free; the
    compiler emits a linear S=D pipeline, or a folded S=2D wave under
    ``force_wave``).
    """
    from repro.runtime.compile import PipelineModelFns

    def embed_fn(edge_p, mb, aux):
        return lm_mod.embed_tokens(edge_p, mb["tokens"], cfg)

    def block_fn(lp, x, aux):
        positions = jnp.arange(x.shape[1])[None, :]
        x, _, _ = lm_mod.apply_layer(lp, x, cfg, dense_ffn=False,
                                     positions=positions)
        return x

    def loss_fn(edge_p, x, mb, aux):
        logits = lm_mod.unembed(edge_p, x[:, :-1], cfg)
        return lm_mod.softmax_xent(logits, mb["tokens"][:, 1:])

    def split_blocks(params):
        edge = {k: v for k, v in params.items() if k != "layers"}
        return (params["layers"],), edge

    def merge_blocks(stacks, edge):
        return {**edge, "layers": stacks[0]}

    return PipelineModelFns(
        init_fn=lambda key: lm_mod.init_lm(key, cfg),
        embed_fn=embed_fn, loss_fn=loss_fn, block_fn=block_fn,
        split_blocks=split_blocks, merge_blocks=merge_blocks)


def diffusion_model_fns(cfg: Any, kind: str = "uvit"):
    """UViT / Hunyuan-DiT as block-level compile-path callables.

    Pairs with :func:`repro.models.diffusion.uvit_pipeline_graph`: every
    encoder block emits its output as a skip; the mirror decoder block
    consumes it (fully-paired graph -> mirror-symmetric folded partitions).
    """
    from repro.runtime.compile import PipelineModelFns

    ad = DiffusionPipelineAdapter(cfg, None, kind)   # callbacks only

    def enc_block_fn(bp, x, aux):
        y = diff_mod._apply_vit_block(bp, x, cfg, **ad._blk_kwargs(aux))
        return y, y

    def dec_block_fn(bp, x, skip, aux):
        return diff_mod._apply_vit_block(bp, x, cfg, skip=skip,
                                         **ad._blk_kwargs(aux))

    def split_blocks(params):
        edge = {k: v for k, v in params.items()
                if k not in ("enc_blocks", "dec_blocks")}
        return (params["enc_blocks"], params["dec_blocks"]), edge

    def merge_blocks(stacks, edge):
        return {**edge, "enc_blocks": stacks[0], "dec_blocks": stacks[1]}

    init = diff_mod.init_uvit if kind == "uvit" else diff_mod.init_hunyuan
    return PipelineModelFns(
        init_fn=lambda key: init(key, cfg),
        embed_fn=ad.embed_fn, loss_fn=ad.loss_fn,
        enc_block_fn=enc_block_fn, dec_block_fn=dec_block_fn,
        split_blocks=split_blocks, merge_blocks=merge_blocks,
        num_param_stacks=2)


def skipvit_model_fns(cfg):
    """SkipViT (homogeneous stack, arbitrary skip topology) as compile-path
    callables.

    Pairs with :func:`repro.models.diffusion.skipvit_pipeline_graph`.  One
    parameter stack covers emitters, bottleneck blocks and consumers: every
    encoder-half block emits its output to the stash, every decoder-half
    block consumes additively (``x + skip @ skip_in``) — rows the layout's
    skip pairing marks skip-less receive zeros and reduce to plain blocks.
    This is the model family whose partitions exercise asymmetric folds
    (the fold's turnaround cut may land anywhere, including inside the
    bottleneck run).
    """
    from repro.runtime.compile import PipelineModelFns

    def embed_fn(edge_p, mb, aux):
        return diff_mod.uvit_embed(edge_p, mb["xt"], aux["t"], mb, cfg)

    def enc_block_fn(bp, x, aux):
        y = diff_mod._apply_vit_block(bp, x, cfg)
        return y, y

    def dec_block_fn(bp, x, skip, aux):
        x = x + skip @ bp["skip_in"].astype(x.dtype)
        return diff_mod._apply_vit_block(bp, x, cfg)

    def loss_fn(edge_p, x, mb, aux):
        pred = diff_mod.uvit_output(edge_p, x, cfg)
        return jnp.mean(jnp.square(pred.astype(jnp.float32)
                                   - mb["noise"].astype(jnp.float32)))

    def split_blocks(params):
        edge = {k: v for k, v in params.items() if k != "blocks"}
        return (params["blocks"],), edge

    def merge_blocks(stacks, edge):
        return {**edge, "blocks": stacks[0]}

    return PipelineModelFns(
        init_fn=lambda key: diff_mod.init_skipvit(key, cfg),
        embed_fn=embed_fn, loss_fn=loss_fn,
        enc_block_fn=enc_block_fn, dec_block_fn=dec_block_fn,
        split_blocks=split_blocks, merge_blocks=merge_blocks,
        num_param_stacks=1)
