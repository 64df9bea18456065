"""The Hunyuan-DiT family: how a cell of it is built from the program's
public pieces, what it is fed, how much work a step is, and its reference.

The program under test is PULSE's training path as ``launch/train.py``
builds it for ``--arch hunyuan-dit``: ``hunyuan_pipeline_graph`` (the
fold's turnaround pinned to the boundary of the two block stacks) and
``hunyuan_model_fns`` planned by ``auto_pipeline`` with the cell's pinned
plan, and the jitted ``pipeline_step`` (the conditioning once a step
outside the executor, pipelined loss and grads, the non-finite guard,
AdamW, donated state).  Nothing of the step, the executor or the model is
copied here.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.families.uvit import train_step  # noqa: F401  (the same step)
from bench.reference import hydit as reference


def model_config(cfg: dict, cell: dict):
    """The program's ``HunyuanDiTConfig`` for a configuration file and a
    cell's latent resolution (the RoPE grid follows the resolution)."""
    import jax.numpy as jnp

    from repro.models.diffusion import HunyuanDiTConfig

    if cfg["head_dim"] * cfg["n_heads"] != cfg["d_model"]:
        raise ValueError("the program's Hunyuan-DiT ties head_dim to "
                         "d_model / n_heads")
    keys = ("in_ch", "out_ch", "patch", "d_model", "n_layers", "n_heads",
            "d_ff", "text_dim", "clip_len", "t5_dim", "t5_len", "t5_hidden",
            "pool_heads", "pool_dim", "freq_dim", "rope_theta", "rope_base",
            "norm_eps")
    return HunyuanDiTConfig(
        name=cfg["name"], img_size=cell["latent_size"],
        q_block=cfg.get("attn_q_block"),
        dtype=jnp.dtype(cfg["dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        **{k: cfg[k] for k in keys})


def plan(cfg: dict, cell: dict):
    """``(model_cfg, compiled)``: the cell's pinned plan through
    ``auto_pipeline``."""
    from repro.models.diffusion import hunyuan_pipeline_graph
    from repro.runtime.adapters import hunyuan_model_fns
    from repro.runtime.compile import auto_pipeline

    mc = model_config(cfg, cell)
    p = cell["plan"]
    mb_rows = cell["global_batch"] // p["microbatches"]
    compiled = auto_pipeline(
        hunyuan_pipeline_graph(mc, batch=mb_rows), hunyuan_model_fns(mc),
        p["dp"] * p["pp"], pipeline_devices=p["pp"],
        microbatches=p["microbatches"], dp_size=p["dp"],
        zero_stage=p["zero_stage"], wire_dtype=cfg["dtype"])
    return mc, compiled


@dataclasses.dataclass
class Batches:
    """Text-conditioned latents from ``(seed, step)``: latents, CLIP and
    mT5 states standard normal; the mT5 valid-token count lognormal
    around ``t5_typical`` (sigma ``t5_sigma``), clipped to ``[8, t5_len]``,
    and CLIP's that count clipped to ``clip_len``."""

    size: int
    channels: int
    clip: tuple[int, int]
    t5: tuple[int, int]
    batch: int
    t5_typical: float
    t5_sigma: float
    seed: int

    def __call__(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed * 1_000_003 + step) * 65_539)
        normal = lambda *shape: rng.standard_normal(shape, np.float32)
        n5 = np.clip(np.rint(rng.lognormal(np.log(self.t5_typical),
                                           self.t5_sigma, self.batch)),
                     8, self.t5[0]).astype(np.int32)
        return {"latents": normal(self.batch, self.size, self.size,
                                  self.channels),
                "clip": normal(self.batch, *self.clip),
                "t5": normal(self.batch, *self.t5),
                "clip_len": np.minimum(n5, self.clip[0]).astype(np.int32),
                "t5_len": n5}


def batches(cfg: dict, cell: dict, seed: int) -> Batches:
    return Batches(cell["latent_size"], cfg["in_ch"],
                   (cfg["clip_len"], cfg["text_dim"]),
                   (cfg["t5_len"], cfg["t5_dim"]), cell["global_batch"],
                   cell["t5_typical"], cell["t5_sigma"], seed)


def model_flops(cfg: dict, cell: dict) -> float:
    """Model FLOPs of one training step: 3x the forward's matrix products
    in the blocks (two for the backward): both attentions' projections and
    products (cross-attention's text K/V included), the MLP and the
    skip-ins; recomputation not.  The conditioning networks, the patch
    embedding and the final layer are left out: together about 0.2% of a
    step at 1024 px."""
    d, ff, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    n = (cell["latent_size"] // cfg["patch"]) ** 2
    lt = cfg["clip_len"] + cfg["t5_len"]
    assert hd * cfg["n_heads"] == d
    block = (2 * n * 4 * d * d + 2 * 2 * n * n * d            # self
             + 2 * n * 2 * d * d + 2 * lt * cfg["text_dim"] * 2 * d
             + 2 * 2 * n * lt * d                              # cross
             + 2 * n * 2 * d * ff)                             # MLP
    n_enc, n_dec, _ = reference.counts(cfg)
    skip = 2 * n * 2 * d * d
    return 3.0 * cell["global_batch"] * (cfg["n_layers"] * block
                                         + n_dec * skip)


def reference_run(cfg: dict, cell: dict, seed: int, steps: int, devices,
                  *, operand: str = "float32", fault: str | None = None
                  ) -> dict:
    """The reference's readings over the first ``steps`` steps of a run
    from ``seed``: losses, first-step gradient and parameter-change norms
    per leaf (per block for the block stacks)."""
    import jax

    key = jax.random.PRNGKey(seed)
    ref = reference.Reference(cfg, cell["latent_size"], devices,
                              operand=operand, fault=fault,
                              stages=cell["plan"]["pp"])
    return ref.train(key, batches(cfg, cell, seed),
                     lambda k: jax.random.fold_in(key, k), steps,
                     cfg["optimizer"])
