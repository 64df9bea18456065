"""The UViT family: how a cell of it is built from the program's public
pieces, what it is fed, how much work a step is, and its reference.

The program under test is PULSE's training path as ``launch/train.py``
builds it: ``uvit_pipeline_graph`` and ``diffusion_model_fns`` planned by
``auto_pipeline`` with the cell's pinned plan, and the jitted
``pipeline_step`` (pipelined loss and grads, the non-finite guard, AdamW,
donated state).  Nothing of the step, the executor or the model is copied
here.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import uvit as reference


def model_config(cfg: dict, cell: dict):
    """The program's ``UViTConfig`` for a configuration file and a cell's
    latent resolution (the positional embedding follows the resolution)."""
    import jax.numpy as jnp

    from repro.models.diffusion import UViTConfig

    if cfg["head_dim"] * cfg["n_heads"] != cfg["d_model"]:
        raise ValueError("the program's UViT ties head_dim to "
                         "d_model / n_heads")
    return UViTConfig(
        name=cfg["name"], img_size=cell["latent_size"], in_ch=cfg["in_ch"],
        patch=cfg["patch"], d_model=cfg["d_model"],
        n_layers=cfg["n_layers"], n_heads=cfg["n_heads"], d_ff=cfg["d_ff"],
        n_classes=cfg["n_classes"], norm_eps=cfg["norm_eps"],
        dtype=jnp.dtype(cfg["dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def plan(cfg: dict, cell: dict):
    """``(model_cfg, compiled)``: the cell's pinned plan through
    ``auto_pipeline``."""
    from repro.models.diffusion import uvit_pipeline_graph
    from repro.runtime.adapters import diffusion_model_fns
    from repro.runtime.compile import auto_pipeline

    mc = model_config(cfg, cell)
    p = cell["plan"]
    mb_rows = cell["global_batch"] // p["microbatches"]
    compiled = auto_pipeline(
        uvit_pipeline_graph(mc, batch=mb_rows), diffusion_model_fns(mc, "uvit"),
        p["dp"] * p["pp"], pipeline_devices=p["pp"],
        microbatches=p["microbatches"], dp_size=p["dp"],
        zero_stage=p["zero_stage"], wire_dtype=cfg["dtype"])
    return mc, compiled


def train_step(compiled, model_cfg, mesh, opt: dict):
    """The program's jitted train step and the shardings of its
    ``(params, opt_state, batch, rng, lr)``."""
    from repro.launch.train import pipeline_step
    from repro.optim import AdamWConfig

    opt_cfg = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                          eps=opt["eps"], weight_decay=opt["weight_decay"],
                          clip_norm=opt["clip_norm"])
    return pipeline_step(compiled, model_cfg, mesh, opt_cfg)


@dataclasses.dataclass
class Batches:
    """Class-conditioned synthetic latents from ``(seed, step)``: each
    class a fixed Gaussian mode, each sample its mode plus 0.3 noise.  The
    arithmetic of the program's ``SyntheticLatentDataset``, kept here so
    that the benchmark's inputs cannot change with the program."""

    size: int
    channels: int
    n_classes: int
    batch: int
    seed: int

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self.modes = rng.normal(0, 1, size=(
            self.n_classes, self.size, self.size, self.channels)
        ).astype(np.float32)

    def __call__(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed * 1_000_003 + step) * 65_539)
        labels = rng.integers(0, self.n_classes, size=self.batch
                              ).astype(np.int32)
        lat = (self.modes[labels] + 0.3 * rng.normal(0, 1, size=(
            self.batch, self.size, self.size, self.channels))
               ).astype(np.float32)
        return {"latents": lat, "labels": labels}


def batches(cfg: dict, cell: dict, seed: int) -> Batches:
    return Batches(cell["latent_size"], cfg["in_ch"], cfg["n_classes"],
                   cell["global_batch"], seed)


def model_flops(cfg: dict, cell: dict) -> float:
    """Model FLOPs of one training step: 3x the forward's matrix products
    (two for the backward), attention's two products and the decoder's
    skip projection included, recomputation not.  The patch embedding, the
    time MLP and the output projection are left out: together they are
    under 0.05% of a step at these widths."""
    d, ff, h = cfg["d_model"], cfg["d_ff"], cfg["n_layers"] // 2
    n = reference.n_tokens(cfg, cell["latent_size"])
    block = 2 * n * (4 * d * d) + 2 * (2 * n * n * d) + 2 * n * (2 * d * ff)
    skip = 2 * n * (2 * d * d)
    return 3.0 * cell["global_batch"] * (2 * h * block + h * skip)


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
