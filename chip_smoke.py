"""Bring-up check of the PULSE training path on a TPU.

Trains the paper's UViT-H (``configs/uvit_h.py``) at its published widths
through the normal entry point, ``launch/train.py`` -> ``auto_pipeline``
-> table executor -> AdamW, for a few steps on random weights from the
trainer's seed.  It checks that every loss is finite, that the step-0
pipelined loss agrees with a float32 single-device ``uvit_loss`` on the
same parameters, batch, timesteps and noise, and (one chip) that the fused
skip-in Pallas kernel compiles for the chip and matches its reference.

    python chip_smoke.py             # one chip: P=1 wave fold, 8 of 32 layers
    python chip_smoke.py --chips 4   # four chips: P=4, then P=2 x dp=2 ZeRO-2,
                                     # all 32 layers

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits nonzero and prints no such line when JAX's first device is
not a TPU or when any phase fails.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys

#: agreement of a bf16 computation with its float32 reference: a few bf16
#: ulps (2^-8 each) of rounding through the network, far below what a wrong
#: skip pairing, schedule or gradient would move a random model's loss
RTOL = 2e-2
#: peak AdamW rate of the 20-step warm-up.  At d_model 2560, 1e-3 already
#: throws the loss from 1.8 to 5.7 on the first step, in float32 too
#: (Adam's first step moves every weight by the full rate)
LR = 1e-4
GLOBAL_BATCH = 32
#: (layers, microbatches, steps, warm-up steps, plans as (dp, pp, zero))
ONE_CHIP = (8, 4, 12, 2, ((1, 1, 0),))
FOUR_CHIPS = (32, 8, 4, 1, ((1, 4, 0), (2, 2, 2)))


class SmokeError(RuntimeError):
    """A phase's result is wrong."""


def model_line(arch: str, layers: int | None) -> str:
    import jax

    from repro.launch import train
    from repro.models.diffusion import init_uvit

    cfg = train.pipeline_config(arch, layers)
    full = train.pipeline_config(arch)
    shapes = jax.eval_shape(lambda k: init_uvit(k, cfg),
                            jax.random.PRNGKey(train.SEED))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    return (f"model: {cfg.name} d_model {cfg.d_model}, d_ff {cfg.d_ff}, "
            f"{cfg.n_heads} heads, latents {cfg.img_size}x{cfg.img_size}x"
            f"{cfg.in_ch}, patch {cfg.patch} ({cfg.n_tokens} tokens), "
            f"{cfg.n_classes} classes; depth {cfg.n_layers} of "
            f"{full.n_layers} layers (depth is the only cut); {n:,} "
            f"parameters ({jax.numpy.dtype(cfg.param_dtype).name})")


def reference_loss(arch: str, layers: int | None, global_batch: int
                   ) -> float:
    """The trainer's step-0 loss computed without the pipeline: the same
    parameters (its seed), batch, timesteps and noise, with the bf16
    weights read in float32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train
    from repro.models.diffusion import init_uvit, uvit_loss

    cfg = train.pipeline_config(arch, layers)
    key = jax.random.PRNGKey(train.SEED)
    params = jax.jit(lambda k: init_uvit(k, cfg))(key)
    batch = train.pipeline_loader(cfg, global_batch).get(0)
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p, b, r: uvit_loss(p, b, r, f32))(
            params, batch, jax.random.fold_in(key, 0))
    return float(loss)


def train_plan(arch: str, layers: int | None, dp: int, pp: int, zero: int,
               global_batch: int, microbatches: int, steps: int):
    """One in-process run of the training driver; its TrainResult."""
    from repro.launch import train

    argv = ["--pipeline", "--arch", arch, "--dp", str(dp), "--pp", str(pp),
            "--zero-stage", str(zero), "--global-batch", str(global_batch),
            "--microbatches", str(microbatches), "--steps", str(steps),
            "--lr", str(LR), "--log-every", "1"]
    if layers is not None:
        argv += ["--layers", str(layers)]
    return train.run(train._parse_args(argv))


def check_plan(res, ref: float, warmup: int, *, must_fall: bool) -> None:
    """Print a run's plan, compile time and steps; raise on a wrong one."""
    p = res.plan
    print(f"plan: P={p['P']} dp={p['dp']} V={p['V']} M={p['M']} "
          f"zero={p['zero_stage']} wire={p['wire_dtype']} "
          f"(S={p['S']} stages, cuts {p['cuts']})")
    print(f"compile: {res.compile_s:.2f} s (step program)")
    steps = sorted(res.losses)
    for s in steps:
        tag = " (warm-up)" if s < warmup else ""
        print(f"step {s}: loss {res.losses[s]:.6f} time "
              f"{res.step_s[s]:.4f} s{tag}")
    timed = [s for s in steps if s >= warmup]
    print(f"step time: median {statistics.median(res.step_s[s] for s in timed):.4f}"
          f" s over {len(timed)} timed steps (host clock, to the loss on the "
          "host)")
    bad = [s for s in steps if not math.isfinite(res.losses[s])]
    if bad:
        raise SmokeError(f"non-finite loss at steps {bad}")
    l0 = res.losses[0]
    rel = abs(l0 - ref) / abs(ref)
    print(f"step-0 loss {l0:.6f} vs float32 single-device reference "
          f"{ref:.6f}: relative difference {rel:.3e} (rtol {RTOL})")
    if rel > RTOL:
        raise SmokeError(f"pipelined step-0 loss {l0} disagrees with the "
                         f"reference {ref} (rel {rel:.3e} > {RTOL})")
    if must_fall and not res.losses[timed[-1]] < res.losses[timed[0]]:
        raise SmokeError(
            f"loss did not fall over the timed steps: step {timed[0]} "
            f"{res.losses[timed[0]]} -> step {timed[-1]} "
            f"{res.losses[timed[-1]]}")


def kernel_phase(rows: int = 2048, width: int = 2560) -> None:
    """The fused skip-in kernel at UViT-H width, compiled for the device
    (``tpu_custom_call`` in the program), against its reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.skip_matmul import (skip_concat_matmul,
                                           skip_concat_matmul_reference)

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (rows, width), jnp.bfloat16)
    s = jax.random.normal(ks[1], (rows, width), jnp.bfloat16)
    w = (jax.random.normal(ks[2], (2 * width, width))
         / (2 * width) ** 0.5).astype(jnp.bfloat16)
    fn = jax.jit(skip_concat_matmul)
    text = fn.lower(h, s, w).compile().as_text()
    if "tpu_custom_call" not in text:
        raise SmokeError("skip_concat_matmul compiled without its Pallas "
                         "kernel (no tpu_custom_call in the program)")
    out = fn(h, s, w).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(skip_concat_matmul_reference)(h, s, w)
    ref = ref.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    print(f"kernel: skip_concat_matmul bf16 ({rows}, {width}) x "
          f"({2 * width}, {width}) compiled with tpu_custom_call; max |out -"
          f" ref| / max |ref| = {err:.3e} (limit {RTOL})")
    if err > RTOL:
        raise SmokeError(f"skip_concat_matmul disagrees with its reference "
                         f"({err:.3e} > {RTOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the multi-chip pipeline plans only")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{devs[0].platform!r}); this check runs on a TPU only",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")
    layers, micro, steps, warmup, plans = (
        FOUR_CHIPS if args.chips == 4 else ONE_CHIP)
    print(model_line("uvit-h", layers))
    if args.chips == 1:
        kernel_phase()
    ref = reference_loss("uvit-h", layers, GLOBAL_BATCH)
    print(f"reference: float32 single-device step-0 loss {ref:.6f}")
    for dp, pp, zero in plans:
        res = train_plan("uvit-h", layers, dp, pp, zero, GLOBAL_BATCH, micro,
                         steps)
        check_plan(res, ref, warmup, must_fall=args.chips == 1)
        used = res.device_bytes
        print("bytes_in_use per device with the training state live: "
              + ", ".join(f"{b / 2 ** 30:.2f} GiB" for b in used))
        if args.chips == 4 and min(used) < 0.5 * max(used):
            raise SmokeError(f"training state not spread over the chips: "
                             f"bytes_in_use {used}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    print("peak HBM per device over the run: "
          + ", ".join(f"{b / 2 ** 30:.2f} GiB" for b in peaks))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
