"""Measured (wall-clock) pipeline throughput on simulated devices.

Unlike the analytic models, this actually RUNS the wave executor and the
skip-carry baseline on 8 forced host devices and times steps — a measured
reproduction of the paper's headline direction (PULSE > baseline) at CPU
scale.  Runs in a subprocess to keep the parent single-device.
"""
from __future__ import annotations

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.runtime.compat import shard_map
from repro.models.diffusion import UViTConfig, init_uvit
from repro.runtime.pipeline import PipelineConfig
from repro.runtime.adapters import DiffusionPipelineAdapter, make_diffusion_microbatches

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = UViTConfig("b", img_size=16, in_ch=4, patch=2, d_model=128,
                 n_layers=8, n_heads=4, d_ff=256, n_classes=10)
key = jax.random.PRNGKey(0)
params = init_uvit(key, cfg)
B, M = 16, 4
batch = {"latents": jax.random.normal(key, (B, 16, 16, 4)),
         "labels": jax.random.randint(key, (B,), 0, 10)}
mb, aux = make_diffusion_microbatches(batch, key, M, cfg, "uvit")
pcfg = PipelineConfig(num_devices=4, num_microbatches=M,
                      data_axes=("data",), dp_size=2)
ad = DiffusionPipelineAdapter(cfg, pcfg, "uvit")

def bench(fn, stacks, edge):
    def loss(stacks, edge, mb, aux):
        return shard_map(fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("model"), stacks[0]),
                      jax.tree.map(lambda _: P("model"), stacks[1]),
                      jax.tree.map(lambda _: P(), edge),
                      jax.tree.map(lambda _: P(None, "data"), mb),
                      jax.tree.map(lambda _: P(None, "data"), aux)),
            out_specs=P(), check_vma=False)(stacks[0], stacks[1], edge, mb, aux)
    g = jax.jit(jax.grad(loss, argnums=(0, 1)))
    out = g(stacks, edge, mb, aux)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(g(stacks, edge, mb, aux))
    return (time.perf_counter() - t0) / 3

stacks, edge = ad.split_params(params)
t_wave = bench(ad.build(), stacks, edge)
stacks_b, edge_b = ad.split_params_skip_carry(params)
t_base = bench(ad.build_skip_carry_baseline(), stacks_b, edge_b)
print(f"RESULT wave_us={t_wave*1e6:.0f} base_us={t_base*1e6:.0f} "
      f"speedup={t_base/t_wave:.2f}")
"""


def run() -> list[str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu")   # simulated devices
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    for line in res.stdout.splitlines():
        if line.startswith("RESULT"):
            kv = dict(p.split("=") for p in line.split()[1:])
            return [
                f"pipeline_cpu.uvit8L.wave_step_us,{kv['wave_us']},",
                f"pipeline_cpu.uvit8L.skipcarry_step_us,{kv['base_us']},"
                f"speedup={kv['speedup']}x",
            ]
    raise RuntimeError(f"bench failed: {res.stdout[-500:]} {res.stderr[-2000:]}")


if __name__ == "__main__":
    print("\n".join(run()))
