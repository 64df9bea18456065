"""Paper Figs. 6 & 7: per-block imbalance + skip-aware DP improvement.

Prints ``name,us_per_call,derived`` CSV rows:
  partition_balance.<model>.blockwise_max_us  (max stage fwd time, baseline)
  partition_balance.<model>.dp_max_us         (skip-aware DP)
  derived = improvement %.
"""
from __future__ import annotations

import time

from repro.core.partition import blockwise_partition, partition_bidirectional
from repro.models.diffusion import (UViTConfig, uvit_block_graph,
                                    HunyuanDiTConfig, hunyuan_block_graph,
                                    UNetConfig, unet_block_graph)

MODELS = {
    "sdv2": lambda: unet_block_graph(
        UNetConfig("sdv2", img_size=32, base_ch=448, ch_mults=(1, 2, 4, 4),
                   blocks_per_level=2, attn_levels=(1, 2, 3), ctx_dim=1024),
        batch=32),
    "uvit": lambda: uvit_block_graph(
        UViTConfig("uvit", img_size=32, d_model=2560, n_layers=32,
                   n_heads=20, d_ff=10240), batch=32),
    # DiT-g/2 at its published widths, 64x64 latents (512 px)
    "hunyuan": lambda: hunyuan_block_graph(
        HunyuanDiTConfig("hy", img_size=64), batch=32),
}


def run() -> list[str]:
    rows = []
    for name, make in MODELS.items():
        g = make()
        times = [b.fwd_time for b in g.blocks]
        imbalance = max(times) / (sum(times) / len(times))
        t0 = time.perf_counter()
        dp = partition_bidirectional(g, 8, lam=0.0)
        solve_us = (time.perf_counter() - t0) * 1e6
        bw = blockwise_partition(g, 8, folded=True, lam=0.0)
        imp = 100.0 * (1 - dp.objective / bw.objective)
        rows.append(f"partition_balance.{name}.block_imbalance,"
                    f"{solve_us:.0f},max/mean={imbalance:.2f}x")
        rows.append(f"partition_balance.{name}.blockwise_max_us,"
                    f"{bw.objective*1e6:.1f},")
        rows.append(f"partition_balance.{name}.dp_max_us,"
                    f"{dp.objective*1e6:.1f},improvement={imp:.1f}%")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
