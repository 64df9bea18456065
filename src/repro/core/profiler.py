"""Per-block cost profiling (paper §IV-A "profile layer runtimes").

``analytic_block_costs``: FLOPs / peak + bytes / HBM-bandwidth roofline
estimate — deterministic, used for dry-runs and the tuner on CPU where
wall-clock timing of TPU kernels is meaningless.  Measured block costs
come from a profiler trace of the training step instead: the program
names its stages and the model's parts with ``jax.named_scope``
(``runtime/scopes.py``) and ``bench/scopes.py`` sums each scope's device
time.
"""
from __future__ import annotations

import dataclasses

from typing import Sequence

from repro.core.graph import Block, BlockGraph
from repro.core.hw import Hardware, TPU_V5E


def analytic_time(flops: float, bytes_moved: float, hw: Hardware = TPU_V5E) -> float:
    """max(compute, memory) roofline time for one block."""
    return max(flops / hw.peak_flops, bytes_moved / hw.hbm_bw)


def analytic_block_costs(
    blocks: Sequence[Block], hw: Hardware = TPU_V5E
) -> tuple[Block, ...]:
    """Return blocks with ``fwd_time`` replaced by the roofline estimate."""
    out = []
    for b in blocks:
        bytes_moved = 2 * b.param_bytes + 2 * b.act_bytes  # read params+act, write act
        t = analytic_time(b.flops, bytes_moved, hw)
        out.append(Block(b.name, t, b.param_bytes, b.act_bytes, b.skip_bytes, b.flops))
    return tuple(out)


def reprofile_graph(graph: BlockGraph, hw: Hardware = TPU_V5E) -> BlockGraph:
    """Analytically re-profile every block of a graph for hardware ``hw``."""
    return dataclasses.replace(graph,
                               blocks=analytic_block_costs(graph.blocks, hw))
