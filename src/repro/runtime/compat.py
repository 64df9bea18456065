"""JAX APIs whose home or behaviour moves between releases, in one place.

Runtime, train-step, test-helper and benchmark code imports ``shard_map``
from here (README "JAX compat imports"); the lint rule
``compat-only-experimental`` keeps ``jax.experimental`` imports out of
everything but this module, ``runtime/sharding.py`` and the kernels.
"""
from __future__ import annotations

from typing import Any

from jax import shard_map  # noqa: F401  (re-exported)


def tree_to_host(tree: Any) -> Any:
    """Pull every concrete array in a pytree to host memory.

    Stage stacks that come out of a ``bind``-ed executor (parameters or
    gradients) are sharded over the ``"model"`` axis and, under ZeRO-2,
    over ``"data"`` too.  On JAX 0.9 the ``jax.make_mesh`` default gives
    those meshes explicit axis types, and re-assembling the stacks on the
    device fails outright (the ``linear-zero2`` / ``wave-zero1`` /
    ``wave-zero2`` equivalence configs without this pull): eagerly with a
    ``ShardingTypeError`` on the slot gather of a ``[2@model, ...,
    32@data, ...]`` ZeRO-2 stack, and under jit with "slicing on sharded
    dims ... not divisible by mesh axes" on the per-device row slice.
    The host copy is a plain array that slices and concatenates without a
    sharding.  No-op on tracers, so merge helpers stay usable under jit.
    Merging is a cold path (gradient checks, checkpoint export, a caller
    asking for the logical params), so one transfer per merge is cheap.
    """
    import jax
    import numpy as np

    def pull(x):
        if isinstance(x, jax.core.Tracer):
            return x
        if isinstance(x, jax.Array):
            return np.asarray(x)
        return x

    return jax.tree.map(pull, tree)
