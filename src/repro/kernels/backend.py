"""Where the Pallas kernels run: compiled by Mosaic on a TPU, in interpret
mode on the CPU (the test suite), nowhere else."""
from __future__ import annotations

import jax


def use_interpret() -> bool:
    """True on the CPU backend, False on a TPU; any other backend raises
    rather than silently running a kernel through the interpreter."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or in interpret mode on the "
        f"CPU; the default JAX backend is {backend!r}")
