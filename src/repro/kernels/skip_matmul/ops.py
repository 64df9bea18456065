"""jit'd wrapper with custom VJP (backward = three plain matmuls)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.skip_matmul.kernel import skip_concat_matmul_fwd
from repro.kernels.backend import use_interpret


# Single source of truth for the launch constraints lives in the static
# analysis layer (repro.analysis.kernel_check, jax-free): each dim must
# be a positive multiple of its clamped block size and the VMEM-resident
# blocks must fit the core budget.  Callers check the predicate and
# reject shapes that do not tile before reaching the kernel's
# trace-time assert.
from repro.analysis.kernel_check import skip_concat_matmul_supported  # noqa: F401


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def skip_concat_matmul(h, s, w):
    """h,s: (..., D); w: (2D, N) -> (..., N)."""
    shape = h.shape
    D = shape[-1]
    hf = h.reshape(-1, D)
    sf = s.reshape(-1, D)
    out = skip_concat_matmul_fwd(hf, sf, w, interpret=use_interpret())
    return out.reshape(*shape[:-1], w.shape[1])


def _fwd(h, s, w):
    return skip_concat_matmul(h, s, w), (h, s, w)


def _bwd(res, g):
    h, s, w = res
    D = h.shape[-1]
    gf = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    hf = h.reshape(-1, D).astype(jnp.float32)
    sf = s.reshape(-1, D).astype(jnp.float32)
    w1, w2 = w[:D].astype(jnp.float32), w[D:].astype(jnp.float32)
    dh = (gf @ w1.T).reshape(h.shape).astype(h.dtype)
    ds = (gf @ w2.T).reshape(s.shape).astype(s.dtype)
    dw = jnp.concatenate([hf.T @ gf, sf.T @ gf], axis=0).astype(w.dtype)
    return dh, ds, dw


skip_concat_matmul.defvjp(_fwd, _bwd)
