"""jit'd wrapper with custom VJP.

Backward of h_t = a_t h_{t-1} + x_t is itself a *reversed* gated scan:
    dx_t = g_t,   g_{t-1} += a_t * g_t  =>  dX = reverse-scan(a_{t+1}, dh)
    da_t = dX_t * h_{t-1}
so the same kernel serves both directions (time-flipped).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels.linear_scan.kernel import gated_linear_scan_fwd
from repro.kernels.backend import use_interpret


@jax.custom_vjp
def gated_linear_scan(a, x):
    """a, x: (R, T, C) -> h: (R, T, C) with h_t = a_t*h_{t-1} + x_t."""
    return gated_linear_scan_fwd(a, x, interpret=use_interpret())


def _fwd(a, x):
    h = gated_linear_scan(a, x)
    return h, (a, h)


def _bwd(res, g):
    a, h = res
    # dX solves the reversed recurrence: dX_t = g_t + a_{t+1} dX_{t+1}
    a_next = jnp.concatenate([a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1)
    dx = gated_linear_scan(a_next[:, ::-1], g[:, ::-1].astype(a.dtype))[:, ::-1]
    h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)
    da = (dx.astype(jnp.float32) * h_prev.astype(jnp.float32)).astype(a.dtype)
    return da, dx.astype(g.dtype)


gated_linear_scan.defvjp(_fwd, _bwd)
