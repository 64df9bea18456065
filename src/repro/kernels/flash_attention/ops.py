"""jit'd public wrapper: GQA layout handling + custom VJP.

Forward runs the Pallas kernel (interpret=True on CPU so the kernel body
itself is what's validated); backward recomputes through the jnp reference
(flash backward kernel is follow-up work — the training hot path already
runs under per-layer remat, so the recompute is the same one remat pays).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.backend import use_interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=None,
                    block_q=128, block_k=128):
    """q: (B,S,Hq,D); k,v: (B,T,Hkv,D) with Hq % Hkv == 0. -> (B,S,Hq,D)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    groups = Hq // Hkv
    kf = jnp.repeat(k, groups, axis=2) if groups > 1 else k
    vf = jnp.repeat(v, groups, axis=2) if groups > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kf = kf.transpose(0, 2, 1, 3).reshape(B * Hq, T, D)
    vf = vf.transpose(0, 2, 1, 3).reshape(B * Hq, T, D)
    out = flash_attention_fwd(qf, kf, vf, causal=causal, window=window,
                              block_q=min(block_q, S),
                              block_k=min(block_k, T),
                              interpret=use_interpret())
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)


def _ref_gqa(q, k, v, causal, window):
    groups = q.shape[2] // k.shape[2]
    kf = jnp.repeat(k, groups, axis=2) if groups > 1 else k
    vf = jnp.repeat(v, groups, axis=2) if groups > 1 else v
    return attention_reference(q, kf, vf, causal=causal, window=window)


def _fwd(q, k, v, causal, window, block_q, block_k):
    out = flash_attention(q, k, v, causal, window, block_q, block_k)
    return out, (q, k, v)


def _bwd(causal, window, block_q, block_k, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: _ref_gqa(q, k, v, causal, window),
                     q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
