"""Blocked online-softmax attention (FlashAttention) as a Pallas TPU kernel.

TPU adaptation (not a CUDA port): the kernel is organised around MXU-shaped
matmul tiles — q/k/v blocks live in VMEM via BlockSpec; block sizes default
to (128 x head_dim) so both q.kT and p.v contractions feed the 128x128
systolic array; running max/sum are rank-1 f32 VREG-resident columns.

Grid: (batch*heads, S/block_q).  The kv loop is a fori_loop inside the
kernel over T/block_k tiles of the *whole* K/V rows, which stream
HBM->VMEM block by block.  Causal and sliding-window masking are applied
per tile; fully-masked tiles still execute (masked) — tile skipping is a
known follow-up optimization (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                 window: int | None, sm_scale: float, q_block: int,
                 kv_len: int):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale      # (block_q, D)
    bq, D = q.shape
    nk = kv_len // block_k

    def body(ki, carry):
        acc, m_prev, l_prev = carry
        rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        k = k_ref[rows, :].astype(jnp.float32)
        v = v_ref[rows, :].astype(jnp.float32)
        s = q @ k.T                                     # (bq, bk)
        q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out.astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int | None = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: (BH, S, D); k,v: (BH, T, D).  S % block_q == 0, T % block_k == 0."""
    BH, S, D = q.shape
    T = k.shape[1]
    assert S % block_q == 0 and T % block_k == 0
    sm_scale = 1.0 / math.sqrt(D)
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, causal=causal, window=window,
        sm_scale=sm_scale, q_block=block_q, kv_len=T)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
