"""Fused skip-concat matmul:  y = [h | s] @ W  ==  h @ W1 + s @ W2.

Every decoder block of UViT / Hunyuan-DiT (and the UNet up-path) consumes
its locally-cached skip activation through exactly this contraction; fusing
it avoids materialising the (M, 2D) concat in HBM — on TPU that halves the
activation read traffic of the projection (the concat would round-trip
HBM->VMEM twice).

Grid: (M/bm, N/bn); the K loop streams both halves of W and reuses the
h/s tiles already resident in VMEM.  f32 accumulation in VREGs; tiles are
(bm x bk)·(bk x bn) MXU shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(h_ref, s_ref, w1_ref, w2_ref, o_ref, *, block_k: int, K: int):
    bm = h_ref.shape[0]
    bn = o_ref.shape[1]
    nk = K // block_k

    def body(ki, acc):
        sl = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        acc += jnp.dot(h_ref[:, sl], w1_ref[sl, :],
                       preferred_element_type=jnp.float32)
        acc += jnp.dot(s_ref[:, sl], w2_ref[sl, :],
                       preferred_element_type=jnp.float32)
        return acc

    acc = jax.lax.fori_loop(0, nk, body,
                            jnp.zeros((bm, bn), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def skip_concat_matmul_fwd(h: jax.Array, s: jax.Array, w: jax.Array, *,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """h,s: (M, D); w: (2D, N)."""
    M, D = h.shape
    N = w.shape[1]
    w1, w2 = w[:D], w[D:]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, D)
    assert M % bm == 0 and N % bn == 0 and D % bk == 0
    kernel = functools.partial(_kernel, block_k=bk, K=D)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bn), lambda i, j: (0, j)),
            pl.BlockSpec((D, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), h.dtype),
        interpret=interpret,
    )(h, s, w1, w2)
