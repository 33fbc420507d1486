"""Pallas int8-weight matmul with fused dequant.

The weight matrix stays int8 in HBM and is dequantized in-register: each
grid cell DMA's an int8 [K, bn] tile, upcasts it in VMEM, contracts, and
applies the per-output-channel scale to the fp32 accumulator — fp weights
are never materialized.  Serving uses this for the LM head and MLP
projections, where weight bytes dominate the decode-step HBM traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tile(dim: int, pref: int, align: int) -> int:
    """Largest divisor of ``dim`` that is <= ``pref`` and a multiple of
    ``align`` (the TPU tiling of that block dim), else ``dim`` itself —
    a block spanning the whole dim is always legal."""
    for t in range(min(dim, pref) // align * align, 0, -align):
        if dim % t == 0:
            return t
    return dim


def _kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # int8 -> f32 in-register
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = acc * s_ref[...]               # fused per-channel rescale


def int8_matmul(x: jax.Array, w: jax.Array, scale: jax.Array, *,
                block_m: int = 256, block_n: int = 256,
                interpret: bool) -> jax.Array:
    """x: [M, K] float; w: [K, N] int8; scale: [1, N] fp32 per-output-
    channel.  Returns [M, N] fp32 = (x @ dequant(w)) with the rescale
    fused into the accumulator."""
    M, K = x.shape
    Kw, N = w.shape
    if K != Kw:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    bm, bn = _tile(M, block_m, 8), _tile(N, block_n, 128)
    return pl.pallas_call(
        _kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, w, scale.astype(jnp.float32))
