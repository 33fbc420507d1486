"""Fused RMSNorm Pallas kernel: one HBM pass per row block (the unfused
XLA form reads x twice — once for the variance reduction, once for the
scale — and materializes the fp32 upcast)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # [rows, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    w = 1.0 + s_ref[...].astype(jnp.float32)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool) -> jax.Array:
    """x: [..., d]; scale: [d] (gemma-style 1+scale)."""
    shp = x.shape
    d = shp[-1]
    rows = 1
    for s in shp[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    if rows % br:
        br = rows
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out.reshape(shp)
