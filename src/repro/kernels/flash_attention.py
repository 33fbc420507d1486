"""Flash attention (prefill/train) as a Pallas TPU kernel.

Grid (B·H, n_q, n_k), dimension semantics (parallel, parallel, arbitrary):
for a fixed (head, q-block) the k dimension is iterated sequentially, so
the online-softmax state (m, l, acc) lives in VMEM scratch across k steps.
Block shapes are MXU-aligned (q/k blocks multiples of 128 where the
problem allows); causal block skipping is done with @pl.when — skipped
blocks issue no MXU work.

VMEM working set per step: q (cq·hd) + k,v (ck·hd each) + acc (cq·hd fp32)
+ scores (cq·ck fp32) ≈ 1.3 MB at cq=ck=256, hd=128 — comfortably inside
the ~16 MB/core budget with double buffering.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, softcap: Optional[float],
            block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: a k block fully above the diagonal contributes nothing —
    # @pl.when skips it (no MXU work issued)
    if causal:
        needed = k_start <= q_start + block_q - 1
    else:
        needed = ki >= 0

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [cq, hd]
        k = k_ref[0].astype(jnp.float32)                  # [ck, hd]
        v = v_ref[0].astype(jnp.float32)                  # [ck, dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [cq, ck]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    softcap: Optional[float] = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool) -> jax.Array:
    """q: [B, Sq, H, hd]; k/v: [B, Sk, H, hd/dv] (kv pre-expanded to H
    heads).  Returns [B, Sq, H, dv]."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    dv = v.shape[-1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(f"seq ({Sq},{Sk}) must tile ({block_q},{block_k})")
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = hd ** -0.5

    # [B, S, H, d] -> [B*H, S, d]
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, dv)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, dv).transpose(0, 2, 1, 3)
