"""Chunked linear recurrence h_t = a_t ⊙ h_{t-1} + b_t as a Pallas TPU
kernel (the Mamba/RG-LRU inner loop).

Grid (B, n_feature_blocks, n_chunks): the chunk dim is sequential; the
carry h lives in VMEM scratch across chunks, so HBM sees each (a, b)
element exactly once and h only at chunk granularity — the TPU-native
replacement for the CUDA selective-scan kernel.  Within a chunk the
recurrence is a VPU loop over time (elementwise; no MXU needed).

The recurrence is elementwise over (d_inner, d_state), so the kernel sees
each time step as one lane-dense row of bd·ds features: the (di, ds)
trailing dims are flattened (a free reshape), which keeps a small d_state
from padding every VMEM tile to 128 lanes.  The time loop loads and
stores whole 8-row sublane groups and steps through their rows in
registers.

VMEM per step: 3 arrays · 2 buffers · (chunk · bd · ds) fp32 ≈ 12 MB at
chunk=256, bd=128, ds=16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, h_scr, *,
            chunk: int, rows: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def group(g, h):
        t = pl.multiple_of(g * rows, rows)
        a = a_ref[0, pl.ds(t, rows)].astype(jnp.float32)     # [rows, W]
        b = b_ref[0, pl.ds(t, rows)].astype(jnp.float32)
        out = []
        for r in range(rows):
            h = a[r:r + 1] * h + b[r:r + 1]
            out.append(h)
        o_ref[0, pl.ds(t, rows)] = jnp.concatenate(out).astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // rows, group, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def ssm_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *,
             chunk: int = 256, block_d: int = 0,
             interpret: bool):
    """a, b: [B, S, di, ds]; h0: [B, di, ds] -> (h [B,S,di,ds] fp32,
    h_last [B,di,ds] fp32)."""
    B, S, di, ds = a.shape
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} must tile chunk={chunk}")
    bd = block_d or min(di, 128)
    if di % bd:
        raise ValueError(f"d_inner={di} must tile block_d={bd}")
    n_chunks = S // chunk
    n_d = di // bd
    W = bd * ds

    kernel = functools.partial(_kernel, chunk=chunk,
                               rows=math.gcd(chunk, 8), n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, W), lambda b_, d, c: (b_, c, d))
    state_spec = pl.BlockSpec((1, 1, W), lambda b_, d, c: (b_, 0, d))
    h, h_last = pl.pallas_call(
        kernel,
        grid=(B, n_d, n_chunks),
        in_specs=[seq_spec, seq_spec, state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di * ds), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, di * ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        interpret=interpret,
    )(a.reshape(B, S, di * ds), b.reshape(B, S, di * ds),
      h0.reshape(B, 1, di * ds))
    return h.reshape(B, S, di, ds), h_last.reshape(B, di, ds)
