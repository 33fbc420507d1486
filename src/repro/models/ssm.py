"""Mamba1 selective SSM mixer (falcon-mamba).

TPU adaptation: the recurrence h_t = a_t ⊙ h_{t-1} + b_t is evaluated as a
*chunked associative scan* — parallel (VPU-friendly) within a chunk via
``jax.lax.associative_scan``, sequential carry across chunks — instead of
the CUDA selective-scan kernel.  This bounds the materialized state to
[B, chunk, d_inner, d_state] and gives remat a natural chunk boundary.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.common.types import ModelConfig
from repro.runtime.parallel import Parallelism, NO_PARALLEL


def _init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def dt_rank_of(cfg: ModelConfig) -> int:
    s = cfg.ssm
    return s.dt_rank if s.dt_rank else -(-cfg.d_model // 16)


def ssm_init(key, cfg: ModelConfig, d_stream: int, dtype=jnp.float32):
    s = cfg.ssm
    di, ds, dc = s.d_inner, s.d_state, s.d_conv
    dtr = dt_rank_of(cfg)
    ks = jax.random.split(key, 6)
    A = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
    return {
        "in_proj": _init(ks[0], (d_stream, 2 * di), d_stream, dtype),
        "conv_w": _init(ks[1], (dc, di), dc, jnp.float32),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": _init(ks[2], (di, dtr + 2 * ds), di, dtype),
        "dt_w": _init(ks[3], (dtr, di), dtr, jnp.float32),
        "dt_bias": jnp.full((di,), math.log(math.e - 1), jnp.float32),  # softplus^-1(1)*~
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _init(ks[4], (di, d_stream), di, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: [B,S,di]; w: [dc,di]."""
    dc = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (dc - 1, 0), (0, 0)))
    S = x.shape[1]
    y = sum(xp[:, i:i + S] * w[i][None, None, :] for i in range(dc))
    return y + b[None, None, :]


def _scan_op(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a2 * a1, a2 * b1 + b2


def _chunked_linear_scan(a: jax.Array, b: jax.Array, h0: jax.Array,
                         chunk: int) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + b_t over axis 1.  a,b: [B,S,...]; h0: [B,...].
    Returns (h [B,S,...], h_last)."""
    B, S = a.shape[:2]
    c = chunk if (S % chunk == 0 and S > chunk) else S
    nc = S // c
    ar = a.reshape((B, nc, c) + a.shape[2:])
    br = b.reshape((B, nc, c) + b.shape[2:])

    def outer(h, inputs):
        ac, bc = inputs                                  # [B,c,...]
        cum_a, local = jax.lax.associative_scan(_scan_op, (ac, bc), axis=1)
        h_all = local + cum_a * h[:, None]
        return h_all[:, -1], h_all

    h_last, hs = jax.lax.scan(outer, h0,
                              (jnp.moveaxis(ar, 1, 0), jnp.moveaxis(br, 1, 0)))
    hs = jnp.moveaxis(hs, 0, 1).reshape((B, S) + a.shape[2:])
    return hs, h_last


def ssm_apply(params, x: jax.Array, *, cfg: ModelConfig,
              par: Parallelism = NO_PARALLEL, return_cache: bool = False,
              h0=None):
    """x: [B,S,d] -> (out [B,S,d], cache | None).

    cache = (conv_state [B, d_conv-1, di], h [B, di, ds]).
    """
    s = cfg.ssm
    B, S, _ = x.shape
    di, ds = s.d_inner, s.d_state
    xz = x @ params["in_proj"]
    xz = par.cs(xz, "batch", None, "d_inner")
    xr, z = xz[..., :di], xz[..., di:]
    xc = jax.nn.silu(_causal_conv(xr, params["conv_w"], params["conv_b"]))

    dtr = params["dt_w"].shape[0]
    x_dbl = xc @ params["x_proj"]
    dt_in, Bt, Ct = (x_dbl[..., :dtr], x_dbl[..., dtr:dtr + ds],
                     x_dbl[..., dtr + ds:])
    dt = jax.nn.softplus(
        (dt_in @ params["dt_w"]).astype(jnp.float32) + params["dt_bias"])
    dt = par.cs(dt, "batch", None, "d_inner")
    A = -jnp.exp(params["A_log"])                            # [di, ds]
    a = jnp.exp(dt[..., None] * A[None, None])               # [B,S,di,ds]
    b = (dt * xc.astype(jnp.float32))[..., None] * Bt.astype(jnp.float32)[:, :, None, :]
    if h0 is None:
        h0 = jnp.zeros((B, di, ds), jnp.float32)
    h, h_last = _chunked_linear_scan(a, b, h0.astype(jnp.float32), s.chunk)
    y = jnp.einsum("bsiz,bsz->bsi", h, Ct.astype(jnp.float32))
    y = (y + params["D"][None, None] * xc.astype(jnp.float32)).astype(x.dtype)
    out = (y * jax.nn.silu(z)) @ params["out_proj"]
    out = par.cs(out, "batch", None, "d_model")
    cache = None
    if return_cache:
        dc = params["conv_w"].shape[0]
        conv_state = xr[:, S - (dc - 1):] if S >= dc - 1 else jnp.pad(
            xr, ((0, 0), (dc - 1 - S, 0), (0, 0)))
        cache = (conv_state.astype(x.dtype), h_last.astype(jnp.float32))
    return out, cache


def ssm_chunk(params, x: jax.Array, cache, *, cfg: ModelConfig,
              par: Parallelism = NO_PARALLEL, chunk_lens=None):
    """Chunked-prefill step: C tokens appended to carried recurrent state.

    x: [B, C, d]; cache = (conv_state [B, dc-1, di], h [B, di, ds]) rows
    for the chunk batch (gathered per slot by the caller).  The carry
    replaces the zero left-pad of the whole-prompt conv with the previous
    chunk's last dc-1 inputs, and h seeds the scan, so consecutive chunks
    compose to the full-prompt recurrence.

    ``chunk_lens`` [B] gives per-row valid token counts: padded tail
    positions of a final chunk perform *identity* state updates
    (a=1, b=0) and never enter the conv carry, so right-padding cannot
    corrupt the recurrent state — the chunked analogue of exact-length
    prefill.
    """
    s = cfg.ssm
    B, C, _ = x.shape
    di, ds = s.d_inner, s.d_state
    conv_state, h0 = cache
    xz = x @ params["in_proj"]
    xz = par.cs(xz, "batch", None, "d_inner")
    xr, z = xz[..., :di], xz[..., di:]
    dc = params["conv_w"].shape[0]
    w = params["conv_w"]
    xfull = jnp.concatenate([conv_state.astype(xr.dtype), xr], axis=1)
    y = sum(xfull[:, i:i + C] * w[i][None, None, :] for i in range(dc))
    xc = jax.nn.silu(y + params["conv_b"][None, None, :])

    dtr = params["dt_w"].shape[0]
    x_dbl = xc @ params["x_proj"]
    dt_in, Bt, Ct = (x_dbl[..., :dtr], x_dbl[..., dtr:dtr + ds],
                     x_dbl[..., dtr + ds:])
    dt = jax.nn.softplus(
        (dt_in @ params["dt_w"]).astype(jnp.float32) + params["dt_bias"])
    dt = par.cs(dt, "batch", None, "d_inner")
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt[..., None] * A[None, None])               # [B,C,di,ds]
    b = (dt * xc.astype(jnp.float32))[..., None] * Bt.astype(jnp.float32)[:, :, None, :]
    if chunk_lens is not None:
        valid = jnp.arange(C, dtype=jnp.int32)[None] < chunk_lens[:, None]
        a = jnp.where(valid[..., None, None], a, 1.0)
        b = jnp.where(valid[..., None, None], b, 0.0)
    h0 = h0.astype(jnp.float32)
    if cfg.use_pallas and par.mesh is None and C % min(s.chunk, C) == 0:
        from repro.kernels import ops as kops
        h, h_last = kops.ssm_scan(a, b, h0, chunk=s.chunk)
    else:
        h, h_last = _chunked_linear_scan(a, b, h0, s.chunk)
    y = jnp.einsum("bsiz,bsz->bsi", h, Ct.astype(jnp.float32))
    y = (y + params["D"][None, None] * xc.astype(jnp.float32)).astype(x.dtype)
    out = (y * jax.nn.silu(z)) @ params["out_proj"]
    out = par.cs(out, "batch", None, "d_model")
    lens = (jnp.full((B,), C, jnp.int32) if chunk_lens is None
            else chunk_lens.astype(jnp.int32))
    # conv carry = last dc-1 *valid* inputs: xfull rows lens .. lens+dc-2
    idx = lens[:, None] + jnp.arange(dc - 1, dtype=jnp.int32)[None, :]
    conv_new = jnp.take_along_axis(xfull, idx[..., None], axis=1)
    return out, (conv_new.astype(conv_state.dtype), h_last)


def ssm_decode(params, x: jax.Array, cache, *, cfg: ModelConfig,
               par: Parallelism = NO_PARALLEL, active=None):
    """Single-token step. x: [B,1,d]; cache=(conv_state, h).

    ``active`` [B] bool (optional) freezes the state of inactive lanes —
    slots mid-chunked-prefill must not have their recurrent state mutated
    by decode steps of the surrounding batch."""
    s = cfg.ssm
    di, ds = s.d_inner, s.d_state
    conv_state, h = cache
    xz = x[:, 0] @ params["in_proj"]
    xz = par.cs(xz, "batch", "d_inner")
    xr, z = xz[..., :di], xz[..., di:]
    window = jnp.concatenate([conv_state, xr[:, None]], axis=1)  # [B,dc,di]
    w = params["conv_w"]
    xc = jax.nn.silu(jnp.einsum("bci,ci->bi", window.astype(jnp.float32),
                                w) + params["conv_b"]).astype(x.dtype)
    dtr = params["dt_w"].shape[0]
    x_dbl = xc @ params["x_proj"]
    dt_in, Bt, Ct = (x_dbl[..., :dtr], x_dbl[..., dtr:dtr + ds],
                     x_dbl[..., dtr + ds:])
    dt = jax.nn.softplus(
        (dt_in @ params["dt_w"]).astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt[..., None] * A[None])                      # [B,di,ds]
    b = (dt * xc.astype(jnp.float32))[..., None] * Bt.astype(jnp.float32)[:, None, :]
    h_new = a * h + b
    y = jnp.einsum("biz,bz->bi", h_new, Ct.astype(jnp.float32))
    y = (y + params["D"][None] * xc.astype(jnp.float32)).astype(x.dtype)
    out = ((y * jax.nn.silu(z)) @ params["out_proj"])[:, None]
    out = par.cs(out, "batch", None, "d_model")
    win_new = window[:, 1:]
    if active is not None:
        h_new = jnp.where(active[:, None, None], h_new, h)
        win_new = jnp.where(active[:, None, None], win_new, conv_state)
    return out, (win_new, h_new)
