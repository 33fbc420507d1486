"""Pure-jnp oracles for every Pallas kernel (the ground truth the
kernel sweeps assert against)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """q: [B, Sq, H, hd]; k/v: [B, Sk, H, hd] -> [B, Sq, H, dv]."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None, None], s, -2e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, lengths) -> jax.Array:
    """q: [B, H, hd]; caches: [B, S, KH, hd]; lengths: [B] (#valid rows).
    GQA: H = KH * G.  Returns [B, H, hd]."""
    B, S, KH, hd = k_cache.shape
    H = q.shape[1]
    G = H // KH
    qf = q.astype(jnp.float32).reshape(B, KH, G, hd) * hd ** -0.5
    s = jnp.einsum("bngd,bsnd->bngs", qf, k_cache.astype(jnp.float32))
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, -2e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngs,bsnd->bngd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_table,
                               lengths) -> jax.Array:
    """q: [B, H, hd]; pools: [N, bs, KH, hd]; block_table: [B, nmax].
    Gathers the table's blocks into a contiguous cache and defers to the
    dense oracle."""
    N, bs, KH, hd = k_pool.shape
    B = q.shape[0]
    nmax = block_table.shape[1]
    k = k_pool[block_table.reshape(-1)].reshape(B, nmax * bs, KH, hd)
    v = v_pool[block_table.reshape(-1)].reshape(B, nmax * bs, KH,
                                                v_pool.shape[-1])
    return decode_attention_ref(q, k, v, lengths)


def int8_matmul_ref(x, w, scale) -> jax.Array:
    """x: [M, K] float; w: [K, N] int8; scale: [1, N] per-output-channel.
    Returns [M, N] fp32 = x @ (w * scale)."""
    return x.astype(jnp.float32) @ (w.astype(jnp.float32) * scale)


def ssm_scan_ref(a, b, h0) -> tuple:
    """h_t = a_t * h_{t-1} + b_t.  a/b: [B, S, ...]; h0: [B, ...].
    Returns (h [B, S, ...], h_last [B, ...]) in fp32."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    h_last, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                              (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1), h_last


def rmsnorm_ref(x, scale, *, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)
