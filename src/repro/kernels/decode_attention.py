"""Flash-decode as a Pallas TPU kernel: one query token per sequence
against a long KV cache, GQA-aware (KV read once per KV head, applied to
all G query heads in the group).

Two layouts, one kernel:

  paged_decode_attention  — block-pool cache [N, bs, KH, hd] indexed
      through a per-sequence block table (vLLM-style).  The table and the
      valid lengths ride in as *scalar-prefetch* operands, so the block
      index maps can compute DMA sources from the table before the kernel
      body runs — the gather costs no extra pass over HBM.
  decode_attention        — contiguous per-slot cache [B, S, KH, hd],
      served as a pool with the identity table.

The grid is (B, n_s): the cache-sequence dim is iterated sequentially
(online softmax in VMEM scratch).  Per-slot valid lengths mask ragged
continuous-batching batches, and ``max_len`` (the max *valid* length in
the batch, known on the host) truncates the sequential grid so a short
batch does not sweep empty cache blocks — decode is bandwidth-bound and
the kernel reads each *live* cache byte exactly once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _online_softmax_step(q, k, v, s_start, length, m_scr, l_scr, acc_scr, *,
                         scale: float, ks=None, vs=None):
    """One KV-block accumulation: q [G, hd], k [cs, hd], v [cs, dv].

    ``ks``/``vs`` ([cs, 1] fp32) are the per-token-per-head scales of an
    int8 cache block; the dequant happens here, in-register, inside the
    online-softmax loop — int8 is what crosses HBM."""
    q = q.astype(jnp.float32) * scale
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    if ks is not None:
        k = k * ks
        v = v * vs
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))      # [G, cs]
    cols = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < length, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, block_s: int, n_s: int, kv_heads: int,
                  hd: int, dv: int):
    if len(rest) == 6:          # int8 pools: scale blocks ride along
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    s_start = si * block_s

    @pl.when(s_start < length)
    def _compute():
        # the k/v block (all KV heads, lanes = head-major [KH·hd]) was
        # DMA'd from pool row tbl[b, si] by the index map
        for n in range(kv_heads):
            _online_softmax_step(
                q_ref[0, n], k_ref[0, :, n * hd:(n + 1) * hd],
                v_ref[0, :, n * dv:(n + 1) * dv], s_start, length,
                m_scr.at[n], l_scr.at[n], acc_scr.at[n], scale=scale,
                ks=None if ks_ref is None else ks_ref[0, :, n:n + 1],
                vs=None if vs_ref is None else vs_ref[0, :, n:n + 1])

    @pl.when(si == n_s - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           lengths: jax.Array, *,
                           max_len: Optional[int] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: bool) -> jax.Array:
    """Flash-decode over a block-pool cache.

    q: [B, H, hd]; pools: [N, block_size, KH, hd]; block_table:
    [B, max_blocks_per_seq] int32 pool-block ids (entries past a
    sequence's allocation may be anything — they are never read past
    ``lengths``); lengths: [B] valid tokens.  ``max_len`` (static)
    truncates the block sweep to ceil(max_len / block_size) blocks.
    Returns [B, H, hd].

    The table and lengths are scalar-prefetch operands: the k/v BlockSpec
    index maps dereference ``tbl[b, si]`` to pick the DMA source block, so
    the kernel streams exactly the blocks the table names — the paged
    gather is free.  Each grid step takes one whole pool block, all KV
    heads at once, viewed as [block_size, KH·hd]: the block's last two
    dims then span the array's, which the TPU's tiling rules require for
    any KH.  int8 pools pass ``k_scale``/``v_scale`` [N, block_size, KH,
    1] scale pools, whose blocks ride the same table-driven index maps;
    dequant is fused into the softmax loop.
    """
    N, bs, KH, hd = k_pool.shape
    B, H = q.shape[:2]
    dv = v_pool.shape[-1]
    G = H // KH
    nmax = block_table.shape[1]
    n_s = nmax
    if max_len is not None:
        n_s = max(1, min(nmax, -(-max_len // bs)))

    def kv_spec(width):
        return pl.BlockSpec((1, bs, width),
                            lambda b, s, tbl, lens: (tbl[b, s], 0, 0))

    in_specs = [
        pl.BlockSpec((1, KH, G, hd), lambda b, s, tbl, lens: (b, 0, 0, 0)),
        kv_spec(KH * hd),
        kv_spec(KH * dv),
    ]
    inputs = [q.reshape(B, KH, G, hd), k_pool.reshape(N, bs, KH * hd),
              v_pool.reshape(N, bs, KH * dv)]
    if k_scale is not None:
        in_specs += [kv_spec(KH)] * 2
        inputs += [k_scale.reshape(N, bs, KH).astype(jnp.float32),
                   v_scale.reshape(N, bs, KH).astype(jnp.float32)]

    kernel = functools.partial(_paged_kernel, scale=hd ** -0.5,
                               block_s=bs, n_s=n_s, kv_heads=KH, hd=hd,
                               dv=dv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, G, dv),
                               lambda b, s, tbl, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, dv), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *inputs)
    return out.reshape(B, H, dv)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, block_s: int = 512,
                     max_len: Optional[int] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     interpret: bool) -> jax.Array:
    """q: [B, H, hd]; caches: [B, S, KH, hd]; lengths: [B] valid rows.
    ``max_len`` (static, host-known upper bound on lengths) truncates the
    sequential sweep to the live prefix of the cache.  int8 caches pass
    ``k_scale``/``v_scale`` [B, S, KH, 1] per-token-per-head scales;
    dequant is fused into the online-softmax loop.  Returns [B, H, hd].

    A contiguous cache is a block pool whose table is the identity: each
    slot's ``S / block_s`` blocks are consecutive pool rows, so the paged
    kernel serves it through a free reshape.
    """
    B, S, KH, hd = k_cache.shape
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"cache len {S} must tile {block_s}")
    n_s = S // block_s

    def pool(x):
        return None if x is None else x.reshape(B * n_s, block_s,
                                                 *x.shape[2:])

    table = jnp.arange(B * n_s, dtype=jnp.int32).reshape(B, n_s)
    return paged_decode_attention(q, pool(k_cache), pool(v_cache), table,
                                  lengths, max_len=max_len,
                                  k_scale=pool(k_scale),
                                  v_scale=pool(v_scale),
                                  interpret=interpret)
