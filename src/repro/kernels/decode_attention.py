"""Flash-decode as a Pallas TPU kernel: one query token per sequence
against a long KV cache, GQA-aware (KV read once per KV head, applied to
all G query heads in the group).

Two layouts, one kernel:

  paged_decode_attention  — block-pool cache [N, bs, KH, hd] indexed
      through a per-sequence block table (vLLM-style).
  decode_attention        — contiguous per-slot cache [B, S, KH, hd],
      served as a pool with the identity table.

The kernel streams only live KV.  The pools stay in HBM; the block
table, the per-slot valid lengths and the next live slot ride in as
*scalar-prefetch* operands, and the kernel issues its own double-
buffered page copies from them.  The grid has one step per slot; inside
it a loop walks the slot's *compute blocks* of ``pages`` pool blocks
(about 256 tokens), so a step moves a few hundred KiB, not one 16-token
tile.  Each iteration starts the copies of the next compute block (this
slot's, else the first of the next live slot) before it waits for its
own.  A compute block past a slot's length is never copied nor computed,
and a slot of length 0 reads nothing: one compiled program serves every
live length.  In a slot's last compute block, a page past its length,
whose table entry may be anything, copies the pool's block 0 instead.

Under ``jax.vmap`` (PT's tracks, which share the block table and the
lengths) the mapped axis folds into the KV-head axis: 8 tracks of one
KV head present the same work as one stack of 8 KV heads, and each page
copy moves the page of every track at once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
BLOCK_COLS = 2048           # key rows (tokens x heads) per compute block
MAX_PAGES = 16              # pool blocks per compute block


def _kernel(tbl_ref, len_ref, nxt_ref, q_ref, row_head, col_head, col_tok,
            k_hbm, v_hbm, *rest, scale: float, bs: int, pages: int,
            nmax: int):
    """Grid step ``b``: every compute block of slot ``b``.

    q_ref [T·H, hd] (the slot's queries, track-major); pools [T, N,
    bs·KH, hd] in HBM, one row per (token, KV head); buffers [2, T,
    pages, bs·KH, hd] in VMEM.  A compute block is scored in one matmul
    of every query row against every key row of the block, and the
    masks keep each query's own KV head (``row_head`` == ``col_head``)
    and the tokens before the slot's length (``col_tok``: a column's
    token within the block).  int8 pools add scale pools and buffers of
    one lane.  ``slot_ref`` holds the buffer the next block lands in,
    carried from grid step to grid step."""
    if len(rest) == 12:         # int8 pools: scale pools ride along
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         slot_ref, m_scr, l_scr, acc_scr) = rest
        srcs = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                (vs_hbm, vs_buf))
    else:
        o_ref, k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr = rest
        ks_buf = vs_buf = None
        srcs = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    cs = pages * bs
    length = len_ref[b]

    def copy_pages(row, blk, slot, op):
        """Apply ``op`` ("start" or "wait") to the copy of every page of
        compute block ``blk`` of slot ``row`` into buffer ``slot``.  A
        page past the row's length, whose table entry may be anything,
        copies the pool's block 0 in its place (the engine's trash
        block): finite rows, which the masks drop."""
        n = len_ref[row]

        def page(p, carry):
            at = blk * pages + p
            idx = jnp.where(at * bs < n,
                            tbl_ref[row * nmax + jnp.minimum(at, nmax - 1)],
                            0)
            for k, (hbm, buf) in enumerate(srcs):
                getattr(pltpu.make_async_copy(
                    hbm.at[:, idx], buf.at[slot, :, p], sems.at[k, slot]),
                    op)()
            return carry

        jax.lax.fori_loop(0, pages, page, 0)

    @pl.when(b == nxt_ref[0])       # the first live slot: no earlier
    def _first():                   # block prefetched its first one
        slot_ref[0] = 0
        copy_pages(b, 0, 0, "start")

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[...].astype(jnp.float32) * scale

    def load(buf, slot):
        x = buf[slot]                                     # [T, pages, r, w]
        return x.reshape(-1, x.shape[-1])

    def body(i, carry):
        slot = slot_ref[0]
        last = i + 1 >= (length + cs - 1) // cs
        row = jnp.where(last, nxt_ref[b + 1], b)
        blk = jnp.where(last, 0, i + 1)

        @pl.when(row < pl.num_programs(0))
        def _prefetch():
            copy_pages(row, blk, 1 - slot, "start")

        copy_pages(b, i, slot, "wait")
        k, v = load(k_buf, slot), load(v_buf, slot)
        if ks_buf is None:
            qk = q.astype(k.dtype)
        else:
            qk = q
            k = k.astype(jnp.float32) * load(ks_buf, slot)
            v = v.astype(jnp.float32) * load(vs_buf, slot)
        s = jax.lax.dot_general(qk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = ((row_head[...] == col_head[...])          # [T·H, cols]
                & (i * cs + col_tok[...] < length))
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, (length + cs - 1) // cs, body, 0)
    l = jnp.maximum(l_scr[...], 1e-37)
    o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_flash(q, k_pool, v_pool, block_table, lengths, *scales,
                 interpret: bool):
    """q [T, B, H, hd]; pools [T, N, bs, KH, hd]; scales [T, N, bs, KH, 1]
    or none.  T groups of KV heads (PT's tracks, or 1) share the table."""
    T, N, bs, KH, hd = k_pool.shape
    B, H = q.shape[1:3]
    dv = v_pool.shape[-1]
    G = H // KH
    nmax = block_table.shape[1]
    pages = max(1, min(nmax, MAX_PAGES, BLOCK_COLS // (T * KH * bs)))
    r = bs * KH                     # key rows a page holds per group
    lengths = lengths.astype(jnp.int32)
    # nxt[b]: the first slot >= b with live KV (B if none)
    idx = jnp.where(lengths > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jax.lax.cummin(jnp.append(idx, B), reverse=True)
    # key row j of a compute block: group j // (pages·r), token
    # (j % (pages·r)) // KH of the block, KV head j % KH
    j = np.arange(T * pages * r)
    col_head = (j // (pages * r)) * KH + j % KH
    col_tok = (j % (pages * r)) // KH
    row_head = np.arange(T * H) // G

    def pool(x):
        return x.reshape(T, N, r, -1)

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    inputs = [jnp.swapaxes(q, 0, 1).reshape(B, T * H, hd),
              jnp.asarray(row_head[:, None], jnp.int32),
              jnp.asarray(col_head[None], jnp.int32),
              jnp.asarray(col_tok[None], jnp.int32),
              pool(k_pool), pool(v_pool)]
    in_specs = [pl.BlockSpec((None, T * H, hd), lambda b, *_: (b, 0, 0)),
                whole((T * H, 1)), whole((1, j.size)), whole((1, j.size)),
                hbm, hbm]
    bufs = [pltpu.VMEM((2, T, pages, r, hd), k_pool.dtype),
            pltpu.VMEM((2, T, pages, r, dv), v_pool.dtype)]
    if scales:
        inputs += [pool(s.astype(jnp.float32)) for s in scales]
        in_specs += [hbm, hbm]
        bufs += [pltpu.VMEM((2, T, pages, r, 1), jnp.float32)] * 2
    kernel = functools.partial(_kernel, scale=hd ** -0.5, bs=bs,
                               pages=pages, nmax=nmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, T * H, dv), lambda b, *_: (b, 0, 0)),
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((len(bufs), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((T * H, 1), jnp.float32),
            pltpu.VMEM((T * H, 1), jnp.float32),
            pltpu.VMEM((T * H, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T * H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table.astype(jnp.int32).reshape(-1), lengths, nxt, *inputs)
    return jnp.swapaxes(out.reshape(B, T, H, dv), 0, 1)


@functools.lru_cache(maxsize=None)
def _grouped(interpret: bool):
    """``_paged_flash`` with a batching rule that folds a mapped axis of
    q and the pools into T, when the table and lengths are shared."""

    @jax.custom_batching.custom_vmap
    def call(q, k_pool, v_pool, block_table, lengths, *scales):
        return _paged_flash(q, k_pool, v_pool, block_table, lengths,
                            *scales, interpret=interpret)

    @call.def_vmap
    def fold(axis_size, in_batched, q, k_pool, v_pool, block_table,
             lengths, *scales):
        if in_batched[3] or in_batched[4]:
            raise NotImplementedError(
                "paged_decode_attention under vmap needs one block table "
                "and one set of lengths for every mapped row")

        def merge(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((axis_size * x.shape[1],) + x.shape[2:])

        folded = [merge(x, bt) for x, bt in
                  zip((q, k_pool, v_pool, *scales),
                      (*in_batched[:3], *in_batched[5:]))]
        out = call(*folded[:3], block_table, lengths, *folded[3:])
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           lengths: jax.Array, *,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: bool) -> jax.Array:
    """Flash-decode over a block-pool cache.

    q: [B, H, hd]; pools: [N, block_size, KH, hd]; block_table:
    [B, max_blocks_per_seq] int32 pool-block ids (entries past a
    sequence's length may be anything: they are never read); lengths:
    [B] valid tokens, 0 for a lane that reads nothing (its output is 0).
    int8 pools pass ``k_scale``/``v_scale`` [N, block_size, KH, 1] scale
    pools, copied beside their pages; dequant is fused into the softmax
    loop.  Accumulation is float32.  Returns [B, H, hd].

    Under ``jax.vmap`` over q and the pools, with the table and lengths
    shared, the mapped axis becomes more KV heads of the same call.
    """
    scales = () if k_scale is None else (k_scale[None], v_scale[None])
    out = _grouped(interpret)(q[None], k_pool[None], v_pool[None],
                              block_table, lengths, *scales)
    return out[0]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, block_s: int = 512,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     interpret: bool) -> jax.Array:
    """q: [B, H, hd]; caches: [B, S, KH, hd]; lengths: [B] valid rows.
    int8 caches pass ``k_scale``/``v_scale`` [B, S, KH, 1] per-token-per-
    head scales; dequant is fused into the online-softmax loop.  Returns
    [B, H, hd].

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
                                  lengths, k_scale=pool(k_scale),
                                  v_scale=pool(v_scale),
                                  interpret=interpret)
