"""GQA attention: chunked (flash-style) prefill/train path + decode path.

Layout conventions
------------------
- activations: x [B, S, d_stream]
- q weights   : [d_stream, H, hd];  k/v: [d_stream, KH, hd];  o: [H, hd, d_stream]
- full cache  : k/v [B, S_max, KH, hd]   (RoPE already applied to k)
- ring cache  : k/v [B, W, KH, hd] for sliding-window layers; slot = pos % W

The train/prefill path unrolls over q chunks in Python (static slice
bounds => causal/windowed block *skipping*, real FLOP savings in the HLO)
and scans over k sub-chunks with an online-softmax carry (bounded memory).
Scores/accumulators are fp32.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.paged import PagedLeaf, is_paged, token_to_pool
from repro.common.quant import dq, quantize_rows
from repro.common.types import LayerSpec, ModelConfig
from repro.models import rope as rope_lib
from repro.models.norms import rmsnorm, rmsnorm_init
from repro.runtime.parallel import Parallelism, NO_PARALLEL

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def attention_init(key, d_stream: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qk_norm: bool = False,
                   dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    s_in = 1.0 / jnp.sqrt(d_stream)
    s_out = 1.0 / jnp.sqrt(n_heads * head_dim)
    p = {
        "wq": (jax.random.normal(kq, (d_stream, n_heads, head_dim), jnp.float32) * s_in).astype(dtype),
        "wk": (jax.random.normal(kk, (d_stream, n_kv_heads, head_dim), jnp.float32) * s_in).astype(dtype),
        "wv": (jax.random.normal(kv, (d_stream, n_kv_heads, head_dim), jnp.float32) * s_in).astype(dtype),
        "wo": (jax.random.normal(ko, (n_heads, head_dim, d_stream), jnp.float32) * s_out).astype(dtype),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim, jnp.float32)
        p["k_norm"] = rmsnorm_init(head_dim, jnp.float32)
    return p


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _softcap(scores: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _rope_tables(spec: LayerSpec, cfg: ModelConfig, positions: jax.Array,
                 head_dim: int):
    """positions: [B, S] (rope) or [3, B, S] (mrope). Returns cos,sin [B,S,hd/2]."""
    if spec.rope == "none":
        return None
    theta = cfg.rope_theta
    if spec.rope == "local_rope":
        theta = cfg.local_rope_theta
    if spec.rope == "mrope":
        if positions.ndim == 2:      # text-only fallback: 3 identical streams
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return rope_lib.mrope_cos_sin(positions, head_dim, theta,
                                      cfg.mrope_sections)
    if positions.ndim == 3:          # mrope-shaped positions on a rope layer
        positions = positions[0]
    return rope_lib.rope_cos_sin(positions, head_dim, theta)


def _expand_kv(k: jax.Array, n_heads: int, par: Parallelism,
               seq_dim: Optional[str] = None) -> jax.Array:
    """[B, S, KH, hd] -> [B, S, H, hd] by static gather (GQA head map)."""
    kh = k.shape[2]
    idx = jnp.arange(n_heads, dtype=jnp.int32) // (n_heads // kh)
    out = jnp.take(k, idx, axis=2)
    return par.cs(out, "batch", seq_dim, "heads", None)


def _project_qkv(params, x, spec: LayerSpec, cfg: ModelConfig,
                 positions, par: Parallelism):
    """Project + qk-norm + rope.  x: [B,S,d] -> q [B,S,H,hd], k/v [B,S,KH,hd]."""
    hd = params["wq"].shape[-1]
    q = jnp.einsum("bsd,dhk->bshk", x, dq(params["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", x, dq(params["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", x, dq(params["wv"]))
    q = par.cs(q, "batch", None, "heads", None)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, eps=cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, eps=cfg.norm_eps)
    tables = _rope_tables(spec, cfg, positions, hd)
    if tables is not None:
        cos, sin = tables
        q = rope_lib.apply_rope(q, cos, sin)
        k = rope_lib.apply_rope(k, cos, sin)
    return q, k, v


# ---------------------------------------------------------------------------
# chunked causal/windowed attention (train + prefill)
# ---------------------------------------------------------------------------

def _chunk_sizes(s_q: int, s_k: int, cfg: ModelConfig) -> Tuple[int, int]:
    cq = cfg.attn_chunk_q if s_q % cfg.attn_chunk_q == 0 else s_q
    ck = cfg.attn_chunk_k if s_k % cfg.attn_chunk_k == 0 else s_k
    return cq, ck


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        q_start: int = 0,
                        chunk_q: int = 512, chunk_k: int = 1024,
                        par: Parallelism = NO_PARALLEL) -> jax.Array:
    """Online-softmax attention with static causal/window block skipping.

    q: [B, Sq, H, hd]; k/v: [B, Sk, H, hd] (kv already expanded to H heads).
    q token i has absolute position q_start + i; k token j has position j.
    Python-unrolled q chunks => per-chunk static k ranges (block skipping).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    dv = v.shape[-1]                                 # may differ (MLA)
    scale = hd ** -0.5
    cq = chunk_q if Sq % chunk_q == 0 else Sq
    ck = chunk_k if Sk % chunk_k == 0 else Sk
    nq = Sq // cq
    out_chunks = []
    for i in range(nq):
        q_lo = q_start + i * cq                      # abs pos of first q row
        q_hi = q_start + (i + 1) * cq - 1            # abs pos of last q row
        # static k range for this q chunk
        k_hi = min(Sk, q_hi + 1) if causal else Sk
        k_lo = 0
        if window is not None:
            k_lo = max(0, q_lo - window + 1)
        # round to ck multiples (static)
        k_lo = (k_lo // ck) * ck
        k_hi = min(Sk, ((k_hi + ck - 1) // ck) * ck)
        if k_hi <= k_lo:
            out_chunks.append(jnp.zeros((B, cq, H, dv), q.dtype))
            continue
        qi = q[:, i * cq:(i + 1) * cq].astype(jnp.float32) * scale  # [B,cq,H,hd]
        ks = k[:, k_lo:k_hi]
        vs = v[:, k_lo:k_hi]
        nk = (k_hi - k_lo) // ck
        ks = ks.reshape(B, nk, ck, H, hd)
        vs = vs.reshape(B, nk, ck, H, dv)
        q_pos = q_lo + jnp.arange(cq, dtype=jnp.int32)

        def body(carry, inputs):
            m, l, acc = carry
            j, k_c, v_c = inputs
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, k_c.astype(jnp.float32))
            s = _softcap(s, softcap)
            k_pos = k_lo + j * ck + jnp.arange(ck, dtype=jnp.int32)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_c.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, dv), jnp.float32)
        if nk == 1:
            (m, l, acc), _ = body((m0, l0, a0),
                                  (jnp.int32(0), ks[:, 0], vs[:, 0]))
        else:
            (m, l, acc), _ = jax.lax.scan(
                body, (m0, l0, a0),
                (jnp.arange(nk, dtype=jnp.int32),
                 jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0)))
        l = jnp.maximum(l, 1e-37)
        o = (acc / l[..., None]).astype(q.dtype)     # [B,H,cq,hd]
        out_chunks.append(jnp.moveaxis(o, 1, 2))     # [B,cq,H,hd]
    out = out_chunks[0] if nq == 1 else jnp.concatenate(out_chunks, axis=1)
    return par.cs(out, "batch", None, "heads", None)


# ---------------------------------------------------------------------------
# public: prefill / train forward
# ---------------------------------------------------------------------------

def attention_apply(params, x: jax.Array, *, spec: LayerSpec,
                    cfg: ModelConfig, positions: jax.Array,
                    par: Parallelism = NO_PARALLEL,
                    return_cache: bool = False,
                    lengths: Optional[jax.Array] = None):
    """Causal self-attention over x: [B, S, d].  Returns (out, cache|None).

    cache (when requested) is (k, v) with RoPE applied; for windowed layers
    it is a ring buffer of size W = spec.window, else [B, S, KH, hd].

    ``lengths`` [B] gives per-row true prompt lengths when x is a
    right-padded (bucketed) prefill batch.  Causality already keeps padded
    K positions out of every real query row, so the attention math needs
    no extra mask — but ring-buffer caches must be built from the *true*
    last-W positions per row, not the padded tail.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, spec, cfg, positions, par)
    H = q.shape[2]
    kf = _expand_kv(k, H, par)
    vf = _expand_kv(v, H, par)
    if cfg.use_pallas and spec.window is None and par.mesh is None:
        from repro.kernels import ops as kops
        ctx = kops.flash_attention(q, kf, vf, causal=spec.causal,
                                   softcap=spec.attn_logit_softcap)
    else:
        ctx = blockwise_attention(
            q, kf, vf, causal=spec.causal, window=spec.window,
            softcap=spec.attn_logit_softcap,
            chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k, par=par)
    out = jnp.einsum("bshk,hkd->bsd", ctx, dq(params["wo"]))
    out = par.cs(out, "batch", "seq", "d_model")
    cache = None
    if return_cache:
        if spec.window is not None and spec.window < S:
            if lengths is None:
                cache = (_to_ring(k, S, spec.window),
                         _to_ring(v, S, spec.window))
            else:
                cache = (_to_ring_per_row(k, lengths, spec.window),
                         _to_ring_per_row(v, lengths, spec.window))
        else:
            cache = (k, v)
    return out, cache


def _to_ring(k: jax.Array, s: int, w: int) -> jax.Array:
    """Keep the last w positions of k [B,S,KH,hd] in ring order (slot=p%w)."""
    j = jnp.arange(w, dtype=jnp.int32)
    src = (s - 1) - ((s - 1 - j) % w)                # latest pos with pos%w==j
    valid = src >= 0
    ring = jnp.take(k, jnp.clip(src, 0, s - 1), axis=1)
    return jnp.where(valid[None, :, None, None], ring, 0)


def _to_ring_per_row(k: jax.Array, lengths: jax.Array, w: int) -> jax.Array:
    """Per-row ring build for right-padded prefill batches.

    Row b's true sequence is k[b, :lengths[b]]; slot j of the ring holds
    the latest real position p <= lengths[b]-1 with p % w == j, so padded
    positions never enter the ring and real in-window positions are never
    evicted by the padding tail."""
    last = lengths.astype(jnp.int32)[:, None] - 1            # [B,1]
    j = jnp.arange(w, dtype=jnp.int32)[None, :]              # [1,w]
    src = last - ((last - j) % w)                            # [B,w]
    valid = src >= 0
    idx = jnp.clip(src, 0, k.shape[1] - 1)[..., None, None]  # [B,w,1,1]
    ring = jnp.take_along_axis(k, idx, axis=1)
    return jnp.where(valid[..., None, None], ring, 0)


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------

def attention_decode(params, x: jax.Array, cache: Tuple[jax.Array, jax.Array],
                     *, spec: LayerSpec, cfg: ModelConfig,
                     pos: jax.Array, par: Parallelism = NO_PARALLEL,
                     block_table: Optional[jax.Array] = None,
                     kv_max_len: Optional[int] = None,
                     active: Optional[jax.Array] = None):
    """x: [B, 1, d]; cache k/v: [B, S_cache, KH, hd] dense, or ``PagedLeaf``
    block pools [N, bs, KH, hd] addressed through ``block_table``; pos: [B]
    int32 (index of the new token).  ``kv_max_len`` (static, host-known
    upper bound on pos+1) lets the paged kernel skip dead blocks.
    Returns (out [B,1,d], updated cache).

    For windowed layers the cache is a ring buffer (S_cache == window) and
    the new k/v is written at slot pos % W; otherwise at slot pos (for a
    paged cache, at the pool row the block table maps pos to).

    ``active`` [B] bool (optional) freezes dense-leaf writes for inactive
    lanes: paged leaves route inactive lanes to the trash block via the
    masked block table, but ring/state leaves are per-slot arrays with no
    trash row, and a slot mid-chunked-prefill must not have its ring
    mutated by decode steps of the surrounding batch.
    """
    B = x.shape[0]
    positions = pos[:, None]                          # [B,1]
    if spec.rope == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    q, k_new, v_new = _project_qkv(params, x, spec, cfg, positions, par)
    q = q[:, 0]                                       # [B,H,hd]
    H = q.shape[1]
    k_cache, v_cache = cache
    if is_paged(k_cache):
        return _paged_decode(params, q, k_new[:, 0], v_new[:, 0],
                             k_cache, v_cache, spec=spec, cfg=cfg, pos=pos,
                             par=par, block_table=block_table,
                             kv_max_len=kv_max_len, out_dtype=x.dtype)
    S_cache = k_cache.shape[1]
    KH = k_cache.shape[2]
    G = H // KH
    ring = spec.window is not None and S_cache <= spec.window
    slot = (pos % S_cache) if ring else pos
    k_cache = _scatter_cache(k_cache, k_new[:, 0], slot, par, active)
    v_cache = _scatter_cache(v_cache, v_new[:, 0], slot, par, active)

    # grouped GQA einsum: the cache is contracted directly per KV head —
    # no G-fold expansion is materialized, and preferred_element_type
    # gives fp32 accumulation without an fp32 copy of the cache.
    scale = q.shape[-1] ** -0.5
    qg = (q * scale).astype(k_cache.dtype).reshape(B, KH, G, -1)
    s = jnp.einsum("bngd,bsnd->bngs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    s = _softcap(s, spec.attn_logit_softcap)
    j = jnp.arange(S_cache, dtype=jnp.int32)
    if ring:
        # absolute position stored in slot j at time `pos`
        p_j = pos[:, None] - ((pos[:, None] - j[None, :]) % S_cache)
        mask = (p_j >= 0) & (p_j >= pos[:, None] - spec.window + 1)
    else:
        mask = j[None, :] <= pos[:, None]
        if spec.window is not None:
            mask &= j[None, :] > pos[:, None] - spec.window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    s = par.cs(s, "batch", None, None, "kv_seq")
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bngs,bsnd->bngd", (p / l).astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    ctx = ctx.reshape(B, H, -1).astype(x.dtype)
    out = jnp.einsum("bhk,hkd->bd", ctx, dq(params["wo"]))[:, None]
    out = par.cs(out, "batch", None, "d_model")
    return out, (k_cache, v_cache)


@jax.named_scope("kv_write")
def _scatter_cache(cache: jax.Array, new: jax.Array, slot: jax.Array,
                   par: Parallelism,
                   active: Optional[jax.Array] = None) -> jax.Array:
    """Write new [B,KH,hd] into cache [B,S,KH,hd] at per-row slot [B].
    Inactive lanes (``active`` false) keep their old row."""
    b = jnp.arange(cache.shape[0])
    new = new.astype(cache.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None], new, cache[b, slot])
    upd = cache.at[b, slot].set(new)
    return par.cs(upd, "batch", "kv_seq", "kv_heads", None)


# ---------------------------------------------------------------------------
# paged decode / chunked prefill (block-pool caches)
# ---------------------------------------------------------------------------

@jax.named_scope("kv_write")
def pool_write(leaf: PagedLeaf, rows: jax.Array,
               w_idx: jax.Array) -> PagedLeaf:
    """Scatter new rows into one pool leaf at flat pool rows ``w_idx``.

    ``rows`` has leading dims matching ``w_idx`` and trailing dims equal
    to ``leaf.pool.shape[2:]`` — [KH, hd] for K/V pools, [rank] for MLA
    latent pools.  An int8 leaf (``scale is not None``) quantizes each
    row over its last axis and scatters payload + scale through the same
    indices.  Layout-polymorphic: any pageable leaf kind goes through
    here."""
    idx = w_idx.reshape(-1)

    def put(pool, r):
        flat = pool.reshape((-1,) + pool.shape[2:])
        flat = flat.at[idx].set(
            r.astype(flat.dtype).reshape((-1,) + pool.shape[2:]))
        return flat.reshape(pool.shape)

    if leaf.scale is not None:
        qr, sr = quantize_rows(rows.astype(jnp.float32))
        return PagedLeaf(put(leaf.pool, qr), put(leaf.scale, sr))
    return PagedLeaf(put(leaf.pool, rows))


def _paged_write(k_leaf: PagedLeaf, v_leaf: PagedLeaf, k_new: jax.Array,
                 v_new: jax.Array, w_idx: jax.Array):
    """Scatter new K/V rows into pool leaves at pool rows ``w_idx``."""
    return pool_write(k_leaf, k_new, w_idx), pool_write(v_leaf, v_new, w_idx)


@jax.named_scope("kv_gather")
def _paged_gather(pool: jax.Array, block_table: jax.Array, bs: int,
                  par: Parallelism) -> jax.Array:
    """Assemble the contiguous per-slot view [B, S_cap, KH, hd] from a
    pool [N, bs, ...] through the block table (the jnp reference path;
    the Pallas kernel streams blocks without materializing this)."""
    flat = pool.reshape((-1,) + pool.shape[2:])
    B, nmax = block_table.shape
    j = jnp.arange(nmax * bs, dtype=jnp.int32)
    idx = token_to_pool(block_table, jnp.broadcast_to(j[None], (B, j.size)),
                        bs)
    return par.cs(flat[idx], "batch", "kv_seq", "kv_heads", None)


@jax.named_scope("kv_gather")
def pool_read(leaf: PagedLeaf, block_table: jax.Array, bs: int) -> jax.Array:
    """Gather the contiguous per-slot view [B, S_cap, ...] of one pool
    leaf through the block table, dequantizing int8 leaves.  Trailing
    dims follow the pool ([KH, hd] for K/V, [rank] for MLA latents)."""
    def gather(pool):
        flat = pool.reshape((-1,) + pool.shape[2:])
        B, nmax = block_table.shape
        j = jnp.arange(nmax * bs, dtype=jnp.int32)
        idx = token_to_pool(block_table,
                            jnp.broadcast_to(j[None], (B, j.size)), bs)
        return flat[idx]

    g = gather(leaf.pool)
    if leaf.scale is not None:
        g = g.astype(jnp.float32) * gather(leaf.scale)
    return g


def _paged_read(k_leaf: PagedLeaf, v_leaf: PagedLeaf,
                block_table: jax.Array, bs: int, par: Parallelism):
    """Gather the per-slot [B, S_cap, KH, hd] views, dequantizing int8
    leaves (payload * per-token scale) to fp32."""
    k_g = _paged_gather(k_leaf.pool, block_table, bs, par)
    v_g = _paged_gather(v_leaf.pool, block_table, bs, par)
    if k_leaf.scale is not None:
        k_g = k_g.astype(jnp.float32) * _paged_gather(
            k_leaf.scale, block_table, bs, par)
        v_g = v_g.astype(jnp.float32) * _paged_gather(
            v_leaf.scale, block_table, bs, par)
    return k_g, v_g


def _paged_decode(params, q, k_new, v_new, k_leaf: PagedLeaf,
                  v_leaf: PagedLeaf, *, spec: LayerSpec, cfg: ModelConfig,
                  pos: jax.Array, par: Parallelism,
                  block_table: jax.Array, kv_max_len: Optional[int],
                  out_dtype):
    """Decode step against block pools.  q: [B,H,hd]; k_new/v_new:
    [B,KH,hd]; pools [N, bs, KH, hd]; block_table [B, max_blocks_per_seq].

    Only full-attention leaves are ever paged (rings stay dense; a
    windowed layer is paged only when its window covers engine capacity,
    where the window mask is vacuous for every reachable position), so the
    causal mask j <= pos is the whole story.  The jnp path gathers the
    same [B, S, KH, hd] view the dense cache stores and runs the identical
    grouped-GQA einsum — bit-for-bit equal to the dense decode path.
    """
    if block_table is None:
        raise ValueError("paged cache leaf but no block_table passed")
    bs = k_leaf.pool.shape[1]
    B, H = q.shape[:2]
    KH = k_leaf.pool.shape[2]
    G = H // KH
    w_idx = token_to_pool(block_table, pos[:, None], bs)[:, 0]
    k_leaf, v_leaf = _paged_write(k_leaf, v_leaf, k_new, v_new, w_idx)
    new_cache = (k_leaf, v_leaf)
    if cfg.use_pallas and par.mesh is None and spec.attn_logit_softcap is None:
        from repro.kernels import ops as kops
        # kv_max_len truncates the block sweep to the live prefix: a
        # short batch never DMAs the dead tail of the pool; int8 pools
        # ship their scale pools for in-kernel dequant
        ctx = kops.paged_decode_attention(
            q, k_leaf.pool, v_leaf.pool,
            block_table, pos + 1, max_len=kv_max_len,
            k_scale=k_leaf.scale, v_scale=v_leaf.scale)
    else:
        k_g, v_g = _paged_read(k_leaf, v_leaf, block_table, bs, par)
        S_cap = k_g.shape[1]
        scale = q.shape[-1] ** -0.5
        qg = (q * scale).astype(k_g.dtype).reshape(B, KH, G, -1)
        s = jnp.einsum("bngd,bsnd->bngs", qg, k_g,
                       preferred_element_type=jnp.float32)
        s = _softcap(s, spec.attn_logit_softcap)
        j = jnp.arange(S_cap, dtype=jnp.int32)
        mask = j[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        s = par.cs(s, "batch", None, None, "kv_seq")
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.einsum("bngs,bsnd->bngd", (p / l).astype(v_g.dtype),
                         v_g, preferred_element_type=jnp.float32)
        ctx = ctx.reshape(B, H, -1)
    ctx = ctx.astype(out_dtype)
    out = jnp.einsum("bhk,hkd->bd", ctx, dq(params["wo"]))[:, None]
    out = par.cs(out, "batch", None, "d_model")
    return out, new_cache


def attention_chunk(params, x: jax.Array, cache, *, spec: LayerSpec,
                    cfg: ModelConfig, pos: jax.Array,
                    par: Parallelism = NO_PARALLEL,
                    block_table: Optional[jax.Array] = None,
                    kv_max_len: Optional[int] = None,
                    slots: Optional[jax.Array] = None,
                    chunk_lens: Optional[jax.Array] = None):
    """Chunked-prefill / multi-token verify step: C new tokens per row.

    x: [B, C, d]; pos: [B] absolute position of each row's first chunk
    token.  Three cache layouts, dispatched structurally:

    * **paged** — cache: (PagedLeaf, PagedLeaf) pools.  Writes the
      chunk's K/V through the block table, then attends every chunk row
      causally against the full paged cache (which now contains the
      chunk itself) — the C=1 decode step generalized to a block of
      queries.  Two callers: chunked prefill (a long prompt fed
      ``prefill_chunk`` tokens at a time between decode steps) and
      speculative verify (K draft tokens + the carry token scored in one
      forward, per-position logits).
    * **ring** (sliding-window) — cache: dense per-slot ring buffers
      [n_slots, W, KH, hd].  The chunk attends to the gathered ring
      content *plus an in-chunk K/V side buffer* (the chunk's own keys),
      so no ring unroll to full length is ever materialized; then the
      last in-window *valid* token per ring slot is written back
      (``chunk_lens`` [B] gives per-row valid token counts so a padded
      final chunk never evicts real window entries).  ``slots`` [B] maps
      chunk rows to engine slots.
    * **dense full** — cache: [B, S_max, KH, hd] rows aligned with x
      (no ``slots``).  Scatters the chunk at its absolute positions and
      attends causally — the multi-token append path that fills the
      speculative drafter's dense cache chunk-by-chunk.

    ``kv_max_len`` (static, host-known bound on pos + C) truncates the
    paged gather to the live prefix — bitwise-neutral (the dropped
    columns are causally masked, and masked columns contribute exact
    zeros to the online softmax) but skips dead-block bandwidth.  Writes
    always go through the full table so out-of-range positions land in
    the trash block.

    Rows past a prompt's true length write to already-owned or trash
    blocks (paged) or are dropped (ring/dense), and their key positions
    exceed every real query position, so padding in the final chunk is
    invisible — exactly the bucketed-prefill argument.
    """
    B, C, _ = x.shape
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # [B,C]
    rope_positions = positions
    if spec.rope == "mrope":
        rope_positions = jnp.broadcast_to(positions[None], (3, B, C))
    q, k_new, v_new = _project_qkv(params, x, spec, cfg, rope_positions, par)
    H = q.shape[2]
    k_cache, v_cache = cache
    if not is_paged(k_cache):
        ring = spec.window is not None and k_cache.shape[1] <= spec.window
        f = _ring_chunk if ring else _dense_chunk
        ctx, new_cache = f(q, k_new, v_new, k_cache, v_cache, spec=spec,
                           pos=pos, positions=positions, slots=slots,
                           chunk_lens=chunk_lens)
        out = jnp.einsum("bchk,hkd->bcd", ctx.astype(x.dtype),
                         dq(params["wo"]))
        return par.cs(out, "batch", None, "d_model"), new_cache
    if block_table is None:
        raise ValueError("attention_chunk on a paged cache requires a "
                         "block_table")
    k_leaf, v_leaf = k_cache, v_cache
    bs = k_leaf.pool.shape[1]
    KH = k_leaf.pool.shape[2]
    G = H // KH
    w_idx = token_to_pool(block_table, positions, bs)            # [B,C]
    k_leaf, v_leaf = _paged_write(k_leaf, v_leaf, k_new, v_new, w_idx)
    new_cache = (k_leaf, v_leaf)
    read_table = block_table
    if kv_max_len is not None:
        read_table = block_table[:, :-(-kv_max_len // bs)]
    k_g, v_g = _paged_read(k_leaf, v_leaf, read_table, bs, par)
    S_cap = k_g.shape[1]
    scale = q.shape[-1] ** -0.5
    qg = (q * scale).astype(k_g.dtype).reshape(B, C, KH, G, -1)
    s = jnp.einsum("bcngd,bsnd->bcngs", qg, k_g,
                   preferred_element_type=jnp.float32)
    s = _softcap(s, spec.attn_logit_softcap)
    j = jnp.arange(S_cap, dtype=jnp.int32)
    mask = j[None, None, :] <= positions[:, :, None]             # [B,C,S]
    if spec.window is not None:
        mask &= j[None, None, :] > positions[:, :, None] - spec.window
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    s = par.cs(s, "batch", None, None, None, "kv_seq")
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bcngs,bsnd->bcngd", (p / l).astype(v_g.dtype),
                     v_g, preferred_element_type=jnp.float32)
    ctx = ctx.reshape(B, C, H, -1).astype(x.dtype)
    out = jnp.einsum("bchk,hkd->bcd", ctx, dq(params["wo"]))
    out = par.cs(out, "batch", None, "d_model")
    return out, new_cache


def _grouped_softmax_ctx(q, k_src, v_src, mask, softcap):
    """Masked grouped-GQA attention for side-buffer chunk paths.
    q: [B,C,H,hd]; k_src/v_src: [B,S,KH,hd]; mask: [B,C,S].
    Returns ctx [B,C,H,dv] fp32."""
    B, C, H, hd = q.shape
    KH = k_src.shape[2]
    G = H // KH
    scale = hd ** -0.5
    qg = (q * scale).astype(jnp.float32).reshape(B, C, KH, G, hd)
    s = jnp.einsum("bcngd,bsnd->bcngs", qg, k_src.astype(jnp.float32))
    s = _softcap(s, softcap)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bcngs,bsnd->bcngd", p / l,
                     v_src.astype(jnp.float32))
    return ctx.reshape(B, C, H, -1)


def _ring_chunk(q, k_new, v_new, k_cache, v_cache, *, spec, pos, positions,
                slots, chunk_lens):
    """Chunked append against a sliding-window ring buffer.

    The chunk's queries attend to (gathered ring content ⊕ the chunk's
    own K/V as an in-chunk side buffer); afterwards, for each ring slot
    j, the latest *valid* chunk token with position % W == j replaces
    the old entry.  Padded tail tokens (index >= chunk_lens[b]) are
    causally invisible to real queries and never written."""
    if chunk_lens is None:
        chunk_lens = jnp.full(pos.shape, positions.shape[1], jnp.int32)
    k_rows = k_cache if slots is None else k_cache[slots]    # [B,W,KH,hd]
    v_rows = v_cache if slots is None else v_cache[slots]
    B, C = positions.shape
    W = k_rows.shape[1]
    j = jnp.arange(W, dtype=jnp.int32)[None, :]
    # absolute position held by ring slot j before this chunk
    last_old = pos[:, None] - 1                              # [B,1]
    p_j = last_old - ((last_old - j) % W)                    # [B,W]
    src_pos = jnp.concatenate([p_j, positions], axis=1)      # [B,W+C]
    src_ok = jnp.concatenate(
        [p_j >= 0, jnp.ones((B, C), bool)], axis=1)
    k_src = jnp.concatenate([k_rows, k_new.astype(k_rows.dtype)], axis=1)
    v_src = jnp.concatenate([v_rows, v_new.astype(v_rows.dtype)], axis=1)
    mask = (src_ok[:, None, :]
            & (src_pos[:, None, :] <= positions[:, :, None])
            & (src_pos[:, None, :] > positions[:, :, None] - spec.window))
    ctx = _grouped_softmax_ctx(q, k_src, v_src, mask,
                               spec.attn_logit_softcap)
    # --- write back: latest valid position per ring slot
    last = pos[:, None] + chunk_lens[:, None] - 1            # [B,1]
    q_new = last - ((last - j) % W)                          # [B,W]
    from_chunk = q_new >= pos[:, None]
    idx = jnp.clip(q_new - pos[:, None], 0, C - 1)[..., None, None]
    k_upd = jnp.take_along_axis(k_new.astype(k_rows.dtype), idx, axis=1)
    v_upd = jnp.take_along_axis(v_new.astype(v_rows.dtype), idx, axis=1)
    sel = from_chunk[..., None, None]
    k_rows = jnp.where(sel, k_upd, k_rows)
    v_rows = jnp.where(sel, v_upd, v_rows)
    if slots is None:
        return ctx, (k_rows, v_rows)
    return ctx, (k_cache.at[slots].set(k_rows),
                 v_cache.at[slots].set(v_rows))


def _dense_chunk(q, k_new, v_new, k_cache, v_cache, *, spec, pos, positions,
                 slots, chunk_lens):
    """Multi-token append against a dense full-attention cache whose rows
    align with the chunk batch (the speculative drafter's cache, gathered
    per request).  Out-of-range padded positions are dropped."""
    del chunk_lens, slots             # rows are pre-gathered by the caller
    B, C = positions.shape
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    k_cache = k_cache.at[bidx, positions].set(
        k_new.astype(k_cache.dtype), mode="drop")
    v_cache = v_cache.at[bidx, positions].set(
        v_new.astype(v_cache.dtype), mode="drop")
    S = k_cache.shape[1]
    jj = jnp.arange(S, dtype=jnp.int32)
    mask = jj[None, None, :] <= positions[:, :, None]
    if spec.window is not None:
        mask &= jj[None, None, :] > positions[:, :, None] - spec.window
    ctx = _grouped_softmax_ctx(q, k_cache, v_cache, mask,
                               spec.attn_logit_softcap)
    return ctx, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attention_apply(params, x: jax.Array, enc_kv, *,
                          cfg: ModelConfig, par: Parallelism = NO_PARALLEL):
    """x: [B, S, d]; enc_kv = (k, v) [B, S_enc, KH, hd] precomputed from the
    encoder (no causal mask, no rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q = par.cs(q, "batch", None, "heads", None)
    k, v = enc_kv
    H = q.shape[2]
    kf = _expand_kv(k, H, par)
    vf = _expand_kv(v, H, par)
    ctx = blockwise_attention(q, kf, vf, causal=False, window=None,
                              softcap=None, chunk_q=cfg.attn_chunk_q,
                              chunk_k=cfg.attn_chunk_k, par=par)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])
    return par.cs(out, "batch", None, "d_model")


def cross_kv(params, enc_states: jax.Array, par: Parallelism = NO_PARALLEL):
    """Project encoder states once: [B, S_enc, d] -> (k, v)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_states, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_states, params["wv"])
    return k, v
