"""Parallel Track (PT) Transformer — the paper's contribution (Algorithm 1).

A PT model is `n_tracks` independent transformers ("tracks") of width
``cfg.d_model`` (the *per-track* width).  All tracks consume the same
embedded input; after every ``D = cfg.pt.block_depth`` layers the tracks'
hidden states are fused with an all-reduce (mean by default) and every
track continues from the fused state.  Sync points per forward pass drop
from 2·L (Megatron TP) to L/D — e.g. 16× fewer at D=8.

Mapping to the TPU mesh: the stacked track axis of every activation and
parameter is sharded over the mesh axis 'track'; fusion (mean over the
track axis) lowers to exactly ONE all-reduce over 'track' per track-block.
Optionally a 'tp' mesh axis provides Megatron TP *within* each track
(heads/d_ff sharded over 'tp') — the paper's own deployment is one track
per device (no inner TP), which corresponds to a mesh without a 'tp' axis.

The scan unit is one track block (D layers + 1 fusion), so the compiled
HLO while-body contains exactly one cross-track all-reduce — the paper's
sync-count claim is directly visible in (and verified from) the HLO.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import ModelConfig, PTConfig
from repro.models import rope as rope_lib
from repro.models.decoder import _embed, _head, _remat, model_dtype
from repro.models.layers import layer_apply, layer_cache_shape, layer_init
from repro.runtime.parallel import Parallelism, NO_PARALLEL


# ---------------------------------------------------------------------------
# sync-point accounting (the paper's §2.2 claim)
# ---------------------------------------------------------------------------

def dense_tp_sync_points(n_layers: int) -> int:
    """Megatron TP: one all-reduce after attention + one after FFN."""
    return 2 * n_layers


def pt_sync_points(n_layers: int, block_depth: int,
                   fuse_final: bool = True) -> int:
    n = n_layers // block_depth
    if n_layers % block_depth and fuse_final:
        n += 1
    return n


def sync_reduction(n_layers: int, block_depth: int) -> float:
    """2L / (L/D) = 2D — '16x at D=8'."""
    return dense_tp_sync_points(n_layers) / pt_sync_points(n_layers,
                                                           block_depth)


def sync_bytes_per_point(batch: int, seq: int, width: int,
                         bytes_per_el: int = 2) -> int:
    return batch * seq * width * bytes_per_el


# ---------------------------------------------------------------------------
# PT-ification of a dense decoder config
# ---------------------------------------------------------------------------

def _round_mult(x: float, m: int) -> int:
    return max(m, int(round(x / m)) * m)


def pt_ify(cfg: ModelConfig, n_tracks: int, block_depth: int,
           fusion_op: str = "mean", width_mult: int = 128) -> ModelConfig:
    """Build a track-parallel variant of a decoder-only config.

    Per-track width is d/√n (total params ≈ preserved: n·d_t² = d²);
    heads and KV heads are divided across tracks (Table 1's recipe);
    d_ff is scaled to preserve total FFN params.  For MoE configs the
    experts are divided across tracks (PT-MoE: sparsity within tracks).
    """
    if cfg.encdec is not None:
        raise ValueError("PT is defined for decoder-only models")
    d_t = _round_mult(cfg.d_model / math.sqrt(n_tracks), width_mult)
    heads_t = max(1, cfg.n_heads // n_tracks)
    kv_t = max(1, cfg.n_kv_heads // n_tracks)
    d_ff_t = _round_mult(cfg.d_model * cfg.d_ff / (n_tracks * d_t),
                         width_mult) if cfg.d_ff else 0
    kw: Dict[str, Any] = dict(
        name=f"{cfg.name}-pt{n_tracks}d{block_depth}",
        family="pt",
        d_model=d_t, n_heads=heads_t, n_kv_heads=kv_t, d_ff=d_ff_t,
        head_dim=cfg.head_dim,
        pt=PTConfig(n_tracks=n_tracks, block_depth=block_depth,
                    fusion_op=fusion_op),
    )
    if cfg.moe is not None:
        import dataclasses
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            n_routed_experts=max(cfg.moe.top_k, cfg.moe.n_routed_experts // n_tracks))
    if cfg.ssm is not None:
        import dataclasses
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, d_inner=_round_mult(cfg.ssm.d_inner / math.sqrt(n_tracks),
                                         width_mult))
    if cfg.rglru is not None:
        import dataclasses
        kw["rglru"] = dataclasses.replace(
            cfg.rglru, d_inner=_round_mult(cfg.rglru.d_inner / math.sqrt(n_tracks),
                                           width_mult))
    return cfg.replace(**kw)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _pt(cfg: ModelConfig) -> PTConfig:
    if cfg.pt is None:
        raise ValueError(f"{cfg.name} has no PT config")
    return cfg.pt


def _block_counts(cfg: ModelConfig) -> Tuple[int, int]:
    D = _pt(cfg).block_depth
    return cfg.n_layers // D, cfg.n_layers % D


def init_pt(key, cfg: ModelConfig):
    """Params: embed [V, d_t] (shared); blocks leaves [R, D, n_tracks, ...];
    tail leaves [rem, n_tracks, ...]; shared final_norm (+head)."""
    pt = _pt(cfg)
    if len(cfg.pattern_unit) != 1 or cfg.pattern_prefix or cfg.pattern_suffix:
        raise ValueError("PT models use a uniform layer pattern")
    spec = cfg.spec(cfg.pattern_unit[0])
    dtype = model_dtype(cfg)
    d = cfg.d_model
    R, rem = _block_counts(cfg)
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)

    def track_init(k):
        return layer_init(k, cfg, spec, d, dtype)

    def stacked(k, *ns):
        keys = jax.random.split(k, math.prod(ns))
        keys = keys.reshape(ns + keys.shape[1:])
        f = track_init
        for _ in ns:
            f = jax.vmap(f)
        return f(keys)

    params: Dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, d), jnp.float32)
                  * scale).astype(dtype),
        "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
        "blocks": stacked(ks[1], R, pt.block_depth, pt.n_tracks) if R else (),
        "tail": stacked(ks[2], rem, pt.n_tracks) if rem else (),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(ks[3], (d, cfg.vocab_size),
                                            jnp.float32) * scale).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# fusion + track-vmapped layer
# ---------------------------------------------------------------------------

@jax.named_scope("track_fuse")
def _fuse(h: jax.Array, cfg: ModelConfig, par: Parallelism) -> jax.Array:
    """All-reduce across tracks: h [n, B, S, d] -> fused [B, S, d].

    This is THE sync point: with the track dim sharded over the 'track'
    mesh axis the mean lowers to exactly one all-reduce.  The fused value
    is carried (not the broadcast), so a track block costs exactly one
    collective — re-broadcasting to the tracks at block entry is
    communication-free (replicate)."""
    pt = _pt(cfg)
    if pt.fusion_op == "mean":
        f = jnp.mean(h, axis=0)
    elif pt.fusion_op == "sum":
        f = jnp.sum(h, axis=0)
    else:
        raise ValueError(pt.fusion_op)
    return par.cs(f, "batch", None, None)


def _spread(x: jax.Array, cfg: ModelConfig, par: Parallelism) -> jax.Array:
    """Broadcast fused [B, S, d] back to all tracks [n, B, S, d] (free)."""
    pt = _pt(cfg)
    h = jnp.broadcast_to(x[None], (pt.n_tracks,) + x.shape)
    return par.cs(h, "track", "batch", None, None)


def _track_layers(params_block, h, *, cfg, spec, mode, positions, pos,
                  caches, par, lengths=None, block_table=None,
                  kv_max_len=None, slots=None, chunk_lens=None, active=None):
    """Apply one layer per track (vmapped).  params leaves [n, ...];
    h [n, B, S, d]; caches leaves [n, ...] or None.  ``block_table``
    (and the serving extras ``slots``/``chunk_lens``/``active``) are
    closure-captured, i.e. shared (broadcast) across tracks."""
    def one(p, x, c):
        return layer_apply(p, x, cfg=cfg, spec=spec, mode=mode,
                           positions=positions, pos=pos, cache=c, par=par,
                           lengths=lengths, block_table=block_table,
                           kv_max_len=kv_max_len, slots=slots,
                           chunk_lens=chunk_lens, active=active)

    if caches is None:
        out, cache, aux = jax.vmap(lambda p, x: one(p, x, None))(
            params_block, h)
    else:
        out, cache, aux = jax.vmap(one)(params_block, h, caches)
    out = par.cs(out, "track", "batch", None, None)
    return out, cache, jnp.mean(aux)


# ---------------------------------------------------------------------------
# forward / decode
# ---------------------------------------------------------------------------

def pt_forward(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
               par: Parallelism = NO_PARALLEL, mode: str = "train"):
    pt = _pt(cfg)
    spec = cfg.spec(cfg.pattern_unit[0])
    inputs = batch["inputs"]
    B, S = inputs.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = rope_lib.positions_default(B, S)
    lengths = batch.get("lengths") if mode == "prefill" else None
    x = _embed(params, inputs, cfg, positions, par)          # [B,S,d_t]
    want_cache = mode == "prefill"
    R, rem = _block_counts(cfg)

    block_caches = ()
    aux_total = jnp.zeros((), jnp.float32)
    h = x                                                     # fused carry
    if R:
        def body(carry, pblock):                              # pblock [D,n,...]
            hf, auxc = carry
            hh = _spread(hf, cfg, par)                        # free
            cs = []
            for j in range(pt.block_depth):
                pj = jax.tree_util.tree_map(lambda l: l[j], pblock)
                hh, c, aux = _track_layers(pj, hh, cfg=cfg, spec=spec,
                                           mode=mode, positions=positions,
                                           pos=None, caches=None, par=par,
                                           lengths=lengths)
                auxc = auxc + aux
                cs.append(c)
            hf = _fuse(hh, cfg, par)                          # 1 sync / block
            if want_cache:
                stacked = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls), *cs)
                return (hf, auxc), stacked
            return (hf, auxc), None

        body = _remat(body, cfg) if mode == "train" else body
        (h, aux_total), block_caches = jax.lax.scan(
            body, (h, aux_total), params["blocks"])

    tail_caches = []
    if rem:
        ht = _spread(h, cfg, par)
        for i in range(rem):
            pi = jax.tree_util.tree_map(lambda l: l[i], params["tail"])
            ht, c, aux = _track_layers(pi, ht, cfg=cfg, spec=spec, mode=mode,
                                       positions=positions, pos=None,
                                       caches=None, par=par, lengths=lengths)
            aux_total += aux
            tail_caches.append(c)
        h = _fuse(ht, cfg, par) if pt.fuse_final else jnp.mean(ht, axis=0)

    logits = _head(params, h, cfg, par)
    if mode == "train":
        return logits, aux_total
    cache = {"blocks": block_caches, "tail": tuple(tail_caches)}
    return logits, cache, aux_total


def _pt_step(params, cache, x, pos, cfg: ModelConfig, par: Parallelism,
             mode: str, block_table, kv_max_len=None, slots=None,
             chunk_lens=None, active=None):
    """Shared decode/chunk drive: track-block scan + ragged tail."""
    pt = _pt(cfg)
    spec = cfg.spec(cfg.pattern_unit[0])
    R, rem = _block_counts(cfg)

    new_blocks = cache["blocks"]
    h = x                                                     # fused carry
    if R:
        def body(hf, xs):
            pblock, cblock = xs                               # [D,n,...]
            hh = _spread(hf, cfg, par)
            cs = []
            for j in range(pt.block_depth):
                pj = jax.tree_util.tree_map(lambda l: l[j], pblock)
                cj = jax.tree_util.tree_map(lambda l: l[j], cblock)
                hh, c, _ = _track_layers(pj, hh, cfg=cfg, spec=spec,
                                         mode=mode, positions=None,
                                         pos=pos, caches=cj, par=par,
                                         block_table=block_table,
                                         kv_max_len=kv_max_len, slots=slots,
                                         chunk_lens=chunk_lens,
                                         active=active)
                cs.append(c)
            hf = _fuse(hh, cfg, par)
            return hf, jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *cs)

        h, new_blocks = jax.lax.scan(body, h, (params["blocks"],
                                               cache["blocks"]))

    new_tail = []
    if rem:
        ht = _spread(h, cfg, par)
        for i in range(rem):
            pi = jax.tree_util.tree_map(lambda l: l[i], params["tail"])
            ci = cache["tail"][i]
            ht, c, _ = _track_layers(pi, ht, cfg=cfg, spec=spec,
                                     mode=mode, positions=None,
                                     pos=pos, caches=ci, par=par,
                                     block_table=block_table,
                                     kv_max_len=kv_max_len, slots=slots,
                                     chunk_lens=chunk_lens, active=active)
            new_tail.append(c)
        h = _fuse(ht, cfg, par) if pt.fuse_final else jnp.mean(ht, axis=0)
    return h, {"blocks": new_blocks, "tail": tuple(new_tail)}


def pt_decode_step(params, cache, tokens: jax.Array, pos: jax.Array,
                   cfg: ModelConfig, par: Parallelism = NO_PARALLEL,
                   block_table=None, kv_max_len=None, active=None):
    x = _embed(params, tokens[:, None], cfg, pos[:, None], par)
    h, new_cache = _pt_step(params, cache, x, pos, cfg, par, "decode",
                            block_table, kv_max_len, active=active)
    logits = _head(params, h[:, 0], cfg, par)
    return logits, new_cache


def pt_chunk_step(params, cache, tokens: jax.Array, pos: jax.Array,
                  cfg: ModelConfig, par: Parallelism = NO_PARALLEL,
                  block_table=None, kv_max_len=None, slots=None,
                  chunk_lens=None):
    """Chunked-prefill / K-token verify step: tokens [B, C] appended at
    positions pos[:, None] + arange(C) against the cache.  Returns
    (logits [B, C, V], updated cache).  ``kv_max_len`` (static) bounds
    the paged gather to the live cache prefix — the speculative verify
    path scores K+1 draft tokens per slot in one such forward.  With a
    dense cache (``block_table`` None; rows pre-gathered by the caller)
    the same program fills the track-subset drafter's cache
    chunk-by-chunk."""
    positions = pos[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    x = _embed(params, tokens, cfg, positions, par)
    h, new_cache = _pt_step(params, cache, x, pos, cfg, par, "chunk",
                            block_table, kv_max_len, slots=slots,
                            chunk_lens=chunk_lens)
    logits = _head(params, h, cfg, par)
    return logits, new_cache


# ---------------------------------------------------------------------------
# track-subset drafter (speculative decoding)
# ---------------------------------------------------------------------------

def pt_draft_config(cfg: ModelConfig, draft_tracks: int) -> ModelConfig:
    """Config of the track-subset drafter: the same PT stack restricted
    to its first ``draft_tracks`` tracks.  Per-track widths/heads are
    unchanged — only the fusion mean runs over fewer tracks — so sliced
    parameters drive it directly."""
    import dataclasses
    pt = _pt(cfg)
    if not 1 <= draft_tracks <= pt.n_tracks:
        raise ValueError(f"draft_tracks={draft_tracks} not in "
                         f"[1, {pt.n_tracks}]")
    return cfg.replace(
        name=f"{cfg.name}-draft{draft_tracks}",
        pt=dataclasses.replace(pt, n_tracks=draft_tracks))


def pt_draft_params(params, cfg: ModelConfig, draft_tracks: int):
    """Slice the first ``draft_tracks`` tracks out of stacked PT params.

    blocks leaves [R, D, n, ...] -> [R, D, d, ...]; tail [rem, n, ...]
    -> [rem, d, ...]; embed / final_norm / head are shared as-is.  The
    result is a free-standing narrow model (the drafter): in a deployment
    it is replicated per device, so draft decode costs zero sync points.
    """
    pt = _pt(cfg)
    d = draft_tracks
    if not 1 <= d <= pt.n_tracks:
        raise ValueError(f"draft_tracks={d} not in [1, {pt.n_tracks}]")
    R, rem = _block_counts(cfg)
    out = dict(params)
    if R:
        out["blocks"] = jax.tree_util.tree_map(lambda l: l[:, :, :d],
                                               params["blocks"])
    if rem:
        out["tail"] = jax.tree_util.tree_map(lambda l: l[:, :d],
                                             params["tail"])
    return out


def pt_draft_step(draft_params, cache, tokens: jax.Array, pos: jax.Array,
                  cfg_draft: ModelConfig, par: Parallelism = NO_PARALLEL,
                  active=None):
    """One decode step of the track-subset drafter — ZERO sync points.

    ``cfg_draft`` is ``pt_draft_config(cfg, d)`` and ``draft_params`` the
    matching ``pt_draft_params`` slice.  The 'track' mesh axis is
    stripped from the parallelism rules: the d-track stack is local
    (replicated) on every device, the fusion mean is plain compute, and
    the compiled HLO contains no cross-track all-reduce at all — drafting
    K tokens costs K × (narrow forward) and no communication.
    """
    return pt_decode_step(draft_params, cache, tokens, pos, cfg_draft,
                          par.without_axis("track"), active=active)


def pt_init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    pt = _pt(cfg)
    spec = cfg.spec(cfg.pattern_unit[0])
    dtype = model_dtype(cfg)
    R, rem = _block_counts(cfg)
    one = layer_cache_shape(cfg, spec, batch, seq_len, dtype)

    def stack(tree, *ns):
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros(ns + l.shape, l.dtype), tree)

    return {
        "blocks": stack(one, R, pt.block_depth, pt.n_tracks) if R else (),
        "tail": tuple(stack(one, pt.n_tracks) for _ in range(rem)),
    }


def pt_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            par: Parallelism = NO_PARALLEL):
    logits, aux = pt_forward(params, batch, cfg, par, mode="train")
    targets = batch["targets"]
    mask = (targets >= 0).astype(jnp.float32)
    t = jnp.maximum(targets, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0] - logz
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = -jnp.sum(ll * mask) / denom
    return loss + aux, {"loss": loss, "aux": aux, "tokens": jnp.sum(mask)}
