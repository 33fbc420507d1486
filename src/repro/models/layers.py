"""Transformer-layer assembly: norms + mixer + MLP per LayerSpec.

A single ``layer_apply`` drives every mixer flavour in three modes:
  'train'   — full-sequence forward, no cache
  'prefill' — full-sequence forward, returns the layer cache
  'decode'  — single-token step against the cache
Returns (x, cache, aux_loss).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.norms import apply_norm, norm_init
from repro.runtime.parallel import Parallelism, NO_PARALLEL


def layer_init(key, cfg: ModelConfig, spec: LayerSpec, d_stream: int,
               dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": norm_init(cfg.norm, d_stream)}
    if spec.mixer == "gqa":
        p["mixer"] = attn.attention_init(
            ks[0], d_stream, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qk_norm=cfg.qk_norm, dtype=dtype)
    elif spec.mixer == "mla":
        p["mixer"] = mla_lib.mla_init(ks[0], cfg, d_stream, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = ssm_lib.ssm_init(ks[0], cfg, d_stream, dtype)
    elif spec.mixer == "rglru":
        p["mixer"] = rglru_lib.rglru_init(ks[0], cfg, d_stream, dtype)
    else:
        raise ValueError(f"unknown mixer {spec.mixer!r}")
    if cfg.post_norm:
        p["ln1_post"] = norm_init(cfg.norm, d_stream)
    if spec.cross_attn:
        p["ln_x"] = norm_init(cfg.norm, d_stream)
        p["cross"] = attn.attention_init(
            ks[2], d_stream, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qk_norm=False, dtype=dtype)
    if spec.mlp != "none":
        p["ln2"] = norm_init(cfg.norm, d_stream)
        if spec.mlp == "moe":
            p["mlp"] = moe_lib.moe_init(ks[1], cfg, d_stream, dtype)
        else:
            d_ff = cfg.d_ff
            p["mlp"] = mlp_init(ks[1], spec.mlp, d_stream, d_ff, dtype)
        if cfg.post_norm:
            p["ln2_post"] = norm_init(cfg.norm, d_stream)
    return p


def _norm(cfg, params, name, x):
    return apply_norm(cfg.norm, params[name], x, eps=cfg.norm_eps)


def _gathered_rows(cache, slots):
    """Per-slot state leaves -> chunk-batch rows (identity when slots is
    None, i.e. rows already align with the batch)."""
    if slots is None:
        return cache
    return jax.tree_util.tree_map(lambda l: l[slots], cache)


def _scattered_rows(cache, new_rows, slots):
    """Write updated chunk-batch state rows back at their slots."""
    if slots is None:
        return new_rows
    return jax.tree_util.tree_map(
        lambda l, r: l.at[slots].set(r.astype(l.dtype)), cache, new_rows)


# "attn" names the token mixer of every layer kind (attention, or an
# SSM / RG-LRU recurrence) in the step programs' op metadata
@jax.named_scope("attn")
def _mixer(params, h, *, cfg, spec, mode, positions, pos, cache, par,
           lengths=None, block_table=None, kv_max_len=None,
           slots=None, chunk_lens=None, active=None):
    """Dispatch the sequence mixer. Returns (out, new_cache).

    'chunk' mode is layout-polymorphic: paged leaves (GQA K/V, MLA
    latents) write through the block table; ring leaves (sliding-window
    K/V) and state leaves (SSM / RG-LRU) are per-slot dense rows, so the
    chunk batch gathers its rows at ``slots``, advances them by
    ``chunk_lens`` valid tokens, and scatters them back.  'decode' mode
    threads ``active`` so lanes mid-chunked-prefill keep their dense
    rows frozen."""
    if spec.mixer == "gqa":
        if mode == "decode":
            return attn.attention_decode(params, h, cache, spec=spec,
                                         cfg=cfg, pos=pos, par=par,
                                         block_table=block_table,
                                         active=active)
        if mode == "chunk":
            return attn.attention_chunk(params, h, cache, spec=spec,
                                        cfg=cfg, pos=pos, par=par,
                                        block_table=block_table,
                                        kv_max_len=kv_max_len,
                                        slots=slots, chunk_lens=chunk_lens)
        return attn.attention_apply(params, h, spec=spec, cfg=cfg,
                                    positions=positions, par=par,
                                    return_cache=(mode == "prefill"),
                                    lengths=lengths)
    if spec.mixer == "mla":
        if mode == "decode":
            return mla_lib.mla_decode(params, h, cache, spec=spec, cfg=cfg,
                                      pos=pos, par=par,
                                      block_table=block_table,
                                      kv_max_len=kv_max_len)
        if mode == "chunk":
            return mla_lib.mla_chunk(params, h, cache, spec=spec, cfg=cfg,
                                     pos=pos, par=par,
                                     block_table=block_table,
                                     kv_max_len=kv_max_len)
        return mla_lib.mla_apply(params, h, spec=spec, cfg=cfg,
                                 positions=positions, par=par,
                                 return_cache=(mode == "prefill"))
    if spec.mixer == "mamba":
        if mode == "decode":
            return ssm_lib.ssm_decode(params, h, cache, cfg=cfg, par=par,
                                      active=active)
        if mode == "chunk":
            rows = _gathered_rows(cache, slots)
            out, new_rows = ssm_lib.ssm_chunk(params, h, rows, cfg=cfg,
                                              par=par, chunk_lens=chunk_lens)
            return out, _scattered_rows(cache, new_rows, slots)
        return ssm_lib.ssm_apply(params, h, cfg=cfg, par=par,
                                 return_cache=(mode == "prefill"))
    if spec.mixer == "rglru":
        if mode == "decode":
            return rglru_lib.rglru_decode(params, h, cache, cfg=cfg, par=par,
                                          active=active)
        if mode == "chunk":
            rows = _gathered_rows(cache, slots)
            out, new_rows = rglru_lib.rglru_chunk(params, h, rows, cfg=cfg,
                                                  par=par,
                                                  chunk_lens=chunk_lens)
            return out, _scattered_rows(cache, new_rows, slots)
        return rglru_lib.rglru_apply(params, h, cfg=cfg, par=par,
                                     return_cache=(mode == "prefill"))
    raise ValueError(f"unknown mixer {spec.mixer!r}")


def layer_apply(params, x: jax.Array, *, cfg: ModelConfig, spec: LayerSpec,
                mode: str = "train",
                positions: Optional[jax.Array] = None,
                pos: Optional[jax.Array] = None,
                cache: Any = None,
                enc_states: Any = None,
                par: Parallelism = NO_PARALLEL,
                lengths: Optional[jax.Array] = None,
                block_table: Optional[jax.Array] = None,
                kv_max_len: Optional[int] = None,
                slots: Optional[jax.Array] = None,
                chunk_lens: Optional[jax.Array] = None,
                active: Optional[jax.Array] = None):
    """One transformer layer. Returns (x, cache, aux).

    For cross-attention layers the cache is (self_cache, enc_kv): the
    projected encoder K/V is computed once at prefill and carried in the
    cache; `enc_states` (raw encoder output) is only needed in
    train/prefill modes.

    ``lengths`` [B] marks per-row true lengths of a right-padded prefill
    batch (bucketed serving); only ring-buffer cache construction uses it.
    ``block_table`` [B, max_blocks_per_seq] addresses paged cache leaves
    in decode/chunk mode (mode 'chunk' = multi-token chunked prefill
    against the cache — any mixer).  ``slots`` [B] maps chunk rows to
    engine slots for per-slot ring/state leaves; ``chunk_lens`` [B]
    gives valid token counts of a padded final chunk; ``active`` [B]
    bool freezes dense-leaf writes of inactive decode lanes.
    """
    aux = jnp.zeros((), jnp.float32)
    self_cache, enc_kv = (cache if (spec.cross_attn and cache is not None)
                          else (cache, None))

    h = _norm(cfg, params, "ln1", x)
    h, new_cache = _mixer(params["mixer"], h, cfg=cfg, spec=spec, mode=mode,
                          positions=positions, pos=pos, cache=self_cache,
                          par=par, lengths=lengths, block_table=block_table,
                          kv_max_len=kv_max_len, slots=slots,
                          chunk_lens=chunk_lens, active=active)
    if cfg.post_norm:
        h = _norm(cfg, params, "ln1_post", h)
    x = x + h

    if spec.cross_attn:
        if enc_kv is None:
            enc_kv = attn.cross_kv(params["cross"], enc_states, par=par)
        h = _norm(cfg, params, "ln_x", x)
        h = attn.cross_attention_apply(params["cross"], h, enc_kv,
                                       cfg=cfg, par=par)
        x = x + h

    if spec.mlp != "none":
        h = _norm(cfg, params, "ln2", x)
        if spec.mlp == "moe":
            h, aux = moe_lib.moe_apply(params["mlp"], h, cfg=cfg, par=par)
        else:
            h = mlp_apply(params["mlp"], h, spec.mlp, par=par,
                          use_pallas=cfg.use_pallas)
        if cfg.post_norm:
            h = _norm(cfg, params, "ln2_post", h)
        x = x + h

    if spec.cross_attn and new_cache is not None:
        new_cache = (new_cache, enc_kv)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# cache allocation (decode dry-run / serving)
# ---------------------------------------------------------------------------

def layer_cache_shape(cfg: ModelConfig, spec: LayerSpec, batch: int,
                      seq_len: int, dtype, enc_len: int = 0) -> Any:
    """Zero cache for one layer at max sequence seq_len."""
    if spec.mixer == "gqa":
        s = seq_len if spec.window is None else min(seq_len, spec.window)
        shp = (batch, s, cfg.n_kv_heads, cfg.head_dim)
        kv = (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))
        if spec.cross_attn:
            eshp = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
            return (kv, (jnp.zeros(eshp, dtype), jnp.zeros(eshp, dtype)))
        return kv
    if spec.mixer == "mla":
        m = cfg.mla
        return (jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
                jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype))
    if spec.mixer == "mamba":
        s = cfg.ssm
        return (jnp.zeros((batch, s.d_conv - 1, s.d_inner), dtype),
                jnp.zeros((batch, s.d_inner, s.d_state), jnp.float32))
    if spec.mixer == "rglru":
        r = cfg.rglru
        return (jnp.zeros((batch, r.d_conv - 1, r.d_inner), dtype),
                jnp.zeros((batch, r.d_inner), jnp.float32))
    raise ValueError(spec.mixer)
