"""Generic pattern-scanned decoder LM (+ optional whisper-style encoder).

One implementation drives all ten assigned architectures: the layer stack
is (prefix, unit × R, suffix) per ModelConfig.  The repeated unit's params
are stacked on a leading axis and driven by ``lax.scan`` so trace/compile
cost is O(|unit|), not O(L); activation checkpointing wraps the scan body.

Public entry points:
  init_lm(key, cfg)                          -> params
  lm_forward(params, batch, cfg, par, mode)  -> train: (logits, aux)
                                                prefill: (logits, cache, aux)
  lm_decode_step(params, cache, tokens, pos, cfg, par) -> (logits, cache)
  init_cache(cfg, batch, seq_len)            -> zeroed cache pytree
  lm_loss(params, batch, cfg, par)           -> (loss, metrics)
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import quant
from repro.common.types import ModelConfig
from repro.models import rope as rope_lib
from repro.models.attention import cross_kv
from repro.models.layers import layer_apply, layer_cache_shape, layer_init
from repro.models.norms import apply_norm, norm_init
from repro.runtime.parallel import Parallelism, NO_PARALLEL


def model_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol, prevent_cse=False)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked_layer_init(key, cfg: ModelConfig, spec, d_stream, n, dtype):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: layer_init(k, cfg, spec, d_stream, dtype))(keys)


def init_lm(key, cfg: ModelConfig):
    dtype = model_dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    scale = 1.0 / math.sqrt(d)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, d), jnp.float32)
                  * scale).astype(dtype),
        "final_norm": norm_init(cfg.norm, d),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(ks[1], (d, cfg.vocab_size),
                                            jnp.float32) * scale).astype(dtype)
    kp = jax.random.split(ks[2], max(1, len(cfg.pattern_prefix)))
    params["prefix"] = tuple(
        layer_init(kp[i], cfg, cfg.spec(nm), d, dtype)
        for i, nm in enumerate(cfg.pattern_prefix))
    ku = jax.random.split(ks[3], max(1, len(cfg.pattern_unit)))
    params["unit"] = tuple(
        _stacked_layer_init(ku[j], cfg, cfg.spec(nm), d, cfg.pattern_repeat,
                            dtype)
        for j, nm in enumerate(cfg.pattern_unit)) if cfg.pattern_repeat else ()
    ksf = jax.random.split(ks[4], max(1, len(cfg.pattern_suffix)))
    params["suffix"] = tuple(
        layer_init(ksf[i], cfg, cfg.spec(nm), d, dtype)
        for i, nm in enumerate(cfg.pattern_suffix))
    if cfg.encdec is not None:
        params["enc"] = {
            "layers": _stacked_layer_init(ks[5], cfg, cfg.spec("enc"), d,
                                          cfg.encdec.n_enc_layers, dtype),
            "final_norm": norm_init(cfg.norm, d),
        }
    return params


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings. positions [B,S] -> [B,S,d]."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


@jax.named_scope("embed")
def _embed(params, inputs: jax.Array, cfg: ModelConfig,
           positions: jax.Array, par: Parallelism) -> jax.Array:
    dtype = model_dtype(cfg)
    if jnp.issubdtype(inputs.dtype, jnp.floating):
        h = inputs.astype(dtype)                      # precomputed embeds (stub)
    else:
        h = jnp.take(params["embed"], inputs, axis=0)
    if cfg.embedding_multiplier != 1.0:
        h = (h.astype(jnp.float32) * cfg.embedding_multiplier).astype(dtype)
    if cfg.encdec is not None:                        # whisper: sinusoid pos
        p = positions if positions.ndim == 2 else positions[0]
        h = h + _sinusoid(p, cfg.d_model).astype(dtype)
    return par.cs(h, "batch", "seq", "d_model")


@jax.named_scope("lm_head")
def _head(params, h: jax.Array, cfg: ModelConfig, par: Parallelism):
    h = apply_norm(cfg.norm, params["final_norm"], h, eps=cfg.norm_eps)
    if cfg.logits_fp32:
        h = h.astype(jnp.float32)
    if cfg.tie_embeddings:
        w = params["embed"]                # embeddings are never quantized
        logits = jnp.einsum("...d,vd->...v", h, w.astype(h.dtype))
    elif quant.is_quantized(params["head"]):
        logits = quant.matmul(h, params["head"],
                              use_kernel=cfg.use_pallas and par.mesh is None
                              ).astype(h.dtype)
    else:
        logits = h @ params["head"].astype(h.dtype)
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    dims = ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",)
    return par.cs(logits, *dims)


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------

def encode(params, enc_inputs: jax.Array, cfg: ModelConfig,
           par: Parallelism = NO_PARALLEL) -> jax.Array:
    """enc_inputs: [B, S_enc, d] precomputed frame embeddings (stub)."""
    B, S, _ = enc_inputs.shape
    positions = rope_lib.positions_default(B, S)
    h = enc_inputs.astype(model_dtype(cfg))
    h = h + _sinusoid(positions, cfg.d_model).astype(h.dtype)
    h = par.cs(h, "batch", None, "d_model")
    spec = cfg.spec("enc")

    def body(carry, lp):
        x, _ = carry
        x, _, aux = layer_apply(lp, x, cfg=cfg, spec=spec, mode="train",
                                positions=positions, par=par)
        return (x, aux), None

    body = _remat(body, cfg)
    (h, _), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                             params["enc"]["layers"])
    return apply_norm(cfg.norm, params["enc"]["final_norm"], h,
                      eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def lm_forward(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
               par: Parallelism = NO_PARALLEL, mode: str = "train"):
    """batch: {'inputs': [B,S] int32 | [B,S,d] float, 'positions'?: [B,S] or
    [3,B,S] (mrope), 'enc_inputs'?: [B,S_enc,d], 'lengths'?: [B] int32 —
    per-row true lengths when the batch is right-padded to a prefill
    bucket (serving); see layer_apply."""
    inputs = batch["inputs"]
    B, S = inputs.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = rope_lib.positions_default(B, S)
    lengths = batch.get("lengths") if mode == "prefill" else None
    enc_states = None
    if cfg.encdec is not None:
        enc_states = encode(params, batch["enc_inputs"], cfg, par)

    h = _embed(params, inputs, cfg, positions, par)
    want_cache = mode == "prefill"
    caches_prefix = []
    aux_total = jnp.zeros((), jnp.float32)
    for i, nm in enumerate(cfg.pattern_prefix):
        h, c, aux = layer_apply(params["prefix"][i], h, cfg=cfg,
                                spec=cfg.spec(nm), mode=mode,
                                positions=positions, enc_states=enc_states,
                                par=par, lengths=lengths)
        aux_total += aux
        caches_prefix.append(c)

    unit_caches = ()
    if cfg.pattern_repeat:
        def body(carry, lps):
            x, auxc = carry
            cs = []
            for j, nm in enumerate(cfg.pattern_unit):
                x, c, aux = layer_apply(lps[j], x, cfg=cfg,
                                        spec=cfg.spec(nm), mode=mode,
                                        positions=positions,
                                        enc_states=enc_states, par=par,
                                        lengths=lengths)
                auxc = auxc + aux
                cs.append(c)
            return (x, auxc), (tuple(cs) if want_cache else None)

        body = _remat(body, cfg) if mode == "train" else body
        (h, aux_u), unit_caches = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), params["unit"])
        aux_total += aux_u

    caches_suffix = []
    for i, nm in enumerate(cfg.pattern_suffix):
        h, c, aux = layer_apply(params["suffix"][i], h, cfg=cfg,
                                spec=cfg.spec(nm), mode=mode,
                                positions=positions, enc_states=enc_states,
                                par=par, lengths=lengths)
        aux_total += aux
        caches_suffix.append(c)

    logits = _head(params, h, cfg, par)
    if mode == "train":
        return logits, aux_total
    cache = {"prefix": tuple(caches_prefix), "unit": unit_caches,
             "suffix": tuple(caches_suffix)}
    return logits, cache, aux_total


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _step_layers(params, cache, h, pos, cfg: ModelConfig, par: Parallelism,
                 mode: str, block_table, kv_max_len=None, slots=None,
                 chunk_lens=None, active=None):
    """Run the (prefix, unit-scan, suffix) stack in decode or chunk mode."""
    new_prefix = []
    for i, nm in enumerate(cfg.pattern_prefix):
        h, c, _ = layer_apply(params["prefix"][i], h, cfg=cfg,
                              spec=cfg.spec(nm), mode=mode, pos=pos,
                              cache=cache["prefix"][i], par=par,
                              block_table=block_table,
                              kv_max_len=kv_max_len, slots=slots,
                              chunk_lens=chunk_lens, active=active)
        new_prefix.append(c)

    new_unit = cache["unit"]
    if cfg.pattern_repeat:
        def body(x, xs):
            lps, cs_in = xs
            cs_out = []
            for j, nm in enumerate(cfg.pattern_unit):
                x, c, _ = layer_apply(lps[j], x, cfg=cfg, spec=cfg.spec(nm),
                                      mode=mode, pos=pos,
                                      cache=cs_in[j], par=par,
                                      block_table=block_table,
                                      kv_max_len=kv_max_len, slots=slots,
                                      chunk_lens=chunk_lens, active=active)
                cs_out.append(c)
            return x, tuple(cs_out)

        h, new_unit = jax.lax.scan(body, h, (params["unit"], cache["unit"]))

    new_suffix = []
    for i, nm in enumerate(cfg.pattern_suffix):
        h, c, _ = layer_apply(params["suffix"][i], h, cfg=cfg,
                              spec=cfg.spec(nm), mode=mode, pos=pos,
                              cache=cache["suffix"][i], par=par,
                              block_table=block_table,
                              kv_max_len=kv_max_len, slots=slots,
                              chunk_lens=chunk_lens, active=active)
        new_suffix.append(c)
    return h, {"prefix": tuple(new_prefix), "unit": new_unit,
               "suffix": tuple(new_suffix)}


def lm_decode_step(params, cache, tokens: jax.Array, pos: jax.Array,
                   cfg: ModelConfig, par: Parallelism = NO_PARALLEL,
                   block_table: Optional[jax.Array] = None,
                   kv_max_len: Optional[int] = None,
                   active: Optional[jax.Array] = None):
    """tokens: [B] int32; pos: [B] int32 (cache write index).
    ``block_table`` [B, max_blocks_per_seq] addresses paged cache leaves;
    ``kv_max_len`` (static) bounds the paged kernel's block sweep;
    ``active`` [B] bool freezes dense ring/state leaf writes of inactive
    lanes (paged leaves already route them to the trash block).
    Returns (logits [B, V], updated cache)."""
    h = _embed(params, tokens[:, None], cfg, pos[:, None], par)
    h, new_cache = _step_layers(params, cache, h, pos, cfg, par, "decode",
                                block_table, kv_max_len, active=active)
    logits = _head(params, h[:, 0], cfg, par)
    return logits, new_cache


def lm_chunk_step(params, cache, tokens: jax.Array, pos: jax.Array,
                  cfg: ModelConfig, par: Parallelism = NO_PARALLEL,
                  block_table: Optional[jax.Array] = None,
                  kv_max_len: Optional[int] = None,
                  slots: Optional[jax.Array] = None,
                  chunk_lens: Optional[jax.Array] = None):
    """Chunked-prefill / K-token verify step: tokens [B, C] appended at
    positions pos[:, None] + arange(C) against the serving cache.
    Returns (logits [B, C, V], updated cache) — per-position logits, so
    the same program scores a speculative draft (C = K+1) or streams a
    prompt chunk.

    Layout-polymorphic: paged leaves (GQA K/V, MLA latents) write through
    ``block_table``; ring leaves (sliding-window K/V) and state leaves
    (SSM / RG-LRU) advance their per-slot rows at ``slots`` by
    ``chunk_lens`` valid tokens (padded tails of a final chunk do
    identity updates).  ``kv_max_len`` (static) bounds the paged gather
    to the live cache prefix.
    """
    B, C = tokens.shape
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    h = _embed(params, tokens, cfg, positions, par)
    h, new_cache = _step_layers(params, cache, h, pos, cfg, par, "chunk",
                                block_table, kv_max_len, slots=slots,
                                chunk_lens=chunk_lens)
    logits = _head(params, h, cfg, par)
    return logits, new_cache


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               enc_len: int = 0) -> Dict[str, Any]:
    """Zeroed cache pytree for decode at max context seq_len."""
    dtype = model_dtype(cfg)

    def one(nm):
        return layer_cache_shape(cfg, cfg.spec(nm), batch, seq_len, dtype,
                                 enc_len=enc_len)

    unit = ()
    if cfg.pattern_repeat:
        unit = tuple(
            jax.tree_util.tree_map(
                lambda l: jnp.zeros((cfg.pattern_repeat,) + l.shape, l.dtype),
                one(nm))
            for nm in cfg.pattern_unit)
    return {
        "prefix": tuple(one(nm) for nm in cfg.pattern_prefix),
        "unit": unit,
        "suffix": tuple(one(nm) for nm in cfg.pattern_suffix),
    }


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            par: Parallelism = NO_PARALLEL):
    """Next-token cross entropy.  targets == -1 marks padding."""
    logits, aux = lm_forward(params, batch, cfg, par, mode="train")
    targets = batch["targets"]
    mask = (targets >= 0).astype(jnp.float32)
    t = jnp.maximum(targets, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0] - logz
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = -jnp.sum(ll * mask) / denom
    total = loss + aux
    return total, {"loss": loss, "aux": aux, "tokens": jnp.sum(mask)}
