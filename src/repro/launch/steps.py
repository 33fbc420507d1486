"""Step builders: train / prefill / serve step functions for any arch
(decoder-LM or PT), plus the abstract input specs the dry-run lowers
against.

``make_*_step`` returns (fn, in_specs_fn, parallelism) where fn is the
un-jitted step; the dry-run and launchers jit it with shardings from
``runtime.sharding``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.common.pytree import count_params
from repro.common.types import ModelConfig, ShapeSpec
from repro.configs.whisper_medium import ENC_FRAMES
from repro.core import track as pt_lib
from repro.models import decoder as dec_lib
from repro.optim import clip_by_global_norm, make_optimizer, warmup_cosine
from repro.runtime.parallel import (DECODE_RULES, TRAIN_RULES, Parallelism)

# --------------------------------------------------------------------------
# parallelism presets
# --------------------------------------------------------------------------

PT_EXTRA = {"heads": "tp", "kv_heads": "tp", "d_ff": "tp", "d_inner": "tp",
            "vocab": ("track", "tp"), "experts": ("tp",)}


def build_parallelism(cfg: ModelConfig, kind: str, mesh: Optional[Mesh],
                      fsdp: bool = False,
                      seq_shard: bool = False) -> Parallelism:
    rules = dict(DECODE_RULES if kind == "decode" else TRAIN_RULES)
    if cfg.pt is not None:
        rules.update(PT_EXTRA)
        if kind == "decode":
            rules.update({"kv_seq": "tp", "heads": None, "kv_heads": None})
    if fsdp:
        rules["fsdp"] = "data"
    if seq_shard and kind != "decode":
        # Megatron sequence parallelism: the residual stream is
        # seq-sharded over 'model' between sublayers, turning the 2
        # per-layer all-reduces into reduce-scatter + all-gather pairs
        # (half the wire bytes) — a beyond-paper optimization.
        rules["seq"] = "model"
    return Parallelism(mesh=mesh, rules=rules)


def wants_fsdp(cfg: ModelConfig, kind: str) -> bool:
    """FSDP params over 'data' for training anything that would not fit
    replicated optimizer state (everything ≥ ~2B params)."""
    if kind != "train":
        return False
    approx = 12 * cfg.n_layers * cfg.d_model ** 2
    return approx > 2e9


# --------------------------------------------------------------------------
# model fn dispatch (decoder LM vs PT)
# --------------------------------------------------------------------------

def model_fns(cfg: ModelConfig):
    # 'verify' is the chunk step used as a speculative scorer: per-
    # position logits over K+1 tokens against the paged cache, with a
    # static bound on the gather ('chunk' and 'verify' share the program;
    # the split names the two call sites).  'draft' (PT only) is the
    # sync-free track-subset decode step.
    if cfg.pt is not None:
        return {
            "init": pt_lib.init_pt,
            "loss": pt_lib.pt_loss,
            "forward": pt_lib.pt_forward,
            "decode": pt_lib.pt_decode_step,
            "chunk": pt_lib.pt_chunk_step,
            "verify": pt_lib.pt_chunk_step,
            "draft": pt_lib.pt_draft_step,
            "init_cache": lambda c, b, s, enc_len=0: pt_lib.pt_init_cache(c, b, s),
        }
    return {
        "init": dec_lib.init_lm,
        "loss": dec_lib.lm_loss,
        "forward": dec_lib.lm_forward,
        "decode": dec_lib.lm_decode_step,
        "chunk": dec_lib.lm_chunk_step,
        "verify": dec_lib.lm_chunk_step,
        "init_cache": dec_lib.init_cache,
    }


# --------------------------------------------------------------------------
# abstract input specs (ShapeDtypeStructs — never allocated)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Training / prefill batch stand-ins."""
    B, S = shape.global_batch, shape.seq_len
    d = {}
    if cfg.input_kind == "embeds":
        d["inputs"] = _sds((B, S, cfg.d_model), jnp.bfloat16)
    else:
        d["inputs"] = _sds((B, S), jnp.int32)
    if shape.kind == "train":
        d["targets"] = _sds((B, S), jnp.int32)
    if cfg.mrope_sections:
        d["positions"] = _sds((3, B, S), jnp.int32)
    if cfg.encdec is not None:
        d["enc_inputs"] = _sds((B, ENC_FRAMES, cfg.d_model), jnp.bfloat16)
    return d


def cache_specs(cfg: ModelConfig, shape: ShapeSpec) -> Any:
    fns = model_fns(cfg)
    enc_len = ENC_FRAMES if cfg.encdec is not None else 0
    return jax.eval_shape(
        lambda: fns["init_cache"](cfg, shape.global_batch, shape.seq_len,
                                  enc_len=enc_len)
        if cfg.pt is None else fns["init_cache"](cfg, shape.global_batch,
                                                 shape.seq_len))


def decode_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    B = shape.global_batch
    return {
        "cache": cache_specs(cfg, shape),
        "tokens": _sds((B,), jnp.int32),
        "pos": _sds((B,), jnp.int32),
    }


def param_specs(cfg: ModelConfig) -> Any:
    fns = model_fns(cfg)
    return jax.eval_shape(lambda: fns["init"](jax.random.PRNGKey(0), cfg))


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Everything the step function for this cell consumes (sans params /
    optimizer state, which have their own spec builders)."""
    if shape.kind == "decode":
        return decode_specs(cfg, shape)
    return {"batch": batch_specs(cfg, shape)}


# --------------------------------------------------------------------------
# step functions
# --------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, par: Parallelism,
                    microbatches: int = 0,
                    peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10000, clip_norm: float = 1.0):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Gradient accumulation: the global batch is split into microbatches on
    the leading axis and scanned, accumulating fp32 grads — the standard
    memory lever for the large train cells.
    """
    fns = model_fns(cfg)
    n_params = count_params(param_specs(cfg))
    opt_init, opt_update, opt_name = make_optimizer(cfg, n_params)
    mb = microbatches or cfg_default_microbatches(cfg)

    def loss_fn(params, batch):
        return fns["loss"](params, batch, cfg, par)

    def train_step(params, opt_state, batch):
        B = batch["targets"].shape[0]
        # each microbatch must still shard over the data axes — a
        # microbatch smaller than the DP degree would silently REPLICATE
        # activations on every chip (25x compute for v3 before this guard)
        dp = 1
        for a in par.dp_axes:
            dp *= par.mesh.shape[a] if par.mesh else 1
        mb_eff = mb
        while mb_eff > 1 and (B % mb_eff or (B // mb_eff) % dp):
            mb_eff //= 2
        assert B % mb_eff == 0, (B, mb_eff)

        def to_micro(x):
            return x.reshape((mb_eff, B // mb_eff) + x.shape[1:]) \
                if x.shape[0] == B else \
                x.reshape(x.shape[:1] + (mb_eff, B // mb_eff) + x.shape[2:]) \
                .swapaxes(0, 1)

        micro = jax.tree_util.tree_map(to_micro, batch)

        def acc_body(carry, mb_batch):
            gsum, lsum = carry
            (l, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb_batch)
            g32 = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (g32, lsum + l), None

        if mb_eff > 1:
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(acc_body, (zeros, 0.0), micro)
            grads = jax.tree_util.tree_map(lambda g: g / mb_eff, gsum)
            loss = lsum / mb_eff
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)

        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        lr = warmup_cosine(opt_state["step"], peak_lr=peak_lr, warmup=warmup,
                           total=total_steps)
        params, opt_state = opt_update(grads, opt_state, params, lr)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "lr": lr}

    return train_step, opt_init, opt_name


def cfg_default_microbatches(cfg: ModelConfig) -> int:
    """Per-arch accumulation factor for the train_4k cell (sized so
    per-microbatch activations fit 16 GB/chip with remat)."""
    by_name = {
        "deepseek-v3-671b": 16,
        "deepseek-v2-236b": 16,
        "qwen2-vl-72b": 16,
        "nemotron-4-15b": 8,
        "recurrentgemma-9b": 8,
        "falcon-mamba-7b": 8,
        "gemma3-4b": 4,
        "gemma2-2b": 4,
        "whisper-medium": 2,
        "tinyllama-1.1b": 2,
    }
    for k, v in by_name.items():
        if cfg.name.startswith(k):
            return v
    return 4 if cfg.n_layers >= 24 else 1


def make_prefill_step(cfg: ModelConfig, par: Parallelism):
    """(batch) -> (last_logits, cache)."""
    fns = model_fns(cfg)

    def prefill(params, batch):
        logits, cache, _ = fns["forward"](params, batch, cfg, par,
                                          mode="prefill")
        return logits[:, -1], cache

    return prefill


def make_serve_step(cfg: ModelConfig, par: Parallelism):
    """(params, cache, tokens, pos) -> (logits, cache)."""
    fns = model_fns(cfg)

    def serve(params, cache, tokens, pos):
        return fns["decode"](params, cache, tokens, pos, cfg, par)

    return serve


def make_draft_step(cfg: ModelConfig, par: Parallelism, draft_tracks: int):
    """Speculative drafter for a PT config: (draft_params, cache, tokens,
    pos) -> (logits, cache), plus the draft config whose ``init_cache``/
    ``pt_draft_params`` shapes match.  The compiled step carries ZERO
    cross-track collectives (the 'track' mesh axis is stripped — the
    d-track stack runs replicated)."""
    draft_cfg = pt_lib.pt_draft_config(cfg, draft_tracks)

    def draft(draft_params, cache, tokens, pos):
        return pt_lib.pt_draft_step(draft_params, cache, tokens, pos,
                                    draft_cfg, par)

    return draft, draft_cfg


def make_verify_step(cfg: ModelConfig, par: Parallelism):
    """Speculative verifier: (params, cache, tokens [B, K+1], pos,
    block_table) -> (per-position logits [B, K+1, V], cache) against the
    paged cache — one target forward scores a whole draft."""
    fns = model_fns(cfg)

    def verify(params, cache, tokens, pos, block_table, kv_max_len=None):
        return fns["verify"](params, cache, tokens, pos, cfg, par,
                             block_table=block_table,
                             kv_max_len=kv_max_len)

    return verify


def aot_compile(jitted, *args, **static_kwargs):
    """Pre-plan one jitted program for a fixed input bucket: lower it
    against the given example arguments (shapes/dtypes only — nothing
    executes) and compile the executable ahead of time.  The returned
    callable replays the ready program with the tracer, shape dispatch
    and donation analysis all off the hot path; it must be called with
    arguments of exactly the lowered shapes/dtypes, minus the static
    kwargs (those are baked into the executable).

    This is the serving engine's per-bucket "capture once, replay"
    program cache (the CUDA-graph-per-batch-size pattern): the runner
    plans the decode executable, and one spec executable per ``max_len``
    bucket, at startup and dispatches through the plan, falling back to
    the ``jax.jit`` wrapper for unplanned shapes."""
    structs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype)
        if not hasattr(x, "shape") or not hasattr(x, "dtype")
        else jax.ShapeDtypeStruct(x.shape, x.dtype),
        args)
    return jitted.lower(*structs, **static_kwargs).compile()
