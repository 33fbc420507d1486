"""Mixture-of-Experts MLP: shared + routed experts, capacity-based
dispatch with expert-parallel all-to-all.

Expert parallelism maps the expert axis onto the *combined*
``('data','model')`` mesh axes (256-way for deepseek-v3: one routed expert
per chip — expert weights cannot fit at 16-way TP).  Inside a shard_map
block:

  1. each chip takes its 1/TP sub-slice of the data-shard's tokens
     (token sub-sharding over 'model' — routing work is divided, not
     replicated),
  2. routes locally and packs a capacity-bounded send buffer
     [E, c_send, d] via an inverse-index gather (no [T,E,C] one-hot —
     dispatch costs O(T·k·d) bytes, zero extra matmul FLOPs),
  3. ONE all-to-all ships token slots to expert owners, the local
     expert FFN runs, ONE all-to-all ships results back,
  4. combine weights are applied at the source; an all-gather over
     'model' rebuilds the data-shard's token block.

Because each expert is owned by exactly one chip, expert-weight gradients
are local to the owner (no gradient all-reduce for expert params) —
matching production EP training semantics.

Shared experts run Megatron-TP over 'model' on the full token block
(weights d_ff-sharded; one psum combines partial features).

Routing: 'softmax' (switch-style aux loss) or 'sigmoid_bias'
(DeepSeek-V3 aux-free).  E may be stored padded (``n_experts_padded``) so
the expert axis divides the EP size; padded experts are masked at
selection.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.types import ModelConfig
from repro.runtime.parallel import Parallelism, NO_PARALLEL


def _init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def e_store(cfg: ModelConfig) -> int:
    m = cfg.moe
    return max(m.n_routed_experts, m.n_experts_padded)


def moe_init(key, cfg: ModelConfig, d_stream: int, dtype=jnp.float32):
    m = cfg.moe
    ks = jax.random.split(key, 8)
    E, de = e_store(cfg), m.d_expert
    p = {
        "router": _init(ks[0], (d_stream, E), d_stream, jnp.float32),
        "w_gate": _init(ks[1], (E, d_stream, de), d_stream, dtype),
        "w_up": _init(ks[2], (E, d_stream, de), d_stream, dtype),
        "w_down": _init(ks[3], (E, de, d_stream), de, dtype),
    }
    if m.router == "sigmoid_bias":
        p["e_bias"] = jnp.zeros((E,), jnp.float32)
    if m.n_shared_experts > 0:
        ds = m.n_shared_experts * de
        p["ws_gate"] = _init(ks[4], (d_stream, ds), d_stream, dtype)
        p["ws_up"] = _init(ks[5], (d_stream, ds), d_stream, dtype)
        p["ws_down"] = _init(ks[6], (ds, d_stream), ds, dtype)
    return p


def _route(params, x2, cfg: ModelConfig):
    """x2: [T, d] -> weights [T,k] fp32, idx [T,k] int32, aux scalar."""
    m = cfg.moe
    E = m.n_routed_experts
    logits = x2.astype(jnp.float32) @ params["router"]          # [T, E_store]
    if logits.shape[-1] > E:                                     # mask padding
        pad = jnp.full((logits.shape[0], logits.shape[-1] - E), -1e30)
        logits = jnp.concatenate([logits[:, :E], pad], axis=-1)
    if m.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        sel = scores + params["e_bias"][None, :]
        sel = jnp.where(jnp.arange(logits.shape[-1])[None, :] < E, sel, -1e30)
        _, idx = jax.lax.top_k(sel, m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if m.norm_topk_prob:
            w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-20)
        return w * m.routed_scaling_factor, idx, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-20)
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # [T,k,E]
    f = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)
    pbar = jnp.mean(probs[:, :E], axis=0)
    aux = m.aux_loss_coef * E * jnp.sum(f * pbar)
    return w * m.routed_scaling_factor, idx, aux


def _dispatch_indices(idx, E_total: int, cap: int):
    """idx: [T, k] expert ids.  Returns slot [T,k] into a flat
    [E_total*cap] buffer (== E_total*cap for dropped) and keep mask."""
    T, k = idx.shape
    flat = jax.nn.one_hot(idx.reshape(-1), E_total, dtype=jnp.int32)
    pos = (jnp.cumsum(flat, axis=0) - flat)
    pos = jnp.sum(pos * flat, axis=-1).reshape(T, k)
    keep = pos < cap
    slot = jnp.where(keep, idx * cap + pos, E_total * cap)
    return slot, keep


def _pack(x2, slot, T_cap: int, n_slots: int):
    """Inverse-index gather: build [n_slots, d] buffer from x2 [T, d]."""
    tok_for_slot = jnp.full((n_slots,), T_cap, jnp.int32)
    T, k = slot.shape
    tok_src = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                               (T, k)).reshape(-1)
    tok_for_slot = tok_for_slot.at[slot.reshape(-1)].set(tok_src, mode="drop")
    return jnp.take(x2, tok_for_slot, axis=0, mode="fill", fill_value=0)


def _expert_ffn(params, buf, E_loc: int):
    """buf: [E_loc, C, d] -> [E_loc, C, d] with local expert slices."""
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _moe_block(x2, params, *, cfg: ModelConfig, cap: int,
               ep_axes: Sequence[str], tp_axis: Optional[str],
               dp_axes: Sequence[str]):
    """Per-(data-shard × model-shard) MoE body.

    x2: [T_loc, d] — the data shard's tokens (identical across 'model').
    Sub-shards tokens over tp_axis, dispatches over ep_axes via all-to-all,
    and all-gathers results back over tp_axis.  Returns (y [T_loc,d], aux).
    """
    m = cfg.moe
    T_loc, d = x2.shape
    E_total = e_store(cfg)

    tp = jax.lax.axis_size(tp_axis) if tp_axis else 1
    T_sub = -(-T_loc // tp)
    if tp > 1:
        x_pad = jnp.pad(x2, ((0, T_sub * tp - T_loc), (0, 0)))
        me = jax.lax.axis_index(tp_axis)
        xs = jax.lax.dynamic_slice_in_dim(x_pad, me * T_sub, T_sub, axis=0)
    else:
        xs = x2

    w, idx, aux = _route(params, xs, cfg)
    slot, keep = _dispatch_indices(idx, E_total, cap)
    buf = _pack(xs, slot, T_sub, E_total * cap).reshape(E_total, cap, d)

    ep = 1
    for a in ep_axes:
        ep *= jax.lax.axis_size(a)
    if ep > 1:
        buf = jax.lax.all_to_all(buf, ep_axes, split_axis=0, concat_axis=0,
                                 tiled=True)
    E_loc = E_total // ep
    # dim0 of buf = (source_shard, local_expert); group by local expert
    bufr = buf.reshape(ep, E_loc, cap, d).transpose(1, 0, 2, 3)
    bufr = bufr.reshape(E_loc, ep * cap, d)
    out = _expert_ffn(params, bufr, E_loc)
    out = out.reshape(E_loc, ep, cap, d).transpose(1, 0, 2, 3)
    out = out.reshape(E_total, cap, d)
    if ep > 1:
        out = jax.lax.all_to_all(out, ep_axes, split_axis=0, concat_axis=0,
                                 tiled=True)

    out_flat = out.reshape(E_total * cap, d)
    y = jnp.zeros((T_sub, d), x2.dtype)
    for j in range(m.top_k):
        oj = jnp.take(out_flat,
                      jnp.where(keep[:, j], slot[:, j], E_total * cap),
                      axis=0, mode="fill", fill_value=0)
        y = y + w[:, j, None].astype(x2.dtype) * oj

    if tp > 1:
        y = jax.lax.all_gather(y, tp_axis, axis=0, tiled=True)[:T_loc]

    # shared experts: Megatron-TP over tp_axis on the FULL token block
    # (weights d_ff-sharded; one psum combines the partial features)
    if m.n_shared_experts > 0:
        gs = x2 @ params["ws_gate"]
        us = x2 @ params["ws_up"]
        ysh = (jax.nn.silu(gs) * us) @ params["ws_down"]
        if tp > 1:
            ysh = jax.lax.psum(ysh, tp_axis)
        y = y + ysh

    if dp_axes or ep_axes:
        axes = tuple(dict.fromkeys(tuple(dp_axes) + tuple(ep_axes)))
        aux = jax.lax.pmean(aux, axes)
    return y, aux


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _ep_axes(cfg: ModelConfig, par: Parallelism) -> Tuple[str, ...]:
    mesh = par.mesh
    if mesh is None:
        return ()
    E = e_store(cfg)
    for cand in (("data", "model"), ("model",), ("tp",)):
        axes = tuple(a for a in cand if a in mesh.shape)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if axes and n > 1 and E % n == 0:
            return axes
    return ()


def capacity(n_tokens_sub: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = math.ceil(max(1, n_tokens_sub) * m.top_k * m.capacity_factor
                  / m.n_routed_experts)
    return max(4, -(-c // 4) * 4)


def moe_apply(params, x: jax.Array, *, cfg: ModelConfig,
              par: Parallelism = NO_PARALLEL):
    """x: [B, S, d] -> (y [B, S, d], aux loss scalar)."""
    B, S, d = x.shape
    m = cfg.moe
    mesh = par.mesh
    if mesh is None:
        cap = capacity(B * S, cfg)
        y, aux = _moe_block(x.reshape(B * S, d), params, cfg=cfg, cap=cap,
                            ep_axes=(), tp_axis=None, dp_axes=())
        return y.reshape(B, S, d), aux

    ep_axes = _ep_axes(cfg, par)
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape
               and mesh.shape[a] > 1)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b_shard = dp if (dp_size > 1 and B % dp_size == 0) else ()
    T_loc = (B // dp_size if b_shard else B) * S
    tp_axis = "model" if ("model" in mesh.shape
                          and mesh.shape["model"] > 1) else None
    tp = mesh.shape.get(tp_axis, 1) if tp_axis else 1
    cap = capacity(-(-T_loc // tp), cfg)

    def body(xb, pb):
        x2 = xb.reshape(-1, d)
        y, aux = _moe_block(x2, pb, cfg=cfg, cap=cap, ep_axes=ep_axes,
                            tp_axis=tp_axis, dp_axes=b_shard)
        return y.reshape(xb.shape), aux

    in_x = P(b_shard if len(b_shard) > 1 else (b_shard[0] if b_shard else None),
             None, None)
    pspecs = _param_specs(params, cfg, ep_axes, tp_axis)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(in_x, pspecs),
        out_specs=(in_x, P()), check_vma=False)(x, params)
    return y, aux


def _param_specs(params, cfg, ep_axes, tp_axis):
    """PartitionSpecs for the MoE param dict (shard_map view == pjit view)."""
    ep = (ep_axes if len(ep_axes) > 1 else
          (ep_axes[0] if ep_axes else None))
    m = cfg.moe
    ds = m.n_shared_experts * m.d_expert
    specs = {}
    for name in params:
        if name in ("w_gate", "w_up", "w_down"):
            specs[name] = P(ep, None, None)
        elif name in ("ws_gate", "ws_up"):
            specs[name] = P(None, tp_axis)
        elif name == "ws_down":
            specs[name] = P(tp_axis, None)
        else:                       # router, e_bias: replicated
            specs[name] = P(*([None] * params[name].ndim))
    return specs


def moe_tp_axis(cfg: ModelConfig, par: Parallelism) -> Optional[str]:
    mesh = par.mesh
    if mesh is None:
        return None
    ds = cfg.moe.n_shared_experts * cfg.moe.d_expert
    if ("model" in mesh.shape and mesh.shape["model"] > 1
            and (ds == 0 or ds % mesh.shape["model"] == 0)):
        return "model"
    return None


def moe_param_pspecs(cfg: ModelConfig, par: Parallelism):
    """Pjit-level shardings for MoE params (matches shard_map in_specs)."""
    m = cfg.moe
    ep_axes = _ep_axes(cfg, par)
    dummy = {"w_gate": 3, "w_up": 3, "w_down": 3, "router": 2}
    if m.router == "sigmoid_bias":
        dummy["e_bias"] = 1
    if m.n_shared_experts > 0:
        dummy.update({"ws_gate": 2, "ws_up": 2, "ws_down": 2})
    fake = {k: jnp.zeros((1,) * v) for k, v in dummy.items()}
    return _param_specs(fake, cfg, ep_axes, moe_tp_axis(cfg, par))
