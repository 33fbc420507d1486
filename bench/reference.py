"""Plain reference for the decoder configurations of this benchmark.

A pre-norm decoder in float32 at the highest matmul precision:
RMSNorm with weight (1 + scale), grouped-query attention with half-split
RoPE, SwiGLU, untied output head.  ``n_tracks`` copies of each layer run
side by side on the same input and their outputs are averaged every
``block_depth`` layers (Parallel-Track, arXiv 2602.07306 Algorithm 1);
with one track this is the ordinary dense decoder.

It imports nothing of the program under test.  Weights come from
``weights.py`` and are regenerated here one layer at a time, so the
whole model never sits in memory at once in float32.

``control=True`` adds a second stream that computes every weight matmul
from float8 (e4m3) inputs, scaled per row of the activations and per
output column of the weights: the precision below the configuration's
bfloat16 that a later change might be tempted to serve in.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 256          # query rows per attention block
ROWS = 4               # sequences per reference call (padded)
E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round x through float8 e4m3 with one scale per slice along
    ``axis`` (the contraction axes), back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x: jax.Array, w: jax.Array, n_in: int, low: bool) -> jax.Array:
    """x [..., *in] @ w [*in, *out] over the ``n_in`` leading axes of w."""
    lead = x.shape[:x.ndim - n_in]
    k = int(np.prod(w.shape[:n_in]))
    out = w.shape[n_in:]
    x2 = x.reshape(lead + (k,))
    w2 = w.reshape((k, -1))
    if low:
        x2 = _fp8(x2, -1)
        w2 = _fp8(w2, 0)
    y = jnp.einsum("...k,kn->...n", x2, w2)
    return y.reshape(lead + out)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [k, S, heads, hd]; pos [S]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv                 # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v) -> jax.Array:
    """Causal GQA. q [b, S, H, hd]; k, v [b, S, KH, hd]."""
    b, S, H, hd = q.shape
    KH = k.shape[2]
    g = H // KH
    qg = q.reshape(b, S, KH, g, hd) * hd ** -0.5
    out = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(S, lo + Q_BLOCK)
        s = jnp.einsum("bqngd,bknd->bngqk", qg[:, lo:hi], k[:, :hi])
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("bngqk,bknd->bqngd", p, v[:, :hi]))
    return jnp.concatenate(out, axis=1).reshape(b, S, H, hd)


def _layer(h, w, pos, m, low):
    """One single-track layer. h [b, S, d]; w: role -> per-track array."""
    eps = m["norm_eps"]
    x = _rms(h, w["ln1.scale"], eps)
    q = _rope(_mm(x, w["mixer.wq"], 1, low), pos, m["rope_theta"])
    k = _rope(_mm(x, w["mixer.wk"], 1, low), pos, m["rope_theta"])
    v = _mm(x, w["mixer.wv"], 1, low)
    h = h + _mm(_attention(q, k, v), w["mixer.wo"], 2, low)
    x = _rms(h, w["ln2.scale"], eps)
    a = jax.nn.silu(_mm(x, w["mlp.wi_gate"], 1, low)) \
        * _mm(x, w["mlp.wi_up"], 1, low)
    return h + _mm(a, w["mlp.wo"], 1, low)


def _forward(key, tokens, want, m, control: bool):
    """Logits [M, V] at flat positions ``want`` of tokens [b, S]; with
    ``control``, also the float8 stream's."""
    b, S = tokens.shape
    L, n, D = m["n_layers"], m["n_tracks"], m["block_depth"]
    pos = jnp.arange(S)
    emb = W.global_weight(key, m, "embed").astype(jnp.float32)
    h0 = emb[tokens]                                            # [b, S, d]
    streams = (False, True) if control else (False,)
    hs = tuple(h0 for _ in streams)

    def block(r, hs):
        tracks = tuple(jnp.broadcast_to(h, (n,) + h.shape) for h in hs)
        for j in range(D):
            layer = r * D + j
            w = {role: W.layer_slice(key, m, role, layer).astype(jnp.float32)
                 for role in W.LAYER_ROLES}
            tracks = tuple(
                jax.vmap(lambda hh, ww, low=low: _layer(hh, ww, pos, m, low))(
                    t, w) for t, low in zip(tracks, streams))
        return tuple(jnp.mean(t, axis=0) for t in tracks)

    hs = jax.lax.fori_loop(0, L // D, block, hs)
    fn = W.global_weight(key, m, "final_norm.scale")
    head = W.global_weight(key, m, "head").astype(jnp.float32)
    out = []
    for h, low in zip(hs, streams):
        x = _rms(h.reshape(b * S, -1)[want], fn, m["norm_eps"])
        out.append(_mm(x, head, 1, low))
    return out


_JIT = jax.jit(_forward, static_argnums=(3, 4))


def _bucket(x: int, step: int) -> int:
    return -(-x // step) * step


def gaps(seed: int, m: Dict[str, Any], prompts: Sequence[Sequence[int]],
         outputs: Sequence[Sequence[int]], *, max_len: int, max_new: int,
         control: bool = False) -> Dict[str, np.ndarray]:
    """For each served token (output[i] of request r, predicted at
    position len(prompt) - 1 + i), how far its reference logit lies below
    the reference's best: ``served``.  With ``control``, the same for the
    token the float8 stream puts first: ``control``.  Requests run
    ``ROWS`` at a time in one shape, ``max_len`` positions and
    ``ROWS x max_new`` served tokens, so the reference compiles once per
    configuration."""
    if m["n_layers"] % m["block_depth"]:
        raise ValueError("n_layers must be a multiple of block_depth")
    key = W.base_key(seed)
    M = ROWS * max_new
    served, ctrl = [], []
    for lo in range(0, len(prompts), ROWS):
        group = range(lo, min(lo + ROWS, len(prompts)))
        tokens = np.zeros((ROWS, max_len), np.int32)
        want, toks = [], []
        for i, r in enumerate(group):
            seq = list(prompts[r]) + list(outputs[r][:-1])
            if len(seq) > max_len or len(outputs[r]) > max_new:
                raise ValueError(f"request {r} is longer than the "
                                 "reference's shape")
            tokens[i, :len(seq)] = seq
            L = len(prompts[r])
            want += [i * max_len + L - 1 + j for j in range(len(outputs[r]))]
            toks += list(outputs[r])
        want_p = np.asarray(want + [0] * (M - len(want)), np.int32)
        with jax.default_matmul_precision("highest"):
            res = _JIT(key, jnp.asarray(tokens), jnp.asarray(want_p),
                       _frozen(m), control)
        n = np.arange(len(want))
        ref = np.asarray(res[0], np.float64)[:len(want)]
        best = ref.max(axis=-1)
        served.append(best - ref[n, toks])
        if control:
            pick = np.asarray(res[1])[:len(want)].argmax(axis=-1)
            ctrl.append(best - ref[n, pick])
    out = {"served": np.concatenate(served)}
    if control:
        out["control"] = np.concatenate(ctrl)
    return out


class _frozen(dict):
    """A hashable model block, so ``jax.jit`` can take it as static."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items()
                                 if isinstance(v, (int, float, str)))))
