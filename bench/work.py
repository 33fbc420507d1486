"""Operations and bytes that the served model needs, from a configuration
file's ``model`` block alone.  Fixed by the model and the traffic, not by
the code that serves it, so a faster implementation raises the shares
that divide by these without changing the counts.

FLOPs count multiply and add as two.  A token's matmul FLOPs are
2 x the weights it multiplies: every layer matrix of every track and the
output head (the embedding table is a lookup, not a matmul; this is the
2·N·D of ``roofline/analysis.py`` with N the matmul weights).  Attention
adds 4 x heads x head_dim FLOPs per layer and track for each position a
query attends to (QK^T and PV).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_matmul_params(m: Dict[str, Any]) -> int:
    d, H, KH, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return 2 * d * H * hd + 2 * d * KH * hd + 3 * d * ff


def matmul_params(m: Dict[str, Any]) -> int:
    return (m["n_layers"] * m["n_tracks"] * layer_matmul_params(m)
            + m["d_model"] * m["vocab_size"])


def total_params(m: Dict[str, Any]) -> int:
    norms = m["n_layers"] * m["n_tracks"] * 2 * m["d_model"] + m["d_model"]
    return matmul_params(m) + m["vocab_size"] * m["d_model"] + norms


def weight_bytes(m: Dict[str, Any]) -> int:
    """Bytes of every weight as served: matrices in the model's dtype,
    norm scales in float32."""
    norms = m["n_layers"] * m["n_tracks"] * 2 * m["d_model"] + m["d_model"]
    mats = matmul_params(m) + m["vocab_size"] * m["d_model"]
    return mats * DTYPE_BYTES[m["dtype"]] + 4 * norms


def kv_bytes_per_token(m: Dict[str, Any]) -> int:
    return (2 * m["n_layers"] * m["n_tracks"] * m["n_kv_heads"]
            * m["head_dim"] * DTYPE_BYTES[m["dtype"]])


def attn_flops(m: Dict[str, Any], positions) -> float:
    """Attention FLOPs for queries that attend to ``positions`` positions
    in all (a sum over queries)."""
    return (4.0 * m["n_layers"] * m["n_tracks"] * m["n_heads"]
            * m["head_dim"] * float(positions))


def tokens_flops(m: Dict[str, Any], tokens, positions) -> float:
    """``tokens`` query tokens that attend to ``positions`` in all."""
    return 2.0 * matmul_params(m) * float(tokens) + attn_flops(m, positions)


def span_positions(start, count) -> np.ndarray:
    """Positions attended to by ``count`` tokens written at
    start, start+1, ...: sum of (p + 1)."""
    start, count = np.asarray(start, np.float64), np.asarray(count, np.float64)
    return count * start + count * (count + 1) / 2


def decode_call(m: Dict[str, Any], pos) -> Dict[str, float]:
    """What one decode call needs for the active rows at cache positions
    ``pos`` (each writes its new token at ``pos`` and attends to pos + 1
    positions): FLOPs, and bytes of weights read once, the rows' live
    KV read and their new KV written.  The embedding table is read only
    at the rows' token ids."""
    pos = np.asarray(pos, np.float64)
    rows = len(pos)
    dt = DTYPE_BYTES[m["dtype"]]
    kvb = kv_bytes_per_token(m)
    flops = tokens_flops(m, rows, np.sum(pos + 1))
    w = weight_bytes(m) - m["vocab_size"] * m["d_model"] * dt \
        + rows * m["d_model"] * dt
    nbytes = w + kvb * float(np.sum(pos)) + kvb * rows
    return {"flops": flops, "bytes": nbytes}


def least_time(flops: float, nbytes: float, peaks: Dict[str, Any]) -> float:
    """The roofline bound: the larger of compute time and memory time at
    the chip's peaks."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
