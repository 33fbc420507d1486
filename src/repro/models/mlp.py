"""Feed-forward layers: SwiGLU / GeGLU / GELU / squared-ReLU (+ init)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import quant
from repro.runtime.parallel import Parallelism, NO_PARALLEL


def _dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def mlp_init(key, kind: str, d_model: int, d_ff: int, dtype=jnp.float32):
    if kind in ("swiglu", "geglu"):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wi_gate": _dense_init(k1, d_model, d_ff, dtype),
                "wi_up": _dense_init(k2, d_model, d_ff, dtype),
                "wo": _dense_init(k3, d_ff, d_model, dtype)}
    if kind in ("gelu", "sqrelu", "relu"):
        k1, k2 = jax.random.split(key, 2)
        return {"wi_up": _dense_init(k1, d_model, d_ff, dtype),
                "wo": _dense_init(k2, d_ff, d_model, dtype)}
    if kind == "none":
        return {}
    raise ValueError(f"unknown mlp {kind!r}")


def _act(kind: str, g: jax.Array) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(g)
    if kind == "geglu":
        return jax.nn.gelu(g, approximate=True)
    if kind == "gelu":
        return jax.nn.gelu(g, approximate=True)
    if kind == "sqrelu":
        r = jax.nn.relu(g)
        return r * r
    if kind == "relu":
        return jax.nn.relu(g)
    raise ValueError(f"unknown activation {kind!r}")


@jax.named_scope("mlp")
def mlp_apply(params, x: jax.Array, kind: str,
              par: Parallelism = NO_PARALLEL,
              use_pallas: bool = False) -> jax.Array:
    """x: [..., d_model] -> [..., d_model].  Hidden dim TP-sharded.

    int8 weights (``QuantTensor`` leaves) route through ``quant.matmul``;
    with ``use_pallas`` the 2-D (unstacked) case runs the fused-dequant
    Pallas matmul, otherwise the weight is dequantized in-register by XLA.
    """
    if kind == "none":
        return x
    kern = use_pallas and par.mesh is None
    mm = lambda a, w: quant.matmul(a, w, use_kernel=kern)
    batch_dims = ("batch",) + ("seq",) * (x.ndim - 2)
    if kind in ("swiglu", "geglu"):
        g = mm(x, params["wi_gate"])
        u = mm(x, params["wi_up"])
        h = _act(kind, g) * u
    else:
        h = _act(kind, mm(x, params["wi_up"]))
    h = par.cs(h, *batch_dims, "d_ff")
    out = mm(h, params["wo"])
    return par.cs(out, *batch_dims, "d_model")
