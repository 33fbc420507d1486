"""Weights made by the benchmark from ``--seed``.

Every weight is a pure function of (seed, role, layer): the program's
parameter tree is filled from it on the device in one jitted call, and
the reference regenerates any layer's slice from the same function, so
it never reads a weight the program holds.  Values are uniform with the
stated standard deviation, rounded to the dtype they are served in.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

# role -> (axes of the per-track shape that are summed over by the
# matmul, i.e. its fan-in; None for a norm scale)
LAYER_ROLES: Dict[str, Tuple[int, ...] | None] = {
    "ln1.scale": None,
    "ln2.scale": None,
    "mixer.wq": (0,),
    "mixer.wk": (0,),
    "mixer.wv": (0,),
    "mixer.wo": (0, 1),
    "mlp.wi_gate": (0,),
    "mlp.wi_up": (0,),
    "mlp.wo": (0,),
}
NORM_STD = 0.1      # norm scales enter as (1 + scale)


def layer_shapes(m: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Per-track shape of every layer role, from a configuration's
    ``model`` block."""
    d, H, KH, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return {"ln1.scale": (d,), "ln2.scale": (d,),
            "mixer.wq": (d, H, hd), "mixer.wk": (d, KH, hd),
            "mixer.wv": (d, KH, hd), "mixer.wo": (H, hd, d),
            "mlp.wi_gate": (d, ff), "mlp.wi_up": (d, ff),
            "mlp.wo": (ff, d)}


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _role_key(key: jax.Array, role: str, layer) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(role.encode()))
    return jax.random.fold_in(key, layer)


def _values(key, shape, std, dtype) -> jax.Array:
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (u * (math.sqrt(3.0) * std)).astype(dtype)


def matrix_dtype(m: Dict[str, Any]):
    return jnp.dtype(m["dtype"])


def layer_slice(key: jax.Array, m: Dict[str, Any], role: str, layer
                ) -> jax.Array:
    """Layer ``layer``'s weight ``role`` for every track:
    [n_tracks, *per-track shape], in the dtype it is served in."""
    shape = layer_shapes(m)[role]
    fan_axes = LAYER_ROLES[role]
    if fan_axes is None:
        std, dtype = NORM_STD, jnp.float32
    else:
        std = 1.0 / math.sqrt(math.prod(shape[a] for a in fan_axes))
        dtype = matrix_dtype(m)
    return _values(_role_key(key, role, layer), (m["n_tracks"],) + shape,
                   std, dtype)


def global_weight(key: jax.Array, m: Dict[str, Any], role: str) -> jax.Array:
    d, V = m["d_model"], m["vocab_size"]
    if role == "embed":
        return _values(_role_key(key, role, 0), (V, d), 1.0, matrix_dtype(m))
    if role == "head":
        return _values(_role_key(key, role, 0), (d, V), 1.0 / math.sqrt(d),
                       matrix_dtype(m))
    if role == "final_norm.scale":
        return _values(_role_key(key, role, 0), (d,), NORM_STD, jnp.float32)
    raise KeyError(role)


def _path_keys(path: Sequence[Any]) -> Tuple[Any, ...]:
    out = []
    for p in path:
        out.append(getattr(p, "key", getattr(p, "idx", None)))
    return tuple(out)


def _leaf(key, m, keys, shape, dtype) -> jax.Array:
    L, n = m["n_layers"], m["n_tracks"]
    if keys in (("embed",), ("head",)) or keys == ("final_norm", "scale"):
        w = global_weight(key, m, ".".join(keys))
    elif keys[0] == "blocks":
        # Parallel-Track stacking: [L/D, D, n_tracks, *per-track shape]
        role = ".".join(keys[1:])
        w = jax.vmap(lambda l: layer_slice(key, m, role, l))(jnp.arange(L))
        w = w.reshape(shape)
    elif keys[:2] == ("unit", 0) and n == 1:
        # one scanned stack of single-track layers: [L, *shape]
        role = ".".join(keys[2:])
        w = jax.vmap(lambda l: layer_slice(key, m, role, l)[0])(jnp.arange(L))
    else:
        raise SystemExit(f"parameter {keys} has a layout the benchmark's "
                         "weights and reference do not know")
    if w.shape != tuple(shape) or w.dtype != dtype:
        raise SystemExit(f"parameter {keys}: the program wants {shape} "
                         f"{dtype}, the configuration gives {w.shape} "
                         f"{w.dtype}")
    return w


def fill(seed: int, m: Dict[str, Any], shapes: Any) -> Any:
    """The program's parameter tree (``shapes``: its ``jax.eval_shape``),
    made on the default device in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        return treedef.unflatten([_leaf(key, m, _path_keys(p), l.shape,
                                        l.dtype) for p, l in flat])

    return make(base_key(seed))
