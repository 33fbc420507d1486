"""Compile a configuration's chunk and decode programs for a described
TPU v5e (no chip attached) and print what each needs in device memory.

  JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/compile_v5e.py \
      --arch pt-6b-d4 --slots 16 --num-blocks 2048 --rows 1,8,16

Arguments are shapes only: no weight or cache is made, so a full-width
model compiles here in the memory the compiler needs.
"""
from __future__ import annotations

import argparse
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch import steps as steps_lib
from repro.serving.engine import ModelRunner

GiB = 2 ** 30


def abstract(tree, sh, resize=None):
    def one(x):
        shape = tuple(x.shape)
        if resize is not None:
            shape = resize(shape)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sh)
    return jax.tree_util.tree_map(one, tree)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--n-layers", type=int, default=0)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--num-blocks", type=int, required=True)
    ap.add_argument("--max-seq-len", type=int, default=2560)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--rows", default="")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    cfg = get_config(args.arch)
    if args.n_layers:
        cfg = cfg.replace(n_layers=args.n_layers)
    fns = steps_lib.model_fns(cfg)
    p_shape = jax.eval_shape(lambda k: fns["init"](k, cfg),
                             jax.random.PRNGKey(0))
    w_bytes = sum(l.size * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(p_shape))
    small = 6   # a pool this small is made for real on the CPU, then resized
    runner = ModelRunner(cfg, p_shape, max_slots=args.slots,
                         max_seq_len=args.max_seq_len,
                         block_size=args.block_size, num_blocks=small,
                         prefill_chunk=args.chunk)
    nb = args.num_blocks + 1

    def resize(shape):
        return tuple(nb if d == small + 1 else d for d in shape)

    cache = abstract(runner.cache, sh, resize)
    pool = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(cache))
    params = abstract(p_shape, sh)
    print(f"{args.arch}: layers {cfg.n_layers}, weights {w_bytes / GiB:.3f} "
          f"GiB, pool {nb} blocks = {pool / GiB:.3f} GiB")
    bps = runner.kv.blocks_per_seq
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def report(name, fn, *a, **kw):
        t0 = time.perf_counter()
        try:
            c = fn.lower(*a, **kw).compile()
        except Exception as e:   # the chip's compiler refused it
            msg = str(e).splitlines()[0]
            print(f"{name}: REFUSED after {time.perf_counter() - t0:.1f} s:"
                  f" {msg[:300]}", flush=True)
            return
        m = c.memory_analysis()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s | "
              f"temp {m.temp_size_in_bytes / GiB:.3f} GiB, args "
              f"{m.argument_size_in_bytes / GiB:.3f}, out "
              f"{m.output_size_in_bytes / GiB:.3f}, alias "
              f"{m.alias_size_in_bytes / GiB:.3f} | weights + pool + temp "
              f"{(w_bytes + pool + m.temp_size_in_bytes) / GiB:.3f} GiB",
              flush=True)

    B = args.slots
    report(f"decode[{B}]", runner._decode, params, cache, s((B,), i32),
           s((B,), i32), s((B,), jnp.bool_), s((B, bps), i32), s((B,), u32),
           s((B,), i32), s((B,), f32), s((B,), i32), s((B,), f32),
           s((B,), i32), s((B,), i32), max_len=None)
    rows = [int(r) for r in args.rows.split(",") if r] or [B]
    C = args.chunk
    for n in rows:
        report(f"chunk[{n}x{C}]", runner._chunk, params, cache,
               s((n, C), i32), s((n,), i32), s((n, bps), i32), s((n,), i32),
               s((n,), i32), s((n,), u32), s((n,), i32), s((n,), f32),
               s((n,), i32), s((n,), f32))


if __name__ == "__main__":
    main()
