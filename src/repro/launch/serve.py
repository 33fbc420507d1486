"""Serving launcher: spins up the continuous-batching engine on a model
and drives a synthetic request workload, reporting TTFT / TPOT /
throughput — the serving-side end-to-end driver.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 16 --input-len 64 --output-len 32
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.checkpoint import store as ckpt_lib
from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.launch import steps as steps_lib
from repro.serving.engine import (Engine, EngineStallError, Request,
                                  RequestState)
from repro.serving.faults import FaultPlan
from repro.serving.sampler import SampleParams


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--input-len", type=int, default=64)
    ap.add_argument("--output-len", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-budget", type=int, default=4096,
                    help="max padded prefill tokens admitted per step")
    ap.add_argument("--contiguous", action="store_true",
                    help="disable the paged KV cache (per-slot dense)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-cache tokens per KV block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged-cache pool size (default slots*capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: prompt tokens fed per engine "
                    "step (0 = whole-prompt prefill)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="track-speculative decoding: draft K tokens per "
                    "engine step and verify them in one forward (PT "
                    "configs with a paged cache only; 0 = off)")
    ap.add_argument("--draft-tracks", type=int, default=0,
                    help="tracks the drafter runs on (default n_tracks/2)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["float32", "int8"],
                    help="paged KV storage dtype: int8 stores 8-bit "
                    "payloads + per-token fp32 scales (dequant fused "
                    "into the decode kernels); unsupported layouts fall "
                    "back to fp automatically")
    ap.add_argument("--weight-dtype", default=None,
                    choices=["float32", "int8"],
                    help="serving weight dtype: int8 quantizes matmul "
                    "weights rowwise at engine load (norms/embeddings "
                    "stay fp)")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="async pipelined stepping: dispatch up to this "
                    "many engine steps ahead of the packed device-to-"
                    "host transfer (0 = classic blocking loop)")
    ap.add_argument("--preplan", action="store_true",
                    help="AOT-compile the per-bucket decode/verify step "
                    "programs at engine build so the dispatch path "
                    "never traces")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-addressed prefix caching "
                    "(on by default for paged full-attention configs)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared tokens to every "
                    "prompt (system-prompt workload; exercises the "
                    "prefix cache)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue: submissions past this "
                    "many waiting requests are shed as REJECTED "
                    "(default unbounded)")
    ap.add_argument("--watchdog-patience", type=int, default=25,
                    help="consecutive no-progress engine steps before "
                    "the stall watchdog preempts or sheds the head")
    ap.add_argument("--max-preemptions", type=int, default=8,
                    help="evictions a request survives before it is "
                    "REJECTED (termination guarantee under pressure)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request submit-to-done budget in seconds "
                    "(exceeding it yields TIMED_OUT)")
    ap.add_argument("--priority-mix", type=int, default=1,
                    help="cycle request priorities 0..N-1 across the "
                    "workload (N>1 exercises preempt-and-recompute)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault-injection "
                    "schedule (chaos drills)")
    ap.add_argument("--fault-alloc-p", type=float, default=0.0,
                    help="per-call probability of an injected KV "
                    "allocation failure")
    ap.add_argument("--fault-transfer-p", type=float, default=0.0,
                    help="per-call probability of an injected device-to-"
                    "host transfer failure (the step retries)")
    ap.add_argument("--fault-slow-p", type=float, default=0.0,
                    help="per-step probability of an injected slow step")
    ap.add_argument("--fault-slow-s", type=float, default=0.05,
                    help="sleep per injected slow step (seconds)")
    ap.add_argument("--fault-max", type=int, default=None,
                    help="cap on total injected faults (a storm that "
                    "clears; default unbounded)")
    return ap.parse_args(argv)


def init_params(cfg, seed: int):
    """Random-init weights on the default device, in one compiled
    program (no host copy, no per-op dispatch at full width)."""
    fns = steps_lib.model_fns(cfg)
    return jax.jit(lambda key: fns["init"](key, cfg))(
        jax.random.PRNGKey(seed))


def build_engine(args: argparse.Namespace, cfg, params) -> Engine:
    """The engine the launcher's options describe."""
    plan = None
    if args.fault_alloc_p or args.fault_transfer_p or args.fault_slow_p:
        plan = FaultPlan(seed=args.fault_seed, alloc_p=args.fault_alloc_p,
                         transfer_p=args.fault_transfer_p,
                         slow_p=args.fault_slow_p, slow_s=args.fault_slow_s,
                         max_faults=args.fault_max)
        print(f"[serve] fault injection armed: seed={plan.seed} "
              f"alloc_p={plan.alloc_p} transfer_p={plan.transfer_p} "
              f"slow_p={plan.slow_p} max={plan.max_faults}")
    max_seq = args.shared_prefix + args.input_len + args.output_len + 8
    return Engine(cfg, params, max_slots=args.slots, max_seq_len=max_seq,
                  max_waiting_prefill_tokens=args.prefill_budget,
                  paged=not args.contiguous, block_size=args.block_size,
                  num_blocks=args.num_blocks,
                  prefill_chunk=args.prefill_chunk,
                  speculate_k=args.speculate_k,
                  draft_tracks=args.draft_tracks,
                  prefix_cache=not args.no_prefix_cache,
                  kv_dtype=args.kv_dtype,
                  weight_dtype=args.weight_dtype,
                  max_queue=args.max_queue,
                  watchdog_patience=args.watchdog_patience,
                  max_preemptions=args.max_preemptions,
                  fault_plan=plan,
                  pipeline_depth=args.pipeline_depth,
                  preplan=args.preplan)


def submit_workload(eng: Engine, args: argparse.Namespace, cfg,
                    seed: int) -> List[Request]:
    """Submit ``args.requests`` prompts of random tokens drawn from
    ``seed``: a shared prefix of ``args.shared_prefix`` tokens, then
    ``args.input_len`` tokens of each request's own."""
    rng = np.random.default_rng(seed)
    sp = SampleParams(temperature=args.temperature)
    shared = rng.integers(1, cfg.vocab_size,
                          size=(args.shared_prefix,)).tolist()
    reqs = []
    for i in range(args.requests):
        prompt = shared + rng.integers(1, cfg.vocab_size,
                                       size=(args.input_len,)).tolist()
        reqs.append(eng.submit(prompt, args.output_len, params=sp,
                               priority=i % max(1, args.priority_mix),
                               deadline_s=args.deadline_s))
    return reqs


def main() -> None:
    args = parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[serve] device: {dev.platform} {dev.device_kind} "
          f"x{jax.device_count()}")
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    params = init_params(cfg, args.seed)
    if args.ckpt_dir:
        state = ckpt_lib.restore(args.ckpt_dir, {"params": params})
        params = state["params"]
        print(f"[serve] loaded params from {args.ckpt_dir}")
    eng = build_engine(args, cfg, params)
    if args.preplan:
        print(f"[serve] pre-planned {eng.runner.plan_programs()} "
              f"per-bucket step programs")
    # capabilities report: one line per feature, with the gating reason
    # whenever a feature this architecture can't serve (or a requested
    # knob the engine had to drop) — quantization fallbacks included
    caps = eng.capabilities()
    if eng.runner.paged:
        kinds = eng.runner.kv.leaf_kinds()
        layout = ", ".join(f"{v} {k}" for k, v in sorted(kinds.items()))
        print(f"[serve] cache layout: {layout or 'no cache leaves'}")
    for name, c in caps.items():
        state = ("on" if c["active"] else
                 "off" if c["supported"] else "unsupported")
        line = f"[serve] capability {name}: {state}"
        if c["reason"] and (not c["supported"] or not c["active"]):
            line += f" ({c['reason']})"
        print(line)
    if args.speculate_k and not eng.runner.speculate_k:
        print("[serve] --speculate-k ignored: "
              f"{caps['speculative']['reason'] or 'engine is not paged'}")
    if eng.runner.kv_dtype or eng.runner.weight_dtype:
        st = eng.runner.cache_stats()
        extra = (f", pool {st['pool_bytes'] / 1e6:.1f} MB "
                 f"({st['bytes_per_block']} B/block)"
                 if st["mode"] == "paged" else "")
        print(f"[serve] quantized: kv={st.get('kv_dtype', 'float32')} "
              f"weights={st['weight_dtype']} "
              f"({st['quantized_weight_leaves']} leaves){extra}")
    t0 = time.perf_counter()
    reqs = submit_workload(eng, args, cfg, args.seed)
    try:
        eng.run()
    except EngineStallError as e:
        print(f"[serve] STALL: {e}")
        for k, v in e.diagnostic.items():
            print(f"[serve]   {k} = {v}")
    wall = time.perf_counter() - t0

    m = eng.metrics.summary()
    print(f"[serve] {cfg.name}: {args.requests} reqs x "
          f"({args.input_len} in / {args.output_len} out), "
          f"slots={args.slots}")
    print(f"[serve] throughput {m['throughput_tok_s']:9.1f} tok/s   "
          f"wall {wall:.2f}s   engine steps {eng.steps_run}   "
          f"prefill variants {len(eng.runner.prefill_shapes)}   "
          f"cache {eng.runner.cache_stats()['mode']}")
    print(f"[serve] TTFT ms: p50 {m['ttft_ms']['p50']:8.1f}  "
          f"p90 {m['ttft_ms']['p90']:8.1f}  p99 {m['ttft_ms']['p99']:8.1f}")
    print(f"[serve] TPOT ms: p50 {m['tpot_ms']['p50']:8.1f}  "
          f"p90 {m['tpot_ms']['p90']:8.1f}  p99 {m['tpot_ms']['p99']:8.1f}")
    if eng.runner.speculate_k:
        print(f"[serve] speculative: K={eng.runner.speculate_k} on "
              f"{eng.runner.draft_tracks} draft tracks | acceptance "
              f"{m['acceptance_rate']:.2f} (ema {m['acceptance_ema']:.2f}) "
              f"over {m['spec_steps']} spec steps")
    if eng.runner.paged:
        u = eng.runner.kv.utilization()
        if eng.runner.prefix_cache and u["prefix_queries"]:
            hit = (u["prefix_hit_tokens"]
                   / max(1, u["prefix_lookup_tokens"]))
            print(f"[serve] prefix cache: {u['prefix_hit_tokens']} of "
                  f"{u['prefix_lookup_tokens']} prompt tokens served "
                  f"from cache ({100 * hit:.0f}%), "
                  f"{u['cached_free_blocks']} cached blocks retained, "
                  f"{u['cow_copies']} CoW copies")
    by_state = {}
    for r in reqs:
        by_state[r.state.value] = by_state.get(r.state.value, 0) + 1
    pressure = (m["preemptions"] or m["rejected"] or m["shed"]
                or m["timed_out"] or m["watchdog_fires"]
                or m["transfer_faults"])
    if pressure or by_state.keys() != {RequestState.DONE.value}:
        states = ", ".join(f"{k}={v}" for k, v in sorted(by_state.items()))
        print(f"[serve] robustness: {states} | "
              f"preemptions {m['preemptions']} (resumes {m['resumes']}), "
              f"shed {m['shed']}, rejected {m['rejected']}, "
              f"timed_out {m['timed_out']}, watchdog {m['watchdog_fires']}, "
              f"transfer_faults {m['transfer_faults']}")
    if eng.faults is not None:
        fs = eng.faults.summary()
        print(f"[serve] faults injected: {fs['injected']} "
              f"(alloc {fs['alloc_faults']}, transfer "
              f"{fs['transfer_faults']}, slow {fs['slow_steps']})")


if __name__ == "__main__":
    main()
