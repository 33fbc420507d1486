"""Tables 6/7/9/10 analogue: TTFT and TPOT, dense-30B vs PT-30B
(D ∈ {2,4,8}), over the paper's input-length grid — from the analytical
roofline latency model (no GPUs here; see latency_model.py).

``--measure`` additionally times the real engine on reduced models
(CPU wall-clock): the relative dense-vs-PT effect at tiny scale.

``--paged`` / ``--contiguous`` run the toy-size serving smoke under a
FIXED HBM budget (the bytes a 2-slot contiguous cache costs) with a
mixed short/long workload, and append TTFT/TPOT/throughput, peak
concurrency and cache-utilization %% to ``--json`` (BENCH_serving.json
in CI) so the serving-perf trajectory is recorded per commit.

``--speculate`` runs the track-speculative toy smoke on a small PT
model: the same paged engine with and without ``speculate_k`` draft/
verify, mean TPOT + acceptance rate appended to ``--json``.  The tracks
are tied (identical parameters) so the track-subset drafter agrees with
the full model — the trained-model upper bound, reported honestly next
to the random-init (untied) agreement rate.

``--prefix`` measures shared-prefix TTFT cold vs warm (content-addressed
prefix cache), and ``--fork`` the n-way copy-on-write fork scenario —
both appended to ``--json`` under ``prefix_cache`` / ``fork``.

``--quantized`` reruns the fixed-HBM smoke with int8 KV + int8 weights
against fp at the SAME pool byte budget, recording the concurrent-slot
gain and the mean-TPOT delta under ``quantized``.

``--overload`` drives an oversubscribed pool with mixed priorities and
a bounded queue through the robustness layer (preempt-and-recompute,
overload shedding), recording completion / preemption / shed counts
under ``overload``.

``--arch {mla,window,ssm}`` serves one reduced non-GQA architecture
(MLA latents / sliding-window rings / SSM state) through the layout-
polymorphic paged engine, recording TTFT and peak blocks-in-use under
``arch_<kind>`` — the architecture-zoo serving trajectory per commit.
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks.latency_model import decode_token_time, prefill_time
from repro.configs import get_config

INPUT_LENS = (1024, 2048, 4096, 8192, 16384, 63488)


def ttft_table() -> list:
    models = {"dense": get_config("dense-30b")}
    for d in (2, 4, 8):
        models[f"pt_d{d}"] = get_config(f"pt-30b-d{d}")
    rows = []
    print("input_len," + ",".join(f"{m}_ttft_ms" for m in models))
    for L in INPUT_LENS:
        row = {"input_len": L}
        for name, cfg in models.items():
            row[name] = prefill_time(cfg, L, batch=1) * 1e3
        rows.append(row)
        print(f"{L}," + ",".join(f"{row[m]:.2f}" for m in models))
    return rows


def tpot_table() -> list:
    models = {"dense": get_config("dense-30b")}
    for d in (2, 4, 8):
        models[f"pt_d{d}"] = get_config(f"pt-30b-d{d}")
    rows = []
    print("input_len," + ",".join(f"{m}_tpot_ms" for m in models))
    for L in INPUT_LENS:
        row = {"input_len": L}
        for name, cfg in models.items():
            row[name] = decode_token_time(cfg, L, batch=1) * 1e3
        rows.append(row)
        print(f"{L}," + ",".join(f"{row[m]:.3f}" for m in models))
    return rows


def measured(quick: bool = True) -> dict:
    """CPU wall-clock TTFT/TPOT through the real engine (reduced models),
    from the engine's own aggregate metrics (percentiles over requests)."""
    import jax
    import numpy as np
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine

    out = {}
    for name in ("dense-30b", "pt-30b-d8"):
        from repro.configs import reduced_config
        cfg = reduced_config(name)
        fns = steps_lib.model_fns(cfg)
        params = fns["init"](jax.random.PRNGKey(0), cfg)
        eng = Engine(cfg, params, max_slots=2, max_seq_len=96)
        rng = np.random.default_rng(0)
        for _ in range(4):
            eng.submit(rng.integers(1, cfg.vocab_size, 32).tolist(), 16)
        eng.run()
        m = eng.metrics.summary()
        out[name] = {
            "ttft_ms": m["ttft_ms"]["p50"],
            "ttft_p99_ms": m["ttft_ms"]["p99"],
            "tpot_ms": m["tpot_ms"]["p50"],
            "tpot_p99_ms": m["tpot_ms"]["p99"],
            "throughput_tok_s": m["throughput_tok_s"],
        }
        print(f"measured,{name},ttft_p50 {out[name]['ttft_ms']:.1f} ms,"
              f"tpot_p50 {out[name]['tpot_ms']:.2f} ms,"
              f"{out[name]['throughput_tok_s']:.1f} tok/s")
    return out


def bench_smoke(paged: bool, json_path: str | None = None) -> dict:
    """Toy-size serving smoke at a FIXED HBM budget: the bytes a 2-slot
    contiguous cache reserves.  Paged mode spends the same bytes on a
    shared block pool (+ chunked prefill), so mixed short/long traffic
    runs many more concurrent requests and short TTFT stays flat while a
    long prefill is in flight."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine

    cfg = reduced_config("tinyllama-1.1b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    S, bs, base_slots = 96, 8, 2
    budget_blocks = base_slots * S // bs          # == 2-slot contiguous HBM
    if paged:
        eng = Engine(cfg, params, max_slots=8, max_seq_len=S, paged=True,
                     block_size=bs, num_blocks=budget_blocks,
                     prefill_chunk=16)
    else:
        eng = Engine(cfg, params, max_slots=base_slots, max_seq_len=S,
                     paged=False)

    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, 64).tolist(), 8)]
    for _ in range(10):                           # short stream behind it
        reqs.append(eng.submit(rng.integers(1, cfg.vocab_size, 8).tolist(),
                               8))
    peak_util = 0.0
    for _ in range(10_000):                       # capped like Engine.run
        if not eng.scheduler.has_work():
            break
        if eng.step() == 0 and not eng.scheduler.queue:
            break
        if paged:
            u = eng.runner.kv.utilization()
            peak_util = max(peak_util, u["used_blocks"] / u["num_blocks"])
        else:
            busy = sum(int(eng._pos[s]) for s, r in
                       eng.scheduler.active_slots())
            peak_util = max(peak_util, busy / (eng.max_slots * S))
    m = eng.metrics.summary()
    short = np.asarray([r.ttft for r in reqs[1:]]) * 1e3
    out = {
        "mode": "paged" if paged else "contiguous",
        "hbm_budget_tokens": base_slots * S,
        "max_slots": eng.max_slots,
        "max_active": m["max_active"],
        "throughput_tok_s": m["throughput_tok_s"],
        "ttft_ms": m["ttft_ms"],
        "tpot_ms": m["tpot_ms"],
        "short_ttft_p50_ms": float(np.percentile(short, 50)),
        "cache_utilization_pct": round(100 * peak_util, 1),
        "prefill_chunk": eng.runner.prefill_chunk,
        "cache": eng.runner.cache_stats(),
    }
    print(f"smoke,{out['mode']},max_active {out['max_active']},"
          f"short_ttft_p50 {out['short_ttft_p50_ms']:.1f} ms,"
          f"util {out['cache_utilization_pct']:.1f}%,"
          f"{out['throughput_tok_s']:.1f} tok/s")
    if json_path:
        _merge_json(json_path, out["mode"], out)
    return out


def _merge_json(json_path: str, key: str, out: dict) -> None:
    """Merge one smoke's result into the benchmark JSON.

    Robust read-modify-write: a corrupt / partially-written / unreadable
    existing file is discarded instead of crashing the benchmark (CI
    kills mid-write leave exactly that), and the updated document lands
    via temp-file + ``os.replace`` so a reader or a killed run never
    observes a half-written file."""
    merged: dict = {}
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):
                merged = loaded
        except (json.JSONDecodeError, OSError, UnicodeDecodeError):
            pass                       # corrupt/unreadable: start fresh
    merged[key] = out
    if "paged" in merged and "contiguous" in merged:
        merged["slots_gain_at_fixed_hbm"] = (
            merged["paged"]["max_active"]
            / max(1, merged["contiguous"]["max_active"]))
    tmp = json_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=2)
    os.replace(tmp, json_path)         # atomic on POSIX + Windows


def bench_prefix(json_path: str | None = None) -> dict:
    """Shared-prefix smoke: TTFT of a cold prefill vs requests whose
    prompt shares a cached block-aligned prefix (system prompt reuse).
    Warm requests skip prefill for the matched span — only the short
    tail runs through the chunk program — so warm TTFT collapses toward
    the per-step overhead.  Compile variants are warmed up on a separate
    prefix first, so the timed cold/warm split measures prefill work,
    not tracing."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine

    cfg = reduced_config("tinyllama-1.1b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    bs, plen, tail = 8, 120, 8
    eng = Engine(cfg, params, max_slots=4, max_seq_len=160, block_size=bs)
    rng = np.random.default_rng(0)

    def prompt(prefix):
        return prefix + rng.integers(1, cfg.vocab_size, tail).tolist()

    warmup_prefix = rng.integers(1, cfg.vocab_size, plen).tolist()
    shared_prefix = rng.integers(1, cfg.vocab_size, plen).tolist()
    # compile warm-up: one cold prefill shape + one warm-tail chunk shape
    eng.submit(prompt(warmup_prefix), 4)
    eng.run()
    eng.submit(prompt(warmup_prefix), 4)
    eng.run()
    eng.metrics = type(eng.metrics)()
    cold = eng.submit(prompt(shared_prefix), 4)
    eng.run()
    warm = []
    for _ in range(6):
        warm.append(eng.submit(prompt(shared_prefix), 4))
        eng.run()
    u = eng.runner.kv.utilization()
    warm_ms = np.asarray([r.ttft for r in warm]) * 1e3
    out = {
        "prefix_len": plen,
        "tail_len": tail,
        "block_size": bs,
        "cold_ttft_ms": cold.ttft * 1e3,
        "warm_ttft_p50_ms": float(np.percentile(warm_ms, 50)),
        "warm_over_cold": float(np.percentile(warm_ms, 50)
                                / max(1e-9, cold.ttft * 1e3)),
        "warm_cached_prefix": [r.cached_prefix for r in warm],
        "prefix_queries": u["prefix_queries"],
        "prefix_hit_tokens": u["prefix_hit_tokens"],
        "cached_free_blocks": u["cached_free_blocks"],
    }
    print(f"prefix,cold_ttft {out['cold_ttft_ms']:.1f} ms,"
          f"warm_ttft_p50 {out['warm_ttft_p50_ms']:.1f} ms "
          f"({out['warm_over_cold']:.2f}x),hit "
          f"{u['prefix_hit_tokens']} tok")
    if json_path:
        _merge_json(json_path, "prefix_cache", out)
    return out


def bench_fork(json_path: str | None = None, n_forks: int = 3) -> dict:
    """n-way fork smoke: one prompt prefilled once, then forked into n
    sampling children that share every committed block (copy-on-write
    duplicates only the trailing partial block per diverging child).
    Records the block cost vs n+1 independent requests and proves the
    children ran zero extra prefill forwards."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine
    from repro.serving.sampler import SampleParams

    cfg = reduced_config("tinyllama-1.1b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    sp = SampleParams(temperature=1.0)
    eng = Engine(cfg, params, max_slots=n_forks + 1, max_seq_len=96,
                 block_size=8)
    rng = np.random.default_rng(0)
    # compile warm-up: prefill shape + full-batch decode + fork CoW copy
    parent = eng.submit(rng.integers(1, cfg.vocab_size, 32).tolist(), 24,
                        params=sp)
    eng.step()
    eng.fork(parent, n_forks)
    eng.run()
    eng.metrics = type(eng.metrics)()

    prompt = rng.integers(1, cfg.vocab_size, 32).tolist()
    parent = eng.submit(prompt, 24, params=sp)
    eng.step()                       # admit + prefill + first decode step
    kv = eng.runner.kv
    blocks_parent = kv.utilization()["used_blocks"]
    prefills_before = eng.runner.prefill_calls + eng.runner.chunk_calls
    children = eng.fork(parent, n_forks)
    blocks_forked = kv.utilization()["used_blocks"]
    eng.run()
    prefills_after = eng.runner.prefill_calls + eng.runner.chunk_calls
    outs = [parent.output] + [c.output for c in children]
    out = {
        "n_forks": n_forks,
        "parent_blocks": blocks_parent,
        "blocks_after_fork": blocks_forked,
        "naive_blocks": (n_forks + 1) * blocks_parent,
        "block_savings": (n_forks + 1) * blocks_parent - blocks_forked,
        "prefill_forwards_for_children": prefills_after - prefills_before,
        "cow_copies": kv.utilization()["cow_copies"],
        "distinct_outputs": len({tuple(o) for o in outs}),
        "tokens_served": sum(len(o) for o in outs),
    }
    print(f"fork,n={n_forks},blocks {blocks_forked} vs naive "
          f"{out['naive_blocks']},cow {out['cow_copies']},"
          f"child_prefills {out['prefill_forwards_for_children']},"
          f"distinct {out['distinct_outputs']}/{n_forks + 1}")
    if json_path:
        _merge_json(json_path, "fork", out)
    return out


def bench_speculate(json_path: str | None = None, speculate_k: int = 4,
                    draft_tracks: int = 1) -> dict:
    """Track-speculative toy smoke: plain paged decode vs draft/verify on
    the SAME small PT model (8 layers, 4 tracks, D=2, vocab 512).

    Tracks are tied (every track identical) so the d-track drafter agrees
    with the full model — speculative decoding's win scales with draft
    agreement, and tied tracks are the measured-upper-bound stand-in for
    a trained PT model whose tracks correlate.  The random-init (untied)
    agreement is also measured and reported, so the JSON records both
    ends of the acceptance range.  Both engines are warmed up first so
    compile time stays out of the TPOT numbers.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.common.types import LayerSpec, ModelConfig
    from repro.core.track import pt_ify
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine

    dense = ModelConfig(
        name="spec-bench", family="dense", n_layers=8, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512,
        layer_specs={"full": LayerSpec(mixer="gqa", mlp="swiglu")},
        pattern_unit=("full",), tie_embeddings=False, dtype="float32")
    cfg = pt_ify(dense, 4, 2, width_mult=8)
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    untied = jax.tree_util.tree_map(lambda x: x, params)
    params["blocks"] = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[:, :, :1], l.shape), params["blocks"])

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, 8).tolist() for _ in range(8)]

    def run(p, k):
        eng = Engine(cfg, p, max_slots=4, max_seq_len=96, block_size=8,
                     speculate_k=k, draft_tracks=draft_tracks)
        # warm-up replays the measured workload shape so every prefill
        # batch-size variant (4 slots filling, then 1..3 as slots free)
        # compiles before the timed region
        for prompt in prompts:
            eng.submit(prompt, max_new_tokens=8)
        eng.run()
        eng.metrics = type(eng.metrics)()
        for prompt in prompts:
            eng.submit(prompt, max_new_tokens=32)
        eng.run()
        return eng.metrics.summary()

    plain = run(params, 0)
    spec = run(params, speculate_k)
    untied_spec = run(untied, speculate_k)
    out = {
        "model": cfg.name,
        "speculate_k": speculate_k,
        "draft_tracks": draft_tracks,
        "n_tracks": cfg.pt.n_tracks,
        "plain_tpot_mean_ms": plain["tpot_ms"]["mean"],
        "spec_tpot_mean_ms": spec["tpot_ms"]["mean"],
        "tpot_speedup": (plain["tpot_ms"]["mean"]
                         / max(1e-9, spec["tpot_ms"]["mean"])),
        "acceptance_rate": spec["acceptance_rate"],
        "acceptance_ema": spec["acceptance_ema"],
        "spec_steps": spec["spec_steps"],
        "untied_acceptance_rate": untied_spec["acceptance_rate"],
        "throughput_tok_s": spec["throughput_tok_s"],
        "plain_throughput_tok_s": plain["throughput_tok_s"],
    }
    print(f"speculate,K={speculate_k},d={draft_tracks}/{cfg.pt.n_tracks},"
          f"tpot {plain['tpot_ms']['mean']:.2f} -> "
          f"{spec['tpot_ms']['mean']:.2f} ms "
          f"({out['tpot_speedup']:.2f}x),accept "
          f"{out['acceptance_rate']:.2f} (untied "
          f"{out['untied_acceptance_rate']:.2f})")
    if json_path:
        _merge_json(json_path, "speculate", out)
    return out


def bench_quantized(json_path: str | None = None) -> dict:
    """Quantized-serving smoke at a FIXED HBM budget: the fp engine gets
    a small block pool; the int8 engine spends the SAME bytes on int8
    payload + per-token fp32 scale blocks (~3.7x the blocks at head_dim
    64 / fp32), so the same mixed workload runs far more concurrent
    decode slots.  Mean TPOT is recorded next to the concurrency gain so
    the dequant overhead of the fused kernels is visible per commit."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.cache import PagedKVCache
    from repro.serving.engine import Engine

    cfg = reduced_config("tinyllama-1.1b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    S, bs, slots = 96, 8, 16
    budget_blocks = 24              # == the 2-slot contiguous HBM budget

    def run(kv_dtype, num_blocks, weight_dtype=None):
        eng = Engine(cfg, params, max_slots=slots, max_seq_len=S,
                     block_size=bs, num_blocks=num_blocks,
                     kv_dtype=kv_dtype, weight_dtype=weight_dtype)
        rng = np.random.default_rng(0)
        for _ in range(16):
            eng.submit(rng.integers(1, cfg.vocab_size, 24).tolist(), 12)
        eng.run()
        m = eng.metrics.summary()
        st = eng.runner.cache_stats()
        return {"num_blocks": st["num_blocks"],
                "pool_bytes": st["pool_bytes"],
                "bytes_per_block": st["bytes_per_block"],
                "kv_dtype": st["kv_dtype"],
                "weight_dtype": st["weight_dtype"],
                "max_active": m["max_active"],
                "tpot_mean_ms": m["tpot_ms"]["mean"],
                "ttft_p50_ms": m["ttft_ms"]["p50"],
                "throughput_tok_s": m["throughput_tok_s"]}

    fp = run(None, budget_blocks)
    probe = PagedKVCache(fns["init_cache"], cfg, max_slots=slots,
                         max_seq_len=S, block_size=bs, num_blocks=4,
                         kv_dtype="int8")
    int8_blocks = max(4, fp["pool_bytes"] // probe.bytes_per_block())
    q = run("int8", int8_blocks, weight_dtype="int8")
    out = {
        "hbm_budget_bytes": fp["pool_bytes"],
        "block_size": bs,
        "max_slots": slots,
        "fp": fp,
        "int8": q,
        "blocks_gain_at_fixed_hbm": q["num_blocks"] / fp["num_blocks"],
        "slots_gain_at_fixed_hbm": (q["max_active"]
                                    / max(1, fp["max_active"])),
        "tpot_ratio": q["tpot_mean_ms"] / max(1e-9, fp["tpot_mean_ms"]),
    }
    print(f"quantized,budget {fp['pool_bytes']} B,"
          f"blocks {fp['num_blocks']} -> {q['num_blocks']} "
          f"({out['blocks_gain_at_fixed_hbm']:.2f}x),"
          f"slots {fp['max_active']} -> {q['max_active']} "
          f"({out['slots_gain_at_fixed_hbm']:.2f}x),"
          f"tpot {fp['tpot_mean_ms']:.2f} -> {q['tpot_mean_ms']:.2f} ms "
          f"({out['tpot_ratio']:.2f}x)")
    if json_path:
        _merge_json(json_path, "quantized", out)
    return out


def bench_overload(json_path: str | None = None) -> dict:
    """Overload smoke: an oversubscribed block pool, a bounded queue and
    mixed request priorities — the robustness layer's steady state.
    High-priority requests preempt decoding low-priority ones (which
    resume by recompute through the prefix cache), the bounded queue
    sheds the overflow, and every request must land in exactly one
    terminal state with the pool empty.  Preemption/resume/shed/reject
    counts and the completion rate are recorded under ``overload``."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine, EngineStallError, RequestState

    cfg = reduced_config("tinyllama-1.1b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    S, bs = 96, 8
    # each request reserves 24+12-1=35 tokens = 5 blocks; 11 usable
    # blocks run ~2 concurrently for a 16-request, 3-priority workload
    eng = Engine(cfg, params, max_slots=4, max_seq_len=S, block_size=bs,
                 num_blocks=12, max_queue=10, watchdog_patience=50,
                 max_preemptions=4)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(16):
        reqs.append(eng.submit(
            rng.integers(1, cfg.vocab_size, 24).tolist(), 12,
            priority=i % 3))
    try:
        eng.run(max_steps=20_000)
    except EngineStallError as e:
        print(f"overload,STALL,{e.diagnostic}")
    m = eng.metrics.summary()
    states: dict = {}
    for r in reqs:
        states[r.state.value] = states.get(r.state.value, 0) + 1
    eng.runner.kv.check_invariants()
    out = {
        "submitted": len(reqs),
        "completed": states.get(RequestState.DONE.value, 0),
        "states": states,
        "all_terminal": all(r.finished for r in reqs),
        "pool_empty": eng.runner.kv.utilization()["used_blocks"] == 0,
        "preemptions": m["preemptions"],
        "resumes": m["resumes"],
        "shed": m["shed"],
        "shed_rate": m["shed"] / len(reqs),
        "rejected": m["rejected"],
        "timed_out": m["timed_out"],
        "watchdog_fires": m["watchdog_fires"],
        "max_preempt_survived": max(r.preemptions for r in reqs),
        "throughput_tok_s": m["throughput_tok_s"],
        "num_blocks": 12,
        "max_queue": 10,
    }
    print(f"overload,submitted {out['submitted']},completed "
          f"{out['completed']},preemptions {out['preemptions']} "
          f"(resumes {out['resumes']}),shed {out['shed']} "
          f"({100 * out['shed_rate']:.0f}%),terminal "
          f"{out['all_terminal']},pool_empty {out['pool_empty']}")
    if json_path:
        _merge_json(json_path, "overload", out)
    return out


def bench_pipelined(json_path: str | None = None) -> dict:
    """Pipelined-engine smoke on a real reduced model: the same mixed
    workload through ``pipeline_depth=0`` and ``pipeline_depth=1`` (with
    pre-planned per-bucket programs), asserting bitwise-identical outputs
    before recording the pipelined TPOT/TTFT next to the sync numbers.
    On CPU the jitted step dominates so the wall-clock gain is modest —
    the host-overhead headroom itself is what scheduler_overhead.py
    measures — but this smoke keeps the REAL-model pipelined latency and
    the parity bit on the per-commit record."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine

    cfg = reduced_config("tinyllama-1.1b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    S, bs = 96, 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).tolist()
               for n in rng.integers(4, 24, 8)]

    def run(depth, preplan=False):
        eng = Engine(cfg, params, max_slots=4, max_seq_len=S,
                     block_size=bs, prefill_chunk=16,
                     pipeline_depth=depth, preplan=preplan)
        for p in prompts:            # compile warm-up on workload shapes
            eng.submit(p, 8)
        eng.run()
        eng.metrics = type(eng.metrics)()
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.run()
        assert not eng._inflight
        return [r.output for r in reqs], eng.metrics.summary()

    sync_out, sync_m = run(0)
    piped_out, piped_m = run(1, preplan=True)
    assert piped_out == sync_out, "pipelined decode diverged from sync"
    out = {
        "requests": len(prompts),
        "bitwise_equal_sync": piped_out == sync_out,
        "completed": sum(len(o) > 0 for o in piped_out),
        "tpot_ms": piped_m["tpot_ms"],
        "ttft_ms": piped_m["ttft_ms"],
        "sync_tpot_mean_ms": sync_m["tpot_ms"]["mean"],
        "throughput_tok_s": piped_m["throughput_tok_s"],
        "sync_throughput_tok_s": sync_m["throughput_tok_s"],
        "steps_in_flight": piped_m["steps_in_flight"],
    }
    print(f"pipelined,bitwise_equal {out['bitwise_equal_sync']},"
          f"tpot {out['sync_tpot_mean_ms']:.2f} -> "
          f"{out['tpot_ms']['mean']:.2f} ms,"
          f"inflight_peak {out['steps_in_flight']}")
    if json_path:
        _merge_json(json_path, "pipelined", out)
    return out


ARCH_SMOKES = {
    "mla": "deepseek-v2-236b",     # MLA latents paged through 3-D pools
    "window": "gemma2-2b",         # paged full layers + dense ring leaves
    "ssm": "falcon-mamba-7b",      # all-state stack, virtual block metering
}


def bench_arch(kind: str, json_path: str | None = None) -> dict:
    """Architecture-zoo smoke: drive one reduced non-GQA config (MLA /
    sliding-window / SSM) through the layout-polymorphic paged engine
    and record TTFT and peak blocks-in-use under ``arch_<kind>``, so the
    serving-perf trajectory of every cache layout — not just the GQA
    path — is visible per commit.  Chunked prefill is enabled wherever
    the capability table allows it (everywhere but MoE)."""
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch import steps as steps_lib
    from repro.serving.engine import Engine, arch_capabilities

    name = ARCH_SMOKES[kind]
    cfg = reduced_config(name)
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    caps = arch_capabilities(cfg)
    chunk = 8 if caps["chunked_prefill"].supported else 0
    S, bs = 64, 8
    eng = Engine(cfg, params, max_slots=4, max_seq_len=S, block_size=bs,
                 prefill_chunk=chunk)
    rng = np.random.default_rng(0)
    # compile warm-up on the workload shapes, then the timed run
    for _ in range(2):
        eng.submit(rng.integers(1, cfg.vocab_size, 24).tolist(), 8)
    eng.run()
    eng.metrics = type(eng.metrics)()
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, 24).tolist(), 8)
            for _ in range(8)]
    peak_blocks = 0
    for _ in range(10_000):
        if not eng.scheduler.has_work():
            break
        eng.step()
        peak_blocks = max(peak_blocks,
                          eng.runner.kv.utilization()["used_blocks"])
    m = eng.metrics.summary()
    u = eng.runner.kv.utilization()
    assert all(r.finished for r in reqs)
    out = {
        "arch": name,
        "kind": kind,
        "leaf_kinds": u["leaf_kinds"],
        "prefill_chunk": eng.runner.prefill_chunk,
        "chunked_reason": caps["chunked_prefill"].reason,
        "ttft_p50_ms": m["ttft_ms"]["p50"],
        "ttft_p99_ms": m["ttft_ms"]["p99"],
        "tpot_mean_ms": m["tpot_ms"]["mean"],
        "throughput_tok_s": m["throughput_tok_s"],
        "peak_blocks_in_use": peak_blocks,
        "num_blocks": u["num_blocks"],
        "completed": sum(len(r.output) > 0 for r in reqs),
    }
    print(f"arch,{kind},{name},layout {u['leaf_kinds']},"
          f"chunk {out['prefill_chunk']},"
          f"ttft_p50 {out['ttft_p50_ms']:.1f} ms,"
          f"peak_blocks {peak_blocks}/{u['num_blocks']},"
          f"{out['throughput_tok_s']:.1f} tok/s")
    if json_path:
        _merge_json(json_path, f"arch_{kind}", out)
    return out


def main(quick: bool = False) -> dict:
    print("# TTFT (ms), analytical roofline model, batch=1, 8 chips")
    t1 = ttft_table()
    print("# TPOT (ms), analytical roofline model, batch=1, 8 chips")
    t2 = tpot_table()
    res = {"ttft": t1, "tpot": t2}
    if not quick:
        res["measured"] = measured()
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--measure", action="store_true")
    ap.add_argument("--metric", default="both")
    ap.add_argument("--paged", action="store_true",
                    help="toy serving smoke, paged cache + chunked prefill")
    ap.add_argument("--contiguous", action="store_true",
                    help="toy serving smoke, contiguous per-slot cache")
    ap.add_argument("--speculate", action="store_true",
                    help="toy smoke, track-speculative vs plain paged "
                    "decode on a small PT model")
    ap.add_argument("--prefix", action="store_true",
                    help="toy smoke, shared-prefix TTFT cold vs warm "
                    "(content-addressed prefix cache)")
    ap.add_argument("--fork", action="store_true",
                    help="toy smoke, n-way copy-on-write fork from one "
                    "prompt's blocks")
    ap.add_argument("--quantized", action="store_true",
                    help="toy smoke, int8 KV + int8 weights vs fp at a "
                    "fixed HBM byte budget")
    ap.add_argument("--overload", action="store_true",
                    help="toy smoke, oversubscribed pool + mixed "
                    "priorities: preemption/resume/shed accounting")
    ap.add_argument("--pipelined", action="store_true",
                    help="toy smoke, pipelined (depth-1, pre-planned) vs "
                    "sync engine loop: bitwise parity + pipelined TPOT")
    ap.add_argument("--arch", default=None, choices=sorted(ARCH_SMOKES),
                    help="architecture-zoo smoke: serve one reduced "
                    "MLA / sliding-window / SSM config through the "
                    "layout-polymorphic paged engine")
    ap.add_argument("--n-forks", type=int, default=3,
                    help="children per fork for --fork")
    ap.add_argument("--speculate-k", type=int, default=4,
                    help="draft length K for --speculate")
    ap.add_argument("--draft-tracks", type=int, default=1,
                    help="drafter track count for --speculate")
    ap.add_argument("--json", default=None,
                    help="merge smoke results into this JSON file")
    args = ap.parse_args()
    if (args.paged or args.contiguous or args.speculate or args.prefix
            or args.fork or args.quantized or args.overload or args.arch
            or args.pipelined):
        if args.paged:
            bench_smoke(True, args.json)
        if args.contiguous:
            bench_smoke(False, args.json)
        if args.speculate:
            bench_speculate(args.json, args.speculate_k, args.draft_tracks)
        if args.prefix:
            bench_prefix(args.json)
        if args.fork:
            bench_fork(args.json, args.n_forks)
        if args.quantized:
            bench_quantized(args.json)
        if args.overload:
            bench_overload(args.json)
        if args.pipelined:
            bench_pipelined(args.json)
        if args.arch:
            bench_arch(args.arch, args.json)
    else:
        if args.metric in ("ttft", "both"):
            ttft_table()
        if args.metric in ("tpot", "both"):
            tpot_table()
        if args.measure:
            measured()
