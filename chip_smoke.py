#!/usr/bin/env python3
"""Chip smoke: the serving main path on a TPU, end to end, in one process.

  python chip_smoke.py [--seed N]      # one chip: kernels, then pt-6b-d4 served
  python chip_smoke.py --four-chips    # sharded pt-6b-d4 decode step vs one chip

One chip runs three phases, in order:

  1. device  — JAX's first device must be a TPU; anything else exits 1.
  2. kernels — every kernel that ``repro.kernels.ops`` exports, compiled
     for the chip (never interpreted) at pt-6b-d4 widths (the SSM scan at
     falcon-mamba-7b widths, since PT has no SSM), against its
     ``repro.kernels.ref`` oracle run at highest matmul precision.
  3. engine  — pt-6b-d4 at full published width in bf16, random weights
     from ``--seed``, served through the engine ``repro.launch.serve``
     builds, with its defaults: 8 greedy requests of 256 prompt tokens and
     32 new tokens.  Every request must end DONE with 32 tokens, and a
     teacher-forced ``forward`` over prompt + output must pick the
     engine's token at PARITY_MIN of the positions or more.

``--four-chips`` runs only the sharded phase: the pt-6b-d4 decode step
over a ('data', 'track') = (1, 4) mesh against the same step on one chip.
Logits must agree, the compiled track-block loop must hold L/D cross-track
all-reduces, and the parameters must be spread over the four devices.

Times printed here are smoke numbers, not benchmark numbers.  The last
line of standard output is one JSON object, printed only when every phase
passed:  {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "pt-6b-d4"
REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 256, 32

# Teacher forcing feeds the engine's own tokens back, so one disagreement
# cannot cascade: a position disagrees only where the forward's top-2
# logit gap is smaller than the difference between the bf16 decode path
# (paged KV, one token per step) and the bf16 whole-sequence forward.
# Random-init logits over a 100k vocabulary rarely sit that close; a
# tenth of positions disagreeing would mean a real fault, not rounding.
PARITY_MIN = 0.9

# Sharded vs one-chip logits differ only in the order of the fusion sums
# (2 local tracks + a 4-way all-reduce against 8 local tracks), rounded
# to bf16 at each of the 8 track blocks.  A mis-sharded leaf or a lost
# track moves the logits by their own magnitude.
SHARDED_REL_TOL = 5e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"[smoke] FAIL: {msg}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_cases(cfg):
    """(name, ops entry, ref oracle, args, relative tolerance) per kernel,
    with random inputs at the widths of one pt-6b-d4 track."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.quant import quantize
    from repro.kernels import ops, ref

    bf, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def rand(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, f32).astype(dtype)

    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim    # per track
    if cfg.pt is None or KH != 1:
        fail(f"{cfg.name}: expected a PT config with 1 KV head per track")
    d, d_ff = cfg.d_model, cfg.d_ff
    cases = [
        ("flash_attention", ops.flash_attention, ref.flash_attention_ref,
         (rand((1, 2048, H, hd)), rand((1, 2048, H, hd)),
          rand((1, 2048, H, hd))), 2e-2),
        ("rmsnorm", ops.rmsnorm, ref.rmsnorm_ref,
         (rand((REQUESTS * PROMPT_LEN, d)), 0.1 * rand((d,), f32)), 2e-2),
    ]
    w = quantize(rand((d, d_ff), f32), axes=-2)
    cases.append(("int8_matmul", ops.int8_matmul, ref.int8_matmul_ref,
                  (rand((256, d)), w.payload, w.scale.reshape(1, d_ff)),
                  1e-2))
    # paged flash-decode: one PT track (KH=1) and the dense-6b layout
    # (KH=8), 8 slots of 1,024 tokens in 16-token blocks, shuffled tables
    B, bs, nmax = REQUESTS, 16, 64
    lengths = jnp.asarray([1 + (97 * i + 300) % (nmax * bs)
                           for i in range(B)], jnp.int32)
    table = jnp.asarray(np.random.default_rng(0).permutation(B * nmax)
                        .reshape(B, nmax), jnp.int32)
    for kh in (KH, 8):
        g = H // KH
        cases.append((f"paged_decode_attention[KH={kh},hd={hd}]",
                      ops.paged_decode_attention,
                      ref.paged_decode_attention_ref,
                      (rand((B, kh * g, hd)), rand((B * nmax, bs, kh, hd)),
                       rand((B * nmax, bs, kh, hd)), table, lengths), 2e-2))
    cases.append(("decode_attention", ops.decode_attention,
                  ref.decode_attention_ref,
                  (rand((B, H, hd)), rand((B, nmax * bs, KH, hd)),
                   rand((B, nmax * bs, KH, hd)), lengths), 2e-2))
    # falcon-mamba-7b scan widths: d_inner 8192, d_state 16
    a = jax.nn.sigmoid(rand((1, 1024, 8192, 16), f32))
    cases.append(("ssm_scan", ops.ssm_scan, ref.ssm_scan_ref,
                  (a, rand((1, 1024, 8192, 16), f32),
                   rand((1, 8192, 16), f32)), 1e-4))
    return cases


def run_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp

    for name, kernel, oracle, args, tol in kernel_cases(cfg):
        hlo = jax.jit(kernel).lower(*args).as_text()
        if "tpu_custom_call" not in hlo:
            fail(f"kernel {name} did not lower to a TPU custom call")
        out = jax.tree_util.tree_leaves(kernel(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(jax.jit(oracle)(*args))
        err = scale = 0.0
        for o, r in zip(out, want):
            r = r.astype(jnp.float32)
            err = max(err, float(jnp.max(jnp.abs(o.astype(jnp.float32) - r))))
            scale = max(scale, float(jnp.max(jnp.abs(r))))
        bound = tol * max(1.0, scale)
        ok = err <= bound
        log(f"kernel {name}: compiled, max err {err:.3e} "
            f"(tolerance {bound:.3e} = {tol:g} x max(1, max|ref| "
            f"{scale:.3e})) {'ok' if ok else 'OVER'}")
        if not ok:
            fail(f"kernel {name} is over its tolerance")


# ---------------------------------------------------------------------------
# the serving engine, one chip
# ---------------------------------------------------------------------------

def run_engine(cfg, seed: int, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.pytree import count_params
    from repro.launch import serve
    from repro.launch import steps as steps_lib
    from repro.serving.engine import RequestState

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    args = serve.parse_args([
        "--arch", ARCH, "--requests", str(REQUESTS),
        "--input-len", str(PROMPT_LEN), "--output-len", str(NEW_TOKENS),
        "--slots", str(REQUESTS), "--seed", str(seed)])
    params = serve.init_params(cfg, seed)
    leaves = jax.tree_util.tree_leaves(params)
    log(f"engine: {ARCH} {count_params(params) / 1e9:.2f}B params, "
        f"{sum(l.nbytes for l in leaves) / 2**30:.2f} GiB "
        f"({sorted({str(l.dtype) for l in leaves})}) on {dev.device_kind}")
    eng = serve.build_engine(args, cfg, params)
    if cfg.use_pallas:
        fail("the engine was expected on its jnp attention path")

    # a first round with other prompts compiles every program the
    # measured round runs
    serve.submit_workload(eng, args, cfg, seed + 1)
    eng.run()
    t0 = time.perf_counter()
    reqs = serve.submit_workload(eng, args, cfg, seed)
    eng.run()
    wall = time.perf_counter() - t0

    bad = [(r.rid, r.state.value, len(r.output)) for r in reqs
           if r.state != RequestState.DONE or len(r.output) != NEW_TOKENS]
    if bad:
        fail(f"requests not DONE with {NEW_TOKENS} tokens: {bad}")
    log(f"engine: {len(reqs)} requests DONE, {NEW_TOKENS} tokens each")
    ttft = np.median([r.ttft for r in reqs]) * 1e3
    tpot = np.median([r.tpot for r in reqs]) * 1e3
    toks = sum(len(r.output) for r in reqs)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)
    log(f"smoke numbers (not a benchmark): compile {sum(compile_s):.1f} s "
        f"over {len(compile_s)} programs | measured round: TTFT p50 "
        f"{ttft:.2f} ms, TPOT p50 {tpot:.2f} ms, {toks / wall:.1f} tok/s "
        f"| peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)")

    # teacher-forced parity: one forward over prompt + output
    fwd = steps_lib.model_fns(cfg)["forward"]
    seq = jnp.asarray([r.prompt + r.output for r in reqs], jnp.int32)

    @jax.jit
    def greedy(params, seq):
        logits = fwd(params, {"inputs": seq}, cfg, mode="train")[0]
        return jnp.argmax(logits[:, PROMPT_LEN - 1:-1], axis=-1)

    pred = np.asarray(greedy(params, seq))
    got = np.asarray([r.output for r in reqs])
    agree = pred == got
    share = float(agree.mean())
    miss = np.argwhere(~agree)
    first = (f"request {miss[0][0]} position {miss[0][1]} (engine "
             f"{got[tuple(miss[0])]}, forward {pred[tuple(miss[0])]})"
             if len(miss) else "none")
    log(f"parity: teacher-forced forward agrees on {agree.sum()} of "
        f"{agree.size} positions = {share:.4f} (min {PARITY_MIN}); "
        f"first divergence: {first}")
    if share < PARITY_MIN:
        fail("engine tokens disagree with the teacher-forced forward")


# ---------------------------------------------------------------------------
# the sharded PT decode step, four chips
# ---------------------------------------------------------------------------

def run_four_chips(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.compat import make_mesh
    from repro.launch import serve
    from repro.launch import steps as steps_lib
    from repro.roofline import hlo as hlo_lib
    from repro.runtime import sharding as sh

    n_dev = len(jax.devices())
    if n_dev != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {n_dev}")
    fns = steps_lib.model_fns(cfg)
    B, S_cap, n_steps = REQUESTS, 64, 8
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=(n_steps, B)).astype(np.int32)

    def decode_all(step, params, cache):
        out = []
        for t in range(n_steps):
            pos = jnp.full((B,), t, jnp.int32)
            logits, cache = step(params, cache, tokens[t], pos)
            out.append(np.asarray(logits, np.float32))
        return np.stack(out)

    def new_cache():
        return fns["init_cache"](cfg, B, S_cap)

    params = serve.init_params(cfg, seed)
    par1 = steps_lib.build_parallelism(cfg, "decode", None)
    step1 = jax.jit(steps_lib.make_serve_step(cfg, par1),
                    donate_argnums=(1,))
    want = decode_all(step1, params, jax.jit(new_cache)())
    log(f"four chips: one-chip reference decoded {n_steps} steps")

    mesh = make_mesh((1, 4), ("data", "track"))
    par = steps_lib.build_parallelism(cfg, "decode", mesh)
    psh = sh.param_shardings(params, cfg, par)
    params = jax.device_put(params, psh)
    csh = sh.cache_shardings(jax.eval_shape(new_cache), cfg, par)
    cache = jax.jit(new_cache, out_shardings=csh)()
    step = jax.jit(steps_lib.make_serve_step(cfg, par),
                   in_shardings=(psh, csh, None, None),
                   out_shardings=(None, csh), donate_argnums=(1,))
    compiled = step.lower(params, cache, tokens[0],
                          jnp.zeros((B,), jnp.int32)).compile()
    got = decode_all(compiled, params, cache)

    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    argmax_agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"four chips: logits max err {err:.4e} over {n_steps} steps "
        f"(max|ref| {scale:.4e}, tolerance {SHARDED_REL_TOL:g} x max|ref|);"
        f" argmax agrees on {argmax_agree:.4f}")
    if err > SHARDED_REL_TOL * scale:
        fail("sharded logits disagree with one chip")

    blocks = cfg.n_layers // cfg.pt.block_depth
    loops = [l for l in hlo_lib.loop_all_reduces(compiled.as_text(), 4)
             if l["all_reduces"]]
    in_loop = sum(l["trips"] * l["all_reduces"] for l in loops)
    log(f"four chips: loops with all-reduces {loops}; cross-track "
        f"all-reduces in the track-block loop {in_loop} (L/D = {blocks})")
    if (len(loops) != 1 or loops[0]["all_reduces"] != 1
            or loops[0]["group_sizes"] != [4] or in_loop != blocks):
        fail(f"expected one all-reduce over 4 devices per track block, "
             f"{blocks} in the loop")

    per_dev = {d.id: 0 for d in jax.devices()}
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    shares = {k: v / total for k, v in per_dev.items()}
    log(f"four chips: parameter bytes per device {per_dev} of {total} "
        f"= shares {({k: round(v, 4) for k, v in shares.items()})}")
    if not all(0.2 <= s <= 0.3 for s in shares.values()):
        fail("parameters are not spread about a quarter per device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded PT decode phase, on 4 chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {jax.device_count()}")
    if dev.platform != "tpu":
        print("[smoke] FAIL: no TPU; the smoke never falls back to "
              f"{dev.platform}", file=sys.stderr)
        return 1

    from repro.common.compile_cache import enable_compile_cache
    from repro.configs import get_config

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    if cfg.dtype != "bfloat16":
        fail(f"{ARCH} is expected in bf16, not {cfg.dtype}")
    if args.four_chips:
        run_four_chips(cfg, args.seed)
    else:
        run_kernels(cfg)
        run_engine(cfg, args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
