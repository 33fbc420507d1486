"""Model-free :class:`StubRunner`: the full ``ModelRunner`` host-facing
surface with deterministic fake tokens and zero jit.

The engine never looks inside the runner — it only calls the host-facing
methods (prefill / chunk / decode / spec dispatch+wait / fork plumbing)
and reads a handful of attributes.  The stub implements exactly that
surface over a REAL :class:`PagedKVCache` (block accounting, prefix
matching and CoW behave for real) while every "model" output is a pure
hash of ``(request seed, token counter)`` — so scheduler and pipeline
semantics are testable in milliseconds, bitwise-reproducibly, without
compiling a single jitted program.

Two extra powers the real runner doesn't have:

  * ``trace`` — every runner call and every KV-pool mutation is recorded
    in order, so tests can assert WHERE decisions happen (e.g. that
    nothing runs between a pipelined dispatch and its transfer-wait).
  * ``step_time_s`` — simulated device latency.  A dispatch stamps its
    completion time onto a virtual single-stream device
    (``ready_at = max(device_free, now) + step_time_s``); the wait spins
    until then.  This reproduces the real overlap economics: a
    synchronous loop costs ``host + device`` per step, the pipelined
    loop ``max(host, device)`` — which is what
    ``benchmarks/scheduler_overhead.py`` measures.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro.common.types import LayerSpec, ModelConfig
from repro.serving.cache import PagedKVCache
from repro.serving.engine import Engine, ModelRunner, arch_capabilities
from repro.serving.faults import FaultPlan


def stub_token(seed: int, counter: int, vocab: int) -> int:
    """Deterministic fake token: a splitmix-style hash of (seed,
    counter) into ``[1, vocab)``.  Depending on nothing else, the stream
    a request emits is independent of batch composition, admission
    order, preemption and pipelining — exactly the property the real
    per-request PRNG sampler provides, so parity tests transfer."""
    x = (seed * 0x9E3779B97F4A7C15 + counter * 0xBF58476D1CE4E5B9) \
        & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 31
    x = (x * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 29
    return 1 + x % (vocab - 1)


def stub_cfg(vocab: int = 64) -> ModelConfig:
    return ModelConfig(
        name="stub", family="dense", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=1, d_ff=32, vocab_size=vocab, head_dim=8,
        layer_specs={"full": LayerSpec(mixer="gqa", mlp="swiglu")},
        pattern_unit=("full",), tie_embeddings=False, dtype="float32")


class StubRunner:
    """Drop-in ``ModelRunner`` replacement (see module docstring)."""

    # reuse the real implementations verbatim: bucketing/charging are
    # pure host logic, and the fault hook must fire the same schedule
    bucket_for = ModelRunner.bucket_for
    admission_charge = ModelRunner.admission_charge
    _maybe_inject_transfer = ModelRunner._maybe_inject_transfer

    def __init__(self, cfg: ModelConfig, *, max_slots: int,
                 max_seq_len: int, min_bucket: int = 16,
                 paged: bool = True, block_size: int = 8,
                 num_blocks: int = 32, prefill_chunk: int = 0,
                 speculate_k: int = 0, prefix_cache: bool = True,
                 fault_plan: Optional[FaultPlan] = None,
                 step_time_s: float = 0.0):
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.min_bucket = min_bucket
        self.paged = paged
        self.prefill_chunk = prefill_chunk
        self.speculate_k = speculate_k
        self.prefix_cache = prefix_cache and paged
        self.kv_dtype: Optional[str] = None
        self.weight_dtype: Optional[str] = None
        self.capabilities = arch_capabilities(cfg)
        self.quant_fallbacks: List[str] = []
        self.has_dense_leaves = False
        self.exact_prefill = False
        self.n_quantized = 0
        self.faults = fault_plan
        self.step_time_s = step_time_s
        self.trace: List[Tuple[Any, ...]] = []
        self.prefill_calls = 0
        self.chunk_calls = 0
        self.decode_transfers = 0
        self.planned_hits = 0
        self.prefill_shapes: Set[Tuple[int, int]] = set()
        self.chunk_shapes: Set[Tuple[int, ...]] = set()
        self._device_free_at = 0.0
        if paged:
            init_kv = lambda c, b, s: (jnp.zeros((b, s, 1, 4),
                                                 jnp.float32),)
            self.kv = PagedKVCache(init_kv, cfg, max_slots=max_slots,
                                   max_seq_len=max_seq_len,
                                   block_size=block_size,
                                   num_blocks=num_blocks,
                                   fault_plan=fault_plan)
            self._trace_kv_calls()
        else:
            self.kv = None

    # -- call tracing ---------------------------------------------------
    def _trace_kv_calls(self) -> None:
        """Instance-attribute wrap of the pool's public mutators/queries
        so the trace shows every scheduler decision that touched it."""
        for name in ("allocate", "free_slot", "commit_tokens",
                     "ensure_writable", "match_prefix", "fork"):
            orig = getattr(self.kv, name)

            def wrapped(*a, _nm=name, _fn=orig, **k):
                self.trace.append(("kv." + _nm,))
                return _fn(*a, **k)

            setattr(self.kv, name, wrapped)

    # -- simulated device latency --------------------------------------
    def _stamp(self) -> float:
        now = time.perf_counter()
        ready = max(self._device_free_at, now) + self.step_time_s
        self._device_free_at = ready
        return ready

    @staticmethod
    def _wait_until(t: float) -> None:
        while time.perf_counter() < t:
            pass               # busy-spin: sub-ms precision for benches

    # -- prefill family -------------------------------------------------
    def _first_tokens(self, seeds: Sequence[int],
                      counters: Sequence[int]) -> np.ndarray:
        return np.array([stub_token(int(sd), int(c), self.vocab)
                         for sd, c in zip(seeds, counters)], np.int32)

    def prefill(self, prompts, bucket, slots, seeds, counters,
                params_list) -> np.ndarray:
        self.trace.append(("prefill", len(prompts), bucket))
        self.prefill_shapes.add((len(prompts), bucket))
        self.prefill_calls += 1
        self._wait_until(self._stamp())
        self._maybe_inject_transfer("prefill")
        return self._first_tokens(seeds, counters)

    def warm_prefill(self, prompts, matched, slots, seeds, counters,
                     params_list) -> np.ndarray:
        self.trace.append(("warm_prefill", len(prompts)))
        self.prefill_calls += 1
        self._wait_until(self._stamp())
        self._maybe_inject_transfer("chunk")
        return self._first_tokens(seeds, counters)

    def chunk(self, toks, pos, slots, last_idx, seeds, counters,
              params_list) -> np.ndarray:
        self.trace.append(("chunk", tuple(toks.shape)))
        self.chunk_shapes.add(tuple(toks.shape))
        self.chunk_calls += 1
        self._wait_until(self._stamp())
        self._maybe_inject_transfer("chunk")
        return self._first_tokens(seeds, counters)

    # -- drafter / fork plumbing (dense state: nothing to move) --------
    def draft_prefill(self, prompts, bucket, slots) -> None:
        self.trace.append(("draft_prefill", len(prompts)))

    def draft_chunk(self, toks, pos, slots) -> None:
        self.trace.append(("draft_chunk", tuple(toks.shape)))

    def reset_slots(self, slots) -> None:
        self.trace.append(("reset_slots", tuple(slots)))

    def dense_fork(self, src, dsts) -> None:
        self.trace.append(("dense_fork", src, tuple(dsts)))

    def draft_fork(self, src, dsts) -> None:
        self.trace.append(("draft_fork", src, tuple(dsts)))

    def copy_blocks(self, pairs) -> None:
        self.trace.append(("copy_blocks", len(pairs)))

    def plan_programs(self) -> int:
        return 0               # nothing to compile

    def cache_stats(self) -> Dict[str, Any]:
        if not self.paged:
            return {"mode": "stub"}
        return {"mode": "stub", **self.kv.utilization()}

    # -- decode / spec: dispatch + wait --------------------------------
    #
    # The stub mirrors the real runner's carry protocol exactly: with a
    # ``carry`` handle, this step's per-lane counters derive from the
    # previous dispatch's effective values (+1 per decode, +m per spec
    # step) — except ``override`` lanes, which take the host arrays.
    # Host mirrors lag in the pipelined engine just as they do on a real
    # device, so any bookkeeping divergence shows up as a parity break.

    def dispatch_decode(self, toks, pos, active, seeds, counts, temps,
                        tks, tps, eos, remaining, *, carry=None,
                        override=None) -> Dict[str, Any]:
        act = np.asarray(active, bool).copy()
        eff_counts = np.asarray(counts, np.int64).copy()
        eff_rem = np.asarray(remaining, np.int64).copy()
        if carry is not None:
            ov = np.asarray(override, bool)
            eff_counts = np.where(ov, eff_counts, carry["next_counts"])
            eff_rem = np.where(ov, eff_rem, carry["next_remaining"])
        B = len(act)
        out = np.zeros((B,), np.int32)
        done = np.zeros((B,), bool)
        eos_h = np.asarray(eos, np.int64)
        seeds_h = np.asarray(seeds, np.uint32)
        for s in range(B):
            if not act[s]:
                continue
            t = stub_token(int(seeds_h[s]), int(eff_counts[s]), self.vocab)
            out[s] = t
            done[s] = (int(eff_rem[s]) <= 1
                       or (int(eos_h[s]) >= 0 and t == int(eos_h[s])))
        self.trace.append(("dispatch", "decode"))
        return {"kind": "decode", "toks": out, "done": done,
                "active": act, "next_counts": eff_counts + 1,
                "next_remaining": eff_rem - 1, "ready_at": self._stamp()}

    def wait_decode(self, handle: Dict[str, Any]
                    ) -> Tuple[np.ndarray, np.ndarray]:
        self.trace.append(("wait", "decode"))
        self._wait_until(handle["ready_at"])
        self._maybe_inject_transfer("decode")
        self.decode_transfers += 1
        return handle["toks"], handle["done"]

    def decode(self, toks, pos, active, seeds, counts, temps, tks, tps,
               eos, remaining) -> Tuple[np.ndarray, np.ndarray]:
        return self.wait_decode(self.dispatch_decode(
            toks, pos, active, seeds, counts, temps, tks, tps, eos,
            remaining))

    def dispatch_spec(self, toks, pos, active, seeds, counts, temps,
                      tks, tps, *, carry=None, override=None,
                      extra_len: int = 0) -> Dict[str, Any]:
        act = np.asarray(active, bool).copy()
        eff_counts = np.asarray(counts, np.int64).copy()
        if carry is not None:
            ov = np.asarray(override, bool)
            eff_counts = np.where(ov, eff_counts, carry["next_counts"])
        B = len(act)
        K1 = self.speculate_k + 1
        mat = np.zeros((B, K1), np.int32)
        m = np.zeros((B,), np.int32)
        seeds_h = np.asarray(seeds, np.uint32)
        for s in range(B):
            if not act[s]:
                continue
            for j in range(K1):   # accept-all drafter: m = K+1 always
                mat[s, j] = stub_token(int(seeds_h[s]),
                                       int(eff_counts[s]) + j, self.vocab)
            m[s] = K1
        self.trace.append(("dispatch", "spec"))
        return {"kind": "spec", "toks": mat, "m": m, "active": act,
                "next_counts": eff_counts + m,
                "ready_at": self._stamp()}

    def wait_spec(self, handle: Dict[str, Any]
                  ) -> Tuple[np.ndarray, np.ndarray]:
        self.trace.append(("wait", "spec"))
        self._wait_until(handle["ready_at"])
        self._maybe_inject_transfer("draft_verify")
        self.decode_transfers += 1
        return handle["toks"], handle["m"]

    def draft_verify(self, toks, pos, active, seeds, counts, temps, tks,
                     tps) -> Tuple[np.ndarray, np.ndarray]:
        return self.wait_spec(self.dispatch_spec(
            toks, pos, active, seeds, counts, temps, tks, tps))


def stub_engine(*, max_slots: int = 4, max_seq_len: int = 64,
                block_size: int = 8, num_blocks: int = 32,
                paged: bool = True, prefill_chunk: int = 0,
                speculate_k: int = 0, prefix_cache: bool = True,
                fault_plan: Optional[FaultPlan] = None,
                step_time_s: float = 0.0, pipeline_depth: int = 0,
                vocab: int = 64, **engine_kw) -> Tuple[Engine, StubRunner]:
    """An Engine wired to a StubRunner, both built from one consistent
    set of knobs.  Returns ``(engine, runner)``."""
    cfg = stub_cfg(vocab)
    runner = StubRunner(cfg, max_slots=max_slots, max_seq_len=max_seq_len,
                        paged=paged, block_size=block_size,
                        num_blocks=num_blocks, prefill_chunk=prefill_chunk,
                        speculate_k=speculate_k, prefix_cache=prefix_cache,
                        fault_plan=fault_plan, step_time_s=step_time_s)
    eng = Engine(cfg, None, max_slots=max_slots, max_seq_len=max_seq_len,
                 paged=paged, block_size=block_size, num_blocks=num_blocks,
                 prefill_chunk=prefill_chunk, speculate_k=speculate_k,
                 prefix_cache=prefix_cache, fault_plan=fault_plan,
                 pipeline_depth=pipeline_depth, runner=runner, **engine_kw)
    return eng, runner
