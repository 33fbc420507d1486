"""Continuous-batching serving engine: Scheduler + ModelRunner.

The engine is split along the line every production serving stack draws
(vLLM, TensorRT-LLM, Kraken's runtime):

  Scheduler   — pure-Python admission policy.  FCFS queue with a
                max-waiting-prefill-tokens budget per admission round,
                request lifecycle QUEUED -> PREFILL -> DECODE -> DONE,
                slot table for the fixed decode batch.
  ModelRunner — everything that touches the device.  Owns the KV cache,
                the jitted prefill / chunk / decode programs and the
                cache insertion program; knows nothing about queues.
  Engine      — the glue loop (submit / step / run / generate) plus
                streaming callbacks and aggregate serving metrics.

Memory + latency structure (this PR's point):

  * Paged KV cache, layout-polymorphic: every cache leaf is classified
    by a per-leaf layout policy (``common.paged.classify_leaf``) —
    'paged' leaves (GQA K/V, MLA compressed latents, the PT
    [R, D, n_tracks, ...] stacking included) live in a shared block pool
    ([num_blocks, block_size, ...]) addressed through per-slot block
    tables; 'ring' leaves (sliding-window K/V) and 'state' leaves
    (SSM / RG-LRU recurrences) stay dense per-slot and ride along under
    the same block-table admission/reclamation accounting (an all-state
    stack still meters virtual blocks, so scheduling is uniform).  A
    request holds ceil(tokens/block_size) blocks instead of a
    max_seq_len reservation, so short and long requests share HBM and
    the decode batch is bounded by actual token usage.  Finished slots
    return their blocks to the pool the moment the packed (token, done)
    transfer lands (``sampler.sample_step``).  Per-feature support is a
    capability query (``arch_capabilities`` / ``Engine.capabilities``),
    never an ad-hoc architecture allowlist.
  * Chunked prefill: with ``prefill_chunk=C`` set (any non-MoE decoder
    arch), prompts are fed C tokens per engine step through the cache
    and interleaved with decode — a 32k prompt no longer stalls every
    decoding request, and TTFT of short queued requests stays flat
    while long prefills are in flight.  Paged leaves append through the
    block table, ring leaves through an in-chunk side buffer, recurrent
    state through masked chunk updates (padded final-chunk tokens do
    identity state updates).
  * Bucketed prefill (the default path, and the fallback for
    length-sensitive archs): prompts right-padded to power-of-two
    buckets, O(log max_len) compile variants, same-bucket admissions
    batched into ONE prefill call.
  * Device-side batched sampling: model + per-slot sampling + done flags
    jit into one program; the host sees exactly ONE transfer per decode
    step — a packed [2, slots] int32 array of (token, done).
  * Track-speculative decoding (PT configs, ``speculate_k=K`` +
    ``draft_tracks=d``): the first d of n tracks are sliced out of the
    stacked PT params into a free-standing narrow drafter with its own
    dense per-slot cache.  Each engine step runs ONE jitted program —
    K sync-free draft steps (no cross-track all-reduce at all), one
    K+1-token verify forward for every slot against the paged cache
    (the chunked-prefill path generalized to per-position logits), and
    batched rejection sampling — and still lands exactly ONE packed
    [K+2, slots] host transfer.  Slots advance 1..K+1 tokens per step
    (per-slot variable acceptance); greedy output is bitwise-identical
    to plain decode, sampled output keeps the target distribution
    exactly.  Non-PT / non-paged configs fall back to plain decode.
  * Per-request PRNG seeds: every sampling draw is keyed by (request
    seed, token counter), never by an engine-global key, so a request's
    output replays bit-identically regardless of batch composition.
  * Prefix caching + copy-on-write forking (paged, full-attention
    configs): full KV blocks are content-addressed by a chain hash over
    their token ids, so a prompt sharing a cached block-aligned prefix
    (system prompt, few-shot template, earlier turn) skips prefill for
    the matched span — ``allocate`` bumps refcounts instead of
    allocating, and only the uncached tail runs through the prefill
    path.  ``Engine.fork`` clones a decoding request n ways sharing
    every block of its committed tokens; a shared block is copied only
    on the first divergent write (``ensure_writable`` before each
    decode/verify step).  All latency timing uses the monotonic
    ``time.perf_counter`` clock (wall-clock kept only for log
    timestamps), so NTP slews can't corrupt TTFT/TPOT percentiles.
  * Robustness layer: under block exhaustion (admission starvation, a
    fork storm's copy-on-write demand) the engine preempts the lowest-
    priority decoding request — commits its written positions so the
    blocks park in the refcount-zero LRU, requeues it with prompt+output
    as its effective prompt — and the resume replays through the prefix
    cache at the same per-request PRNG counters, bitwise-identical to an
    uncontended run.  Requests carry ``priority``/``deadline_s``, can be
    cancelled mid-flight, and terminate in exactly one of DONE /
    REJECTED / CANCELLED / TIMED_OUT — validation failures and overload
    shedding (bounded queue) are delivered through ``on_event``, never
    as exceptions out of the step loop.  A stall watchdog breaks
    no-forward-progress livelocks (preempt or shed-with-diagnostic), and
    a deterministic ``FaultPlan`` injects allocation failures, transfer
    faults and slow steps at the real choke points for reproducible
    chaos tests.
"""
from __future__ import annotations

import dataclasses
import enum
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.paged import PagedLeaf, is_paged, wrap_paged
from repro.common.types import ModelConfig
from repro.core import track as pt_lib
from repro.launch import steps as steps_lib
from repro.models.attention import paged_decode_kernel
from repro.runtime.parallel import NO_PARALLEL
from repro.serving.cache import (PagedKVCache, batch_axes, insert_rows,
                                 paged_insert_rows)
from repro.serving.faults import FaultPlan, TransferFault
from repro.serving.sampler import (SALT_DRAFT, SALT_SAMPLE, SampleParams,
                                   accept_step, advance_decode, advance_spec,
                                   fork_seeds, prefill_keys, row_keys,
                                   sample_rows, sample_step, stack_params)

RECURRENT_MIXERS = ("mamba", "rglru")

# Step-phase spans on the profiler's host plane.  A span costs about a
# microsecond when no profile is being taken.  Capture a profile around
# the ``Engine.step()`` loop (``jax.profiler.trace``) to see them:
#   serve.step    one Engine.step(); args: step index, rows queued,
#                 prefilling and decoding, and ``t`` (perf_counter_ns at
#                 entry, which puts every Request mark on the trace clock)
#   serve.admit   admission (and bucketed prefill); args: admitted,
#                 prefix-hit tokens, rids
#   serve.prefill one chunk of every prefilling row; args: rows, real and
#                 padded tokens, rids
#   serve.cow     copy-on-write of shared blocks before decode
#   serve.decode  the decode/verify call (pipelined: its dispatch); rids
#   serve.emit    applying sampled tokens: on_token callbacks, finishing
#   serve.inputs  a runner call's argument transfers and block table
#   serve.launch  the jitted program's call
#   serve.fetch   the blocking device-to-host read of its result
SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.cow",
         "serve.decode", "serve.emit", "serve.inputs", "serve.launch",
         "serve.fetch")
_span = jax.profiler.TraceAnnotation


def _rids(rows: Sequence[Tuple[int, "Request"]]) -> str:
    """Request ids of a phase's rows, as one span argument."""
    return " ".join(str(r.rid) for _, r in rows)


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    DONE = "done"
    REJECTED = "rejected"      # never ran: validation / shed / gave up
    CANCELLED = "cancelled"    # Engine.cancel
    TIMED_OUT = "timed_out"    # deadline_s exceeded


TERMINAL_STATES = (RequestState.DONE, RequestState.REJECTED,
                   RequestState.CANCELLED, RequestState.TIMED_OUT)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    params: SampleParams = dataclasses.field(default_factory=SampleParams)
    on_token: Optional[Callable[["Request", int], None]] = None
    seed: int = 0                      # per-request PRNG seed (sampling)
    priority: int = 0                  # higher evicts lower under pressure
    deadline_s: Optional[float] = None  # submit-to-done budget (monotonic)
    on_event: Optional[Callable[["Request", str], None]] = None
    # filled by the engine
    state: RequestState = RequestState.QUEUED
    output: List[int] = dataclasses.field(default_factory=list)
    truncated: bool = False            # max_new_tokens clamped to capacity
    prefilled: int = 0                 # seq tokens consumed (chunked)
    cached_prefix: int = 0             # seq tokens served from cache
    draft_filled: int = 0              # drafter cache tokens (chunked+spec)
    pending_first: Optional[int] = None  # first token parked until the
                                       # drafter catches up (chunked+spec)
    finish_reason: Optional[str] = None  # set on abnormal termination
    preemptions: int = 0               # times evicted + requeued
    # monotonic (perf_counter) latency marks — immune to clock steps
    t_submit: float = 0.0
    t_admitted: float = 0.0            # first slot (a preemption-resume
                                       # keeps it; a rolled-back first
                                       # admission clears it)
    t_first: float = 0.0
    t_done: float = 0.0
    chunk_step_tokens: int = 0         # output tokens after the first
                                       # that waited behind a chunk or
                                       # prefill program (one ran since
                                       # the request's previous token)
    t_submit_wall: float = 0.0         # wall-clock, for log timestamps only

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_submit

    @property
    def tpot(self) -> float:
        n = max(1, len(self.output) - 1)
        return (self.t_done - self.t_first) / n

    @property
    def seq_tokens(self) -> List[int]:
        """Prompt plus everything generated so far — the effective
        prompt a preempted request re-enters the queue with, so its
        recompute replays the same token stream."""
        return self.prompt + self.output

    @property
    def finished(self) -> bool:
        return self.state in TERMINAL_STATES


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class EngineMetrics:
    """Aggregate serving metrics over completed requests."""

    def __init__(self) -> None:
        self.ttfts: List[float] = []
        self.tpots: List[float] = []
        self.prompt_tokens = 0
        self.output_tokens = 0
        self.max_active = 0            # peak concurrently-running requests
        self.t_start: Optional[float] = None
        self.t_last: Optional[float] = None
        # speculative decoding
        self.spec_steps = 0
        self.draft_proposed = 0        # K per active slot per spec step
        self.draft_accepted = 0        # drafts the verify forward kept
        self.acceptance_ema: Optional[float] = None
        # robustness counters
        self.preemptions = 0           # evict-and-requeue events
        self.resumes = 0               # preempted requests re-admitted
        self.rejected = 0              # terminal REJECTED (validation,
                                       # watchdog, preemption give-up)
        self.shed = 0                  # bounded-queue overload rejects
        self.cancelled = 0
        self.timed_out = 0
        self.watchdog_fires = 0
        self.transfer_faults = 0       # TransferFault steps retried
        # pipelined stepping
        self.steps_in_flight = 0       # peak dispatched-but-unfetched steps

    def start(self) -> None:
        if self.t_start is None:
            self.t_start = time.perf_counter()

    def observe(self, req: Request) -> None:
        self.ttfts.append(req.ttft)
        self.tpots.append(req.tpot)
        self.prompt_tokens += len(req.prompt)
        self.output_tokens += len(req.output)
        self.t_last = req.t_done

    def observe_spec(self, accepted: int, proposed: int,
                     alpha: float = 0.2) -> None:
        """One speculative step's acceptance, summed over active slots."""
        if proposed <= 0:
            return
        self.spec_steps += 1
        self.draft_accepted += accepted
        self.draft_proposed += proposed
        rate = accepted / proposed
        self.acceptance_ema = (rate if self.acceptance_ema is None
                               else (1 - alpha) * self.acceptance_ema
                               + alpha * rate)

    def summary(self) -> Dict[str, Any]:
        """TTFT/TPOT percentiles (ms) + output-token throughput.  Safe on
        an engine that never finished a request: every percentile list
        may be empty and every denominator zero."""
        def pct(xs: List[float]) -> Dict[str, float]:
            if not xs:
                return {"p50": 0.0, "p90": 0.0, "p99": 0.0, "mean": 0.0}
            a = np.asarray(xs, np.float64) * 1e3
            return {"p50": float(np.percentile(a, 50)),
                    "p90": float(np.percentile(a, 90)),
                    "p99": float(np.percentile(a, 99)),
                    "mean": float(np.mean(a))}

        elapsed = ((self.t_last or time.perf_counter()) - self.t_start
                   if self.t_start is not None else 0.0)
        return {
            "requests": len(self.ttfts),
            "prompt_tokens": self.prompt_tokens,
            "output_tokens": self.output_tokens,
            "max_active": self.max_active,
            "elapsed_s": elapsed,
            "throughput_tok_s": (self.output_tokens / elapsed
                                 if elapsed > 0 else 0.0),
            "ttft_ms": pct(self.ttfts),
            "tpot_ms": pct(self.tpots),
            "spec_steps": self.spec_steps,
            "acceptance_rate": (self.draft_accepted / self.draft_proposed
                                if self.draft_proposed else 0.0),
            "acceptance_ema": (self.acceptance_ema
                               if self.acceptance_ema is not None else 0.0),
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "rejected": self.rejected,
            "shed": self.shed,
            "cancelled": self.cancelled,
            "timed_out": self.timed_out,
            "watchdog_fires": self.watchdog_fires,
            "transfer_faults": self.transfer_faults,
            "steps_in_flight": self.steps_in_flight,
        }


class EngineStallError(RuntimeError):
    """``Engine.run`` exhausted its step budget with work still pending.
    ``diagnostic`` is the queued/active/pool snapshot at the stall."""

    def __init__(self, message: str, diagnostic: Dict[str, Any]):
        super().__init__(message)
        self.diagnostic = diagnostic


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class Scheduler:
    """FCFS admission over a fixed slot table, budgeted by prefill tokens.

    ``plan_admission`` pops queued requests in order while free slots,
    the per-round padded-token budget and (paged mode) free KV blocks
    last, grouping the admitted set by prefill bucket so each group runs
    as one batched prefill.  Strict FCFS: the first request that does not
    fit the remaining budget or the block pool stops admission for the
    round (no skipping ahead), except that one oversized request is
    always admitted alone rather than livelocking.
    """

    def __init__(self, max_slots: int, bucket_fn: Callable[[int], int],
                 max_waiting_prefill_tokens: int = 4096,
                 charge_fn: Optional[Callable[[Request], int]] = None):
        self.max_slots = max_slots
        self.bucket_fn = bucket_fn
        # charge_fn prices a request in prefill tokens per admission
        # round; it takes the whole Request so prefix-aware runners can
        # charge only the uncached tail of the prompt.  Lengths are of
        # ``seq_tokens`` (prompt + generated) so a preempted request is
        # priced for its full recompute
        self.charge_fn = charge_fn or (lambda r: bucket_fn(len(r.seq_tokens)))
        self.max_waiting_prefill_tokens = max_waiting_prefill_tokens
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_slots

    # -- queue / slot bookkeeping --------------------------------------
    def submit(self, req: Request) -> None:
        req.state = RequestState.QUEUED
        self.queue.append(req)

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def active_slots(self) -> List[Tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.slots) if r is not None]

    def release(self, slot: int) -> None:
        self.slots[slot] = None

    def remove(self, req: Request) -> bool:
        """Drop a queued request (cancel / deadline / watchdog shed)."""
        try:
            self.queue.remove(req)
            return True
        except ValueError:
            return False

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    # -- admission ------------------------------------------------------
    def plan_admission(self, can_fit: Optional[Callable[[Request], bool]]
                       = None) -> List[Tuple[int, List[Tuple[int, Request]]]]:
        """[(bucket, [(slot, request), ...]), ...] for this round.

        ``can_fit`` (paged mode) checks KV-block availability for the
        head-of-line request; a head that does not fit waits — blocks
        free as running requests finish — and nothing skips past it.
        """
        free = self.free_slots()
        budget = self.max_waiting_prefill_tokens
        groups: Dict[int, List[Tuple[int, Request]]] = {}
        admitted = 0
        while free and self.queue:
            head = self.queue[0]
            if can_fit is not None and not can_fit(head):
                break                      # wait for blocks, never skip
            bucket = self.bucket_fn(len(head.seq_tokens))
            if self.charge_fn(head) > budget and admitted:
                break                      # strict FCFS: wait, don't skip
            req = self.queue.popleft()
            slot = free.pop(0)
            self.slots[slot] = req
            req.state = RequestState.PREFILL
            groups.setdefault(bucket, []).append((slot, req))
            budget -= self.charge_fn(req)
            admitted += 1
        return sorted(groups.items())


# ---------------------------------------------------------------------------
# model runner
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Capability:
    """One serving feature's static support verdict for an architecture:
    ``supported`` plus a human-readable ``reason`` when it is not."""
    supported: bool
    reason: Optional[str] = None


def arch_capabilities(cfg: ModelConfig) -> Dict[str, Capability]:
    """Per-feature serving capabilities of an architecture, with recorded
    reasons for every gate.  This is the single source of truth the
    runner's feature gates, the serve launcher's startup report and the
    README support matrix all derive from — replacing the old ad-hoc
    ``pageable_arch`` / chunk-ok / spec-ok allowlists.

    Features:
      paged           — serve through the block-table cache (all decoder
                        archs; ring/state leaves stay dense per-slot
                        under the same block accounting)
      chunked_prefill — feed prompts chunk-by-chunk through the cache
      speculative     — track-speculative draft/verify decoding
      prefix_cache    — content-addressed block sharing across prompts
      int8_kv         — int8 block pools with fused dequant
      fork            — n-way copy-on-write request cloning
    """
    specs = [cfg.spec(nm) for nm in cfg.layer_names]
    has_moe = any(s.mlp == "moe" for s in specs)
    has_window = any(s.window is not None for s in specs)
    has_recurrent = any(s.mixer in RECURRENT_MIXERS for s in specs)
    has_mla = any(s.mixer == "mla" for s in specs)
    # every leaf a block-pool leaf: no per-slot ring/state rows at all
    all_paged = not (has_window or has_recurrent)

    def cap(ok: bool, why: Optional[str]) -> Capability:
        return Capability(ok, None if ok else why)

    paged = cap(cfg.encdec is None,
                "encoder-decoder cross-attention caches are per-request "
                "dense; served through the contiguous cache")
    chunked = cap(paged.supported and not has_moe,
                  paged.reason if not paged.supported else
                  "capacity-based MoE routing is batch-global: a padded "
                  "chunk row would steal expert capacity from real tokens")
    dense_reason = ("sliding-window ring leaves are per-slot rows, not "
                    "content-addressable blocks" if has_window else
                    "recurrent state is a per-slot row, not a "
                    "content-addressable block" if has_recurrent else None)
    prefix = cap(chunked.supported and all_paged,
                 dense_reason or chunked.reason)
    speculative = cap(cfg.pt is not None and chunked.supported and all_paged,
                      "track-speculative decoding needs the PT track "
                      "structure to slice a drafter from"
                      if cfg.pt is None else
                      dense_reason and (dense_reason + "; rejected draft "
                                        "tokens could not be rolled back")
                      or chunked.reason)
    int8_kv = cap(chunked.supported and all_paged and not has_mla,
                  dense_reason or chunked.reason or
                  "int8 quantization of MLA latent pools is unvalidated")
    fork = cap(paged.supported, paged.reason)
    return {"paged": paged, "chunked_prefill": chunked,
            "speculative": speculative, "prefix_cache": prefix,
            "int8_kv": int8_kv, "fork": fork}


class ModelRunner:
    """Device side: cache + jitted prefill / chunk / decode programs."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_seq_len: int, par=NO_PARALLEL, min_bucket: int = 16,
                 paged: bool = True, block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 0,
                 speculate_k: int = 0, draft_tracks: int = 0,
                 prefix_cache: bool = True,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 fault_plan: Optional[FaultPlan] = None):
        if cfg.encdec is not None:
            raise ValueError("engine serves decoder-only models")
        if kv_dtype not in (None, "float32", "int8"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
        if weight_dtype not in (None, "float32", "int8"):
            raise ValueError(f"unsupported weight_dtype {weight_dtype!r}")
        self.cfg = cfg
        self.params = params
        self.par = par
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.min_bucket = min_bucket
        self.faults = fault_plan       # deterministic fault injection
        self.fns = steps_lib.model_fns(cfg)
        # requested dtypes; effective values (self.kv_dtype /
        # self.weight_dtype) are set below after the layout gates, with
        # human-readable fallback reasons in self.quant_fallbacks
        self.kv_dtype: Optional[str] = None
        self.weight_dtype: Optional[str] = None
        self.quant_fallbacks: List[str] = []
        # padded tokens corrupt length-sensitive layers: recurrent state
        # (conv window / SSM state) carries them forward, and capacity-
        # based MoE routing lets them consume expert-capacity slots that
        # belong to real tokens — those architectures prefill at exact
        # prompt length instead of a bucket
        self.exact_prefill = any(
            cfg.spec(nm).mixer in RECURRENT_MIXERS
            or cfg.spec(nm).mlp == "moe" for nm in cfg.layer_names)

        # every feature gate below reads the per-arch capability table
        # (one source of truth, with recorded reasons) instead of its own
        # allowlist
        self.capabilities = arch_capabilities(cfg)
        caps = self.capabilities
        self.kv: Optional[PagedKVCache] = None
        self.paged = paged and caps["paged"].supported
        # int8 KV shares the chunked-prefill gate: every cold prefill is
        # funneled through the chunk program so cold and warm requests
        # attend to identical quantized pool bytes (warm == cold parity).
        want_int8_kv = kv_dtype == "int8"
        int8_kv_ok = self.paged and caps["int8_kv"].supported
        if want_int8_kv and not int8_kv_ok:
            self.quant_fallbacks.append(
                "kv_dtype=int8: "
                + (caps["int8_kv"].reason if self.paged and
                   caps["int8_kv"].reason else "needs the paged cache")
                + "; serving fp KV")
        eff_kv = "int8" if (want_int8_kv and int8_kv_ok) else None
        if self.paged:
            self.kv = PagedKVCache(self.fns["init_cache"], cfg,
                                   max_slots=max_slots,
                                   max_seq_len=max_seq_len,
                                   block_size=block_size,
                                   num_blocks=num_blocks,
                                   kv_dtype=eff_kv,
                                   fault_plan=fault_plan)
            self.kv_dtype = eff_kv
            self.cache = wrap_paged(self.kv.data, self.kv.pageable,
                                    self.kv.scales)
            self._axes, self._seq = self.kv.axes, self.kv.seq
            self._pageable = self.kv.pageable
        else:
            self.cache = self.fns["init_cache"](cfg, max_slots, max_seq_len)
            self._axes = batch_axes(self.fns["init_cache"], cfg)
        # dense (ring/state) leaves riding inside the paged cache need
        # explicit row lifecycle ops: zeroing on chunked re-admission
        # (stale rows from the slot's previous tenant) and physical row
        # copies on fork (the block table shares only pool leaves)
        self.has_dense_leaves = self.paged and not self.kv.all_pageable
        # how paged decode attention reads the pool (``serve.launch``'s
        # ``attn``): a GQA layer paged at full capacity (a window below
        # it keeps a dense ring) streams its live blocks through the
        # kernel where ``paged_decode_kernel`` allows, else gathers
        self.decode_attn = "paged_kernel" if self.paged and any(
            s.mixer == "gqa"
            and (s.window is None or s.window >= max_seq_len)
            and paged_decode_kernel(cfg, s, par, self.kv_dtype == "int8")
            for s in map(cfg.spec, cfg.layer_names)) else "gather"

        # chunked prefill feeds the prompt through the cache with
        # multi-token decode-style steps; paged leaves append through the
        # block table, rings through the in-chunk side buffer, recurrent
        # state through masked chunk updates.  The warm tail prefill
        # behind prefix-cache hits is the same program.
        chunk_ok = self.paged and caps["chunked_prefill"].supported
        self.prefill_chunk = prefill_chunk if chunk_ok else 0
        self.prefix_cache = (prefix_cache and self.paged
                             and caps["prefix_cache"].supported)
        if self.kv is not None:
            self.kv.prefix_cache = self.prefix_cache

        # track-speculative decoding: the drafter is a track slice with a
        # dense per-slot cache; the verify forward is the chunk path
        self.speculate_k = 0
        self.draft_tracks = 0
        spec_ok = (speculate_k > 0 and self.paged
                   and caps["speculative"].supported)
        if spec_ok:
            self.speculate_k = speculate_k
            d = draft_tracks or max(1, cfg.pt.n_tracks // 2)
            self.draft_tracks = min(d, cfg.pt.n_tracks)
            self.draft_cfg = pt_lib.pt_draft_config(cfg, self.draft_tracks)
            self.draft_params = pt_lib.pt_draft_params(params, cfg,
                                                       self.draft_tracks)
            # lightweight per-slot draft cache: dense, since the drafter
            # is narrow (d of n tracks) — no paging machinery needed
            self.draft_cache = pt_lib.pt_init_cache(self.draft_cfg,
                                                    max_slots, max_seq_len)
            self._draft_axes = batch_axes(
                lambda c, b, s: pt_lib.pt_init_cache(self.draft_cfg, b, s),
                cfg)
            self._draft_prefill = jax.jit(self._draft_prefill_impl)
            self._draft_insert = jax.jit(self._draft_insert_impl,
                                         donate_argnums=(0,))
            self._draft_chunk = jax.jit(self._draft_chunk_impl,
                                        donate_argnums=(1,))
            self._spec = jax.jit(self._spec_impl, donate_argnums=(2, 3),
                                 static_argnames=("max_len",))
            self.draft_prefill_shapes: set = set()
            self.draft_chunk_shapes: set = set()

        # int8 weights: quantize AFTER the draft-track slice so the
        # drafter is cut from fp params and quantized independently
        # (slicing a QuantTensor tree would de-align payload and scale
        # rules); leaves without a quantization rule (norms, embeddings,
        # MLA latents, SSM/rglru state mixers, MoE experts) pass through
        # in fp — that IS the layout fallback.
        self.n_quantized = 0
        if weight_dtype == "int8":
            from repro.common.quant import quantize_params
            self.params, self.n_quantized = quantize_params(self.params)
            if self.n_quantized:
                self.weight_dtype = "int8"
                if self.speculate_k:
                    self.draft_params, _ = quantize_params(self.draft_params)
            else:
                self.quant_fallbacks.append(
                    "weight_dtype=int8: no quantizable weight leaves in "
                    "this architecture; serving fp weights")

        # the cache argument is dead after each call (self.cache is
        # rebound to the result), so donate it — on GPU/TPU the update
        # happens in place instead of copying the full KV cache per
        # token (CPU ignores donation with a warning)
        self._prefill = jax.jit(self._prefill_impl)
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,),
                               static_argnames=("max_len",))
        self._chunk = jax.jit(self._chunk_impl, donate_argnums=(1,))
        self._copy_blocks = jax.jit(self._copy_blocks_impl,
                                    donate_argnums=(0,))
        if self.has_dense_leaves:
            self._reset_slots = jax.jit(self._reset_slots_impl,
                                        donate_argnums=(0,))
            self._dense_fork = jax.jit(self._dense_fork_impl,
                                       donate_argnums=(0,))
        if self.speculate_k:
            self._draft_fork = jax.jit(self._draft_fork_impl,
                                       donate_argnums=(0,))
        # pipelined stepping: jitted device-carry composers — step N+1's
        # (token, pos, counter, remaining) inputs computed from step N's
        # packed result ON DEVICE, so consecutive steps chain without a
        # host round-trip — plus the pre-planned (AOT-compiled)
        # per-bucket step executables keyed by (kind, max_len bucket)
        self._advance_decode = jax.jit(advance_decode)
        self._advance_spec = jax.jit(advance_spec)
        self._planned: Dict[Tuple[str, Optional[int]], Any] = {}
        self.planned_hits = 0          # dispatches served pre-planned
        self._table_key = None             # (kv.version, active bytes)
        self._table_dev = None             # cached device block table
        self.prefill_shapes: set = set()   # observed (n_reqs, bucket)
        self.chunk_shapes: set = set()     # observed (n_reqs, chunk)
        self.decode_transfers = 0          # host transfers in decode steps
        self.decode_kernel_calls = 0       # decode calls on the kernel
        self.decode_live_blocks = 0        # pool blocks they streamed
        self.prefill_calls = 0             # bucketed prefill forwards
        self.chunk_calls = 0               # chunk forwards (incl. warm tails)

    # -- bucket policy --------------------------------------------------
    def bucket_for(self, length: int) -> int:
        """Power-of-two padding bucket (identity for recurrent archs)."""
        if length > self.max_seq_len:
            raise ValueError(f"prompt length {length} exceeds engine "
                             f"capacity {self.max_seq_len}")
        if self.exact_prefill:
            return length
        b = self.min_bucket
        while b < length:
            b *= 2
        return min(b, self.max_seq_len)

    def admission_charge(self, req: "Request") -> int:
        """Prefill tokens a request costs per admission round: the padded
        bucket of its *uncached* tail (the prefix-cache hit costs no
        compute; a preempted request recomputing mostly-cached tokens is
        priced for only the uncached remainder), or one chunk when
        chunked prefill spreads the rest over subsequent steps."""
        length = len(req.seq_tokens)
        if self.prefix_cache:
            matched, _ = self.kv.match_prefix(req.seq_tokens)
            length -= matched
        bucket = self.bucket_for(length)
        return min(bucket, self.prefill_chunk) if self.prefill_chunk \
            else bucket

    def cache_stats(self) -> Dict[str, Any]:
        """Cache mode + occupancy (paged) for benchmarks/metrics."""
        quant = {"weight_dtype": self.weight_dtype or "float32",
                 "quantized_weight_leaves": self.n_quantized,
                 "quant_fallbacks": list(self.quant_fallbacks)}
        if not self.paged:
            return {"mode": "contiguous", **quant}
        stats = dict(self.kv.utilization())
        stats.update(mode="paged", block_size=self.kv.block_size,
                     pool_bytes=self.kv.pool_bytes(), **quant)
        return stats

    # -- jitted programs -------------------------------------------------
    def _prefill_impl(self, params, tokens, lengths, seeds, counters,
                      temps, tks, tps):
        """tokens [n, bucket] right-padded; lengths [n] true lengths.
        Returns (first sampled token [n], prefill cache).  The sampled
        token is draw ``counters[i]`` of each request's own key stream —
        0 for a fresh prompt, m for a preempted request recomputing with
        m tokens already emitted, so the resume continues the identical
        sample sequence."""
        batch = {"inputs": tokens, "lengths": lengths}
        logits, cache, _ = self.fns["forward"](params, batch, self.cfg,
                                               self.par, mode="prefill")
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        keys = prefill_keys(seeds, counters)
        toks = sample_rows(last, keys, temps, tks, tps)
        return toks, cache

    def _insert_impl(self, dst, src, slots, table_rows):
        if self.paged:
            # dst stays wrapped: paged_insert_rows scatters payload AND
            # scale pools of quantized leaves (src rows quantized inline)
            return paged_insert_rows(dst, src, self._axes, self._seq,
                                     self._pageable, slots, table_rows,
                                     self.kv.block_size)
        return insert_rows(dst, src, self._axes, slots)

    def _decode_impl(self, params, cache, toks, pos, active, table, seeds,
                     counts, temps, tks, tps, eos, remaining, max_len=None):
        """One decode step for all slots + sampling + done flags, all on
        device.  Returns (cache, packed [2, slots] int32 = (token, done)).
        ``active`` threads into the model so dense (ring/state) rows of
        lanes that are idle or mid-chunked-prefill stay frozen — paged
        leaves are protected by the zeroed table rows instead.
        ``max_len`` (static) would bound the MLA latent gather to a live
        prefix; dispatch passes none, so one decode program serves every
        live length."""
        if self.paged:
            logits, cache = self.fns["decode"](params, cache, toks, pos,
                                               self.cfg, self.par,
                                               block_table=table,
                                               kv_max_len=max_len,
                                               active=active)
        else:
            logits, cache = self.fns["decode"](params, cache, toks, pos,
                                               self.cfg, self.par,
                                               active=active)
        keys = row_keys(seeds, counts, SALT_SAMPLE)
        return cache, sample_step(logits, keys, temps, tks, tps, active,
                                  eos, remaining)

    def _chunk_impl(self, params, cache, toks, pos, table_rows, slots,
                    last_idx, seeds, counters, temps, tks, tps):
        """One prefill chunk for n requests: toks [n, C] appended at
        positions pos[:, None] + arange(C).  Returns (cache, candidate
        first token [n] sampled at each row's last real prompt row —
        meaningful only for rows whose final chunk this is).  The draw
        uses ``counters[i]`` of each row's key stream (0 fresh, m for a
        preempted resume) — see ``_prefill_impl``.

        ``slots`` maps chunk rows to engine slots so per-slot dense
        (ring/state) leaves gather/scatter their rows; ``last_idx + 1``
        is each row's valid token count, so a padded final chunk does
        identity updates on recurrent state past it.  Both are dead code
        (DCE'd) for all-paged architectures."""
        logits, cache = self.fns["chunk"](params, cache, toks, pos,
                                          self.cfg, self.par,
                                          block_table=table_rows,
                                          slots=slots,
                                          chunk_lens=last_idx + 1)
        last = jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0]
        keys = prefill_keys(seeds, counters)
        return cache, sample_rows(last, keys, temps, tks, tps)

    def _draft_prefill_impl(self, draft_params, tokens, lengths):
        """Populate the drafter's dense cache for one admitted prompt
        (the sampled first token comes from the TARGET prefill; only the
        draft KV is needed here)."""
        batch = {"inputs": tokens, "lengths": lengths}
        _, cache, _ = pt_lib.pt_forward(draft_params, batch, self.draft_cfg,
                                        self.par.without_axis("track"),
                                        mode="prefill")
        return cache

    def _draft_insert_impl(self, dst, src, slots):
        return insert_rows(dst, src, self._draft_axes, slots)

    def _copy_blocks_impl(self, cache, src, dst):
        """Copy-on-write block duplication: pool[dst[i]] = pool[src[i]]
        for every pageable leaf.  Gathers happen before any scatter, so a
        block shared n ways can fan out to n copies in one call; padded
        (0, 0) pairs are trash-block self-copies (no-ops)."""
        def move(pool, bax):
            moved = jnp.moveaxis(pool, bax, 0)
            moved = moved.at[dst].set(moved[src])
            return jnp.moveaxis(moved, 0, bax)

        def cp(leaf, bax, pg):
            if not pg:
                return leaf
            if is_paged(leaf):
                # quantized pools: the scale rows fork with the payload,
                # or a CoW copy would dequantize with the wrong scales
                scale = None if leaf.scale is None else move(leaf.scale,
                                                             bax)
                return PagedLeaf(move(leaf.pool, bax), scale)
            return move(leaf, bax)
        return jax.tree_util.tree_map(
            cp, cache, self._axes, self._pageable,
            is_leaf=lambda l: l is None or is_paged(l))

    def _draft_fork_impl(self, cache, srcs, dsts):
        """Clone dense per-slot drafter rows: row[dsts[i]] = row[srcs[i]]
        (padded entries are src-to-src identity copies)."""
        def cp(leaf, bax):
            moved = jnp.moveaxis(leaf, bax, 0)
            moved = moved.at[dsts].set(moved[srcs])
            return jnp.moveaxis(moved, 0, bax)
        return jax.tree_util.tree_map(cp, cache, self._draft_axes,
                                      is_leaf=lambda l: l is None)

    def _reset_slots_impl(self, cache, slots):
        """Zero the dense (ring/state) rows of ``slots``: a chunked
        admission appends to these rows incrementally, so the previous
        tenant's bytes must not seed the new request's recurrent state or
        ring window.  Paged leaves are untouched — the block table
        already isolates them."""
        def zero(leaf, bax, pg):
            if pg:
                return leaf
            moved = jnp.moveaxis(leaf, bax, 0)
            moved = moved.at[slots].set(
                jnp.zeros((), leaf.dtype))
            return jnp.moveaxis(moved, 0, bax)
        return jax.tree_util.tree_map(
            zero, cache, self._axes, self._pageable,
            is_leaf=lambda l: l is None or is_paged(l))

    def _dense_fork_impl(self, cache, srcs, dsts):
        """Physically copy the dense (ring/state) rows of the MAIN cache
        on fork: the block table shares only pool leaves, so children of
        a windowed/recurrent parent need their own copy of its per-slot
        rows (padded entries are src-to-src identity copies)."""
        def cp(leaf, bax, pg):
            if pg:
                return leaf
            moved = jnp.moveaxis(leaf, bax, 0)
            moved = moved.at[dsts].set(moved[srcs])
            return jnp.moveaxis(moved, 0, bax)
        return jax.tree_util.tree_map(
            cp, cache, self._axes, self._pageable,
            is_leaf=lambda l: l is None or is_paged(l))

    def _draft_chunk_impl(self, draft_params, draft_cache, toks, pos,
                          slots):
        """Advance the drafter's dense cache by one chunk per row: rows
        gathered at ``slots``, run through the PT chunk program with no
        block table (the dense multi-token append path), scattered back.
        Logits are discarded — only the K/V matters; positions past a
        row's valid tokens write pad K/V that decode's causal mask never
        reads before it is overwritten."""
        def take(leaf, bax):
            return jnp.moveaxis(jnp.moveaxis(leaf, bax, 0)[slots], 0, bax)
        rows = jax.tree_util.tree_map(take, draft_cache, self._draft_axes,
                                      is_leaf=lambda l: l is None)
        _, rows = pt_lib.pt_chunk_step(draft_params, rows, toks, pos,
                                       self.draft_cfg,
                                       self.par.without_axis("track"))
        return insert_rows(draft_cache, rows, self._draft_axes, slots)

    def _spec_impl(self, params, draft_params, cache, draft_cache, toks,
                   pos, active, table, seeds, counts, temps, tks, tps,
                   max_len=None):
        """One speculative step, fully on device: K sync-free draft steps
        (track-subset model, dense cache), ONE K+1-token verify forward
        for all slots against the paged cache, and batched rejection
        sampling.  Returns (cache, draft_cache, packed [K+2, slots])."""
        K = self.speculate_k
        tok = toks
        d_toks, d_logits = [], []
        # ``active`` freezes the drafter's dense rows of inactive lanes:
        # a slot mid-chunked-prefill is having its draft cache filled by
        # draft_chunk, and a stale-position write from the spec step of
        # OTHER slots would corrupt it (the paged target cache is
        # protected by zeroed table rows instead).
        for j in range(K):
            logits, draft_cache = pt_lib.pt_draft_step(
                draft_params, draft_cache, tok, pos + j, self.draft_cfg,
                self.par, active=active)
            keys = row_keys(seeds, counts + j, SALT_DRAFT)
            tok = sample_rows(logits, keys, temps, tks, tps)
            d_toks.append(tok)
            d_logits.append(logits)
        # one extra draft forward feeds d_K so its K/V lands at pos+K:
        # on the all-accepted path the next step starts from pos+K+1 and
        # the drafter must have seen every accepted position (a rejected
        # tail is simply overwritten next step).  Logits are discarded.
        _, draft_cache = pt_lib.pt_draft_step(
            draft_params, draft_cache, tok, pos + K, self.draft_cfg,
            self.par, active=active)
        seq = jnp.concatenate([toks[:, None]] + [t[:, None] for t in d_toks],
                              axis=1)                       # [B, K+1]
        tgt, cache = self.fns["verify"](params, cache, seq, pos, self.cfg,
                                        self.par, block_table=table,
                                        kv_max_len=max_len)
        packed = accept_step(tgt, jnp.stack(d_logits, axis=1),
                             jnp.stack(d_toks, axis=1), seeds, counts,
                             temps, tks, tps, active)
        return cache, draft_cache, packed

    # -- host-facing ops -------------------------------------------------
    def _maybe_inject_transfer(self, site: str) -> None:
        """Deterministic fault hook at every device-to-host transfer
        point, fired AFTER the device work of the step was issued (like a
        real dead copy): the engine un-does no device state, it simply
        retries — the retry recomputes identical bytes into identical
        positions, so the fault is bitwise-transparent."""
        if self.faults is not None and self.faults.take_transfer(site):
            raise TransferFault(
                f"injected device-to-host transfer failure at {site} "
                f"(op {self.faults.transfer_calls - 1})")

    def prefill(self, prompts: Sequence[Sequence[int]], bucket: int,
                slots: Sequence[int], seeds: Sequence[int],
                counters: Sequence[int],
                params_list: Sequence[SampleParams]) -> np.ndarray:
        """Batched prefill of ``prompts`` into cache ``slots``.  One
        jitted forward per (n, bucket) shape; returns first tokens [n]
        (each row's draw ``counters[i]``)."""
        n = len(prompts)
        tokens = np.zeros((n, bucket), np.int32)
        lengths = np.empty((n,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            lengths[i] = len(p)
        with _span("serve.inputs"):
            temps, tks, tps = stack_params(params_list)
            args = (self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(seeds, jnp.uint32),
                    jnp.asarray(counters, jnp.int32), jnp.asarray(temps),
                    jnp.asarray(tks), jnp.asarray(tps))
            table_rows = (self.kv.table_rows(slots) if self.paged
                          else jnp.zeros((n, 1), jnp.int32))
            slots_d = jnp.asarray(slots, jnp.int32)
        with _span("serve.launch"):
            toks, cache = self._prefill(*args)
            self.cache = self._insert(self.cache, cache, slots_d, table_rows)
        self.prefill_shapes.add((n, bucket))
        self.prefill_calls += 1
        self._maybe_inject_transfer("prefill")
        with _span("serve.fetch"):
            return np.asarray(toks)

    def chunk(self, toks: np.ndarray, pos: np.ndarray, slots: Sequence[int],
              last_idx: np.ndarray, seeds: Sequence[int],
              counters: Sequence[int],
              params_list: Sequence[SampleParams]) -> np.ndarray:
        """One chunk step for the currently-prefilling requests."""
        return self._run_chunk(toks, pos, slots, last_idx, seeds, counters,
                               params_list, "chunk")

    def _run_chunk(self, toks, pos, slots, last_idx, seeds, counters,
                   params_list, site: str) -> np.ndarray:
        """The chunk program on ``toks`` [n, C] at positions ``pos``;
        returns each row's candidate token (fault site ``site``)."""
        with _span("serve.inputs"):
            temps, tks, tps = stack_params(params_list)
            args = (self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(pos), self.kv.table_rows(slots),
                    jnp.asarray(slots, jnp.int32), jnp.asarray(last_idx),
                    jnp.asarray(seeds, jnp.uint32),
                    jnp.asarray(counters, jnp.int32), jnp.asarray(temps),
                    jnp.asarray(tks), jnp.asarray(tps))
        with _span("serve.launch"):
            self.cache, cand = self._chunk(*args)
        self.chunk_shapes.add(tuple(toks.shape))
        self.chunk_calls += 1
        self._maybe_inject_transfer(site)
        with _span("serve.fetch"):
            return np.asarray(cand)

    def warm_prefill(self, prompts: Sequence[Sequence[int]],
                     matched: Sequence[int], slots: Sequence[int],
                     seeds: Sequence[int], counters: Sequence[int],
                     params_list: Sequence[SampleParams]) -> np.ndarray:
        """Prefill only the uncached tails of prefix-matched prompts:
        tokens [matched_i, len_i) run through the chunk program at their
        true positions, attending to the shared cached blocks.  Sampling
        uses draw ``counters[i]`` of each request's key stream (0 fresh),
        so the first token is bitwise-identical to a cold full prefill.
        Returns first tokens [n]."""
        n = len(prompts)
        tails = [len(p) - m for p, m in zip(prompts, matched)]
        bucket = self.bucket_for(max(tails))
        toks = np.zeros((n, bucket), np.int32)
        pos = np.empty((n,), np.int32)
        last_idx = np.empty((n,), np.int32)
        for i, (p, m) in enumerate(zip(prompts, matched)):
            toks[i, :len(p) - m] = p[m:]
            pos[i] = m
            last_idx[i] = len(p) - m - 1
        return self._run_chunk(toks, pos, slots, last_idx, seeds, counters,
                               params_list, "warm_prefill")

    def copy_blocks(self, pairs: Sequence[Tuple[int, int]]) -> None:
        """Apply copy-on-write pairs from ``kv.ensure_writable`` to the
        device pool (one jitted scatter for the whole batch; the pair
        list is padded to a power of two with trash-block self-copies so
        compile variants stay O(log pairs))."""
        if not pairs:
            return
        n = 1
        while n < len(pairs):
            n *= 2
        pad = list(pairs) + [(0, 0)] * (n - len(pairs))
        src = jnp.asarray([p[0] for p in pad], jnp.int32)
        dst = jnp.asarray([p[1] for p in pad], jnp.int32)
        self.cache = self._copy_blocks(self.cache, src, dst)

    def draft_fork(self, src: int, dsts: Sequence[int]) -> None:
        """Clone the drafter's dense cache row ``src`` into rows ``dsts``
        (fork children need the parent's draft K/V; the paged target
        cache is shared by the block table instead)."""
        n = 1
        while n < len(dsts):
            n *= 2
        srcs = [src] * n
        pad = list(dsts) + [src] * (n - len(dsts))   # src->src no-ops
        self.draft_cache = self._draft_fork(
            self.draft_cache, jnp.asarray(srcs, jnp.int32),
            jnp.asarray(pad, jnp.int32))

    def draft_prefill(self, prompts: Sequence[Sequence[int]], bucket: int,
                      slots: Sequence[int]) -> None:
        """Populate the drafter's dense cache for newly-started requests
        (one batched narrow forward; the drafter is d of n tracks).  The
        bucketed-admission path; chunked admissions use ``draft_chunk``
        instead, so a long prompt never stalls the step loop at decode
        start."""
        n = len(prompts)
        tokens = np.zeros((n, bucket), np.int32)
        lengths = np.empty((n,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            lengths[i] = len(p)
        cache = self._draft_prefill(self.draft_params, jnp.asarray(tokens),
                                    jnp.asarray(lengths))
        self.draft_cache = self._draft_insert(
            self.draft_cache, cache, jnp.asarray(slots, jnp.int32))
        self.draft_prefill_shapes.add((n, bucket))

    def draft_chunk(self, toks: np.ndarray, pos: np.ndarray,
                    slots: Sequence[int]) -> None:
        """Advance the drafter's dense cache one chunk per prefilling
        row (``toks`` [n, C] at positions ``pos[:, None] + arange(C)``)
        — the chunked counterpart of ``draft_prefill``, interleaved with
        decode so the drafter is warm the step the target finishes."""
        self.draft_cache = self._draft_chunk(
            self.draft_params, self.draft_cache, jnp.asarray(toks),
            jnp.asarray(pos), jnp.asarray(slots, jnp.int32))
        self.draft_chunk_shapes.add(tuple(toks.shape))

    def reset_slots(self, slots: Sequence[int]) -> None:
        """Zero the dense (ring/state) rows of freshly-admitted chunked
        slots (no-op for all-paged architectures).  The slot list pads to
        a power of two with duplicates so compile variants stay
        O(log slots)."""
        if not self.has_dense_leaves or not slots:
            return
        n = 1
        while n < len(slots):
            n *= 2
        pad = list(slots) + [slots[0]] * (n - len(slots))
        self.cache = self._reset_slots(self.cache,
                                       jnp.asarray(pad, jnp.int32))

    def dense_fork(self, src: int, dsts: Sequence[int]) -> None:
        """Copy the main cache's dense (ring/state) rows of ``src`` into
        ``dsts`` on fork (no-op for all-paged architectures)."""
        if not self.has_dense_leaves:
            return
        n = 1
        while n < len(dsts):
            n *= 2
        pad = list(dsts) + [src] * (n - len(dsts))   # src->src no-ops
        self.cache = self._dense_fork(
            self.cache, jnp.asarray([src] * n, jnp.int32),
            jnp.asarray(pad, jnp.int32))

    def _masked_table(self, active) -> Any:
        """Device block table with inactive lanes zeroed (their writes
        land in the trash block).  Cached across steps; only rebuilt on
        allocate/free/active-set transitions."""
        act = np.asarray(active, bool)
        key_now = (self.kv.version, act.tobytes())
        if key_now != self._table_key:
            self._table_dev = jnp.asarray(
                self.kv.table_np * act.astype(np.int32)[:, None])
            self._table_key = key_now
        return self._table_dev

    def _live_max_len(self, pos, active, extra: int = 0) -> Optional[int]:
        """Static power-of-two-block bound on the live cache prefix
        (compile variants stay O(log blocks))."""
        act = np.asarray(active, bool)
        if not act.any():
            return None
        bs = self.kv.block_size
        need = -(-(int(np.asarray(pos)[act].max()) + 1 + extra) // bs)
        p2 = 1
        while p2 < need:
            p2 *= 2
        return min(self.kv.blocks_per_seq, p2) * bs

    # -- decode / speculative steps: dispatch + wait -------------------
    #
    # Every step is split into a DISPATCH (enqueue the jitted program,
    # return immediately with a handle holding the still-on-device
    # packed result) and a WAIT (the one host transfer).  The sync path
    # is simply dispatch immediately followed by wait; the pipelined
    # engine dispatches step N+1 before waiting on step N, composing
    # N+1's inputs from N's device-resident packed result (``carry``).
    # ``override`` marks lanes whose inputs must come from the host
    # arrays instead (newly admitted / forked / re-assigned slots).

    def dispatch_decode(self, toks, pos, active, seeds, counts, temps,
                        tks, tps, eos, remaining, *, carry=None,
                        override=None) -> Dict[str, Any]:
        """Dispatch one decode step; no host transfer happens here.

        One decode program serves every live length: the paged kernel
        reads the lengths at run time, and the gather reads the whole
        capacity.  ``serve.launch`` names the path (``attn``) and, for
        the kernel, the pool blocks the call streams (``live_blocks``,
        from the host's positions, which lag in-flight steps) of the
        ``blocks`` the gather would read."""
        with _span("serve.inputs"):
            if self.paged:
                # lanes not actively decoding (idle, or mid-chunked-
                # prefill) get zeroed table rows: their stale-position
                # writes land in the trash block, never in blocks owned
                # by live requests.
                table = self._masked_table(active)
            else:
                table = jnp.zeros((len(toks), 1), jnp.int32)
            tok_d, pos_d = jnp.asarray(toks), jnp.asarray(pos)
            counts_d = jnp.asarray(counts, jnp.int32)
            rem_d = jnp.asarray(remaining)
            if carry is not None:
                tok_d, pos_d, counts_d, rem_d = self._advance_decode(
                    carry["packed"], carry["tok"], carry["pos"],
                    carry["counts"], carry["remaining"],
                    jnp.asarray(override), tok_d, pos_d, counts_d, rem_d)
            args = (self.params, self.cache, tok_d, pos_d,
                    jnp.asarray(active), table,
                    jnp.asarray(seeds, jnp.uint32), counts_d,
                    jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
                    jnp.asarray(eos), rem_d)
            span = {"attn": self.decode_attn}
            if self.decode_attn == "paged_kernel":
                act = np.asarray(active, bool)
                live = int(np.sum(-(-(np.asarray(pos)[act] + 1)
                                    // self.kv.block_size)))
                span.update(live_blocks=live, blocks=self.max_slots
                            * self.kv.blocks_per_seq)
                self.decode_kernel_calls += 1
                self.decode_live_blocks += live
        with _span("serve.launch", **span):
            planned = self._planned.get(("decode", None))
            if planned is not None:
                self.cache, packed = planned(*args)
                self.planned_hits += 1
            else:
                self.cache, packed = self._decode(*args)
        return {"kind": "decode", "packed": packed, "tok": tok_d,
                "pos": pos_d, "counts": counts_d, "remaining": rem_d,
                "active": np.asarray(active, bool).copy()}

    def wait_decode(self, handle: Dict[str, Any]
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """The one host transfer of a dispatched decode step."""
        self._maybe_inject_transfer("decode")
        with _span("serve.fetch"):
            host = np.asarray(handle["packed"])    # THE transfer
        self.decode_transfers += 1
        return host[0], host[1].astype(bool)

    def decode(self, toks, pos, active, seeds, counts, temps, tks, tps,
               eos, remaining) -> Tuple[np.ndarray, np.ndarray]:
        """One synchronous decode step.  Exactly one host transfer: the
        packed (token, done) array."""
        return self.wait_decode(self.dispatch_decode(
            toks, pos, active, seeds, counts, temps, tks, tps, eos,
            remaining))

    def dispatch_spec(self, toks, pos, active, seeds, counts, temps, tks,
                      tps, *, carry=None, override=None,
                      extra_len: int = 0) -> Dict[str, Any]:
        """Dispatch one speculative (draft+verify) step; no transfer."""
        with _span("serve.inputs"):
            table = self._masked_table(active)
            # under ``use_pallas`` the verify gather reads only the live
            # prefix; otherwise it reads the whole capacity, as the jnp
            # decode does, so verify logits stay bitwise-identical to the
            # single-token decode path (greedy spec == greedy plain)
            max_len = None
            if self.cfg.use_pallas:
                max_len = self._live_max_len(
                    pos, active, extra=self.speculate_k + extra_len)
            tok_d, pos_d = jnp.asarray(toks), jnp.asarray(pos)
            counts_d = jnp.asarray(counts, jnp.int32)
            if carry is not None:
                tok_d, pos_d, counts_d = self._advance_spec(
                    carry["packed"], carry["tok"], carry["pos"],
                    carry["counts"], jnp.asarray(override), tok_d, pos_d,
                    counts_d)
            args = (self.params, self.draft_params, self.cache,
                    self.draft_cache, tok_d, pos_d, jnp.asarray(active),
                    table, jnp.asarray(seeds, jnp.uint32), counts_d,
                    jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps))
        with _span("serve.launch"):
            planned = self._planned.get(("spec", max_len))
            if planned is not None:
                self.cache, self.draft_cache, packed = planned(*args)
                self.planned_hits += 1
            else:
                self.cache, self.draft_cache, packed = self._spec(
                    *args, max_len=max_len)
        return {"kind": "spec", "packed": packed, "tok": tok_d,
                "pos": pos_d, "counts": counts_d,
                "active": np.asarray(active, bool).copy()}

    def wait_spec(self, handle: Dict[str, Any]
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """The one host transfer of a dispatched speculative step."""
        self._maybe_inject_transfer("draft_verify")
        with _span("serve.fetch"):
            host = np.asarray(handle["packed"])    # THE transfer
        self.decode_transfers += 1
        return host[:-1].T, host[-1]

    def draft_verify(self, toks, pos, active, seeds, counts, temps, tks,
                     tps) -> Tuple[np.ndarray, np.ndarray]:
        """One synchronous speculative step for all decoding slots.
        Exactly one host transfer: the packed (tokens ‖ emitted-count)
        array.  Returns (tokens [slots, K+1], counts [slots])."""
        return self.wait_spec(self.dispatch_spec(
            toks, pos, active, seeds, counts, temps, tks, tps))

    def plan_programs(self) -> int:
        """Pre-plan the steady-state step programs: AOT-lower and
        compile the decode executable (and, when speculating, one spec
        executable per ``max_len`` bucket), so dispatch replays a ready
        program with
        the tracer entirely off the hot path — the CUDA-graph-per-
        batch-size pattern of flashinfer-style runners.  Dispatch falls
        back to the ``jax.jit`` wrapper for any unplanned shape.
        Returns the number of planned executables."""
        B = self.max_slots
        toks = jnp.zeros((B,), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        active = jnp.zeros((B,), bool)
        seeds = jnp.zeros((B,), jnp.uint32)
        counts = jnp.zeros((B,), jnp.int32)
        temps = jnp.zeros((B,), jnp.float32)
        tks = jnp.zeros((B,), jnp.int32)
        tps = jnp.ones((B,), jnp.float32)
        eos = jnp.full((B,), -1, jnp.int32)
        rem = jnp.zeros((B,), jnp.int32)
        if self.paged:
            table = jnp.zeros_like(jnp.asarray(self.kv.table_np))
        else:
            table = jnp.zeros((B, 1), jnp.int32)
        if ("decode", None) not in self._planned:
            self._planned[("decode", None)] = steps_lib.aot_compile(
                self._decode, self.params, self.cache, toks, pos, active,
                table, seeds, counts, temps, tks, tps, eos, rem)
        # the verify gather: one variant per power-of-two live-block
        # bound (pallas), else the single ``None`` variant
        variants: List[Optional[int]] = [None]
        if self.speculate_k and self.cfg.use_pallas:
            bs, p2 = self.kv.block_size, 1
            while p2 <= self.kv.blocks_per_seq:
                variants.append(p2 * bs)
                p2 *= 2
        for max_len in variants:
            if self.speculate_k and ("spec", max_len) not in self._planned:
                self._planned[("spec", max_len)] = steps_lib.aot_compile(
                    self._spec, self.params, self.draft_params, self.cache,
                    self.draft_cache, toks, pos, active, table, seeds,
                    counts, temps, tks, tps, max_len=max_len)
        return len(self._planned)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class Engine:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 4,
                 max_seq_len: int = 256, par=NO_PARALLEL, seed: int = 0,
                 max_waiting_prefill_tokens: int = 4096,
                 min_bucket: int = 16, paged: bool = True,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 0, speculate_k: int = 0,
                 draft_tracks: int = 0, prefix_cache: bool = True,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 watchdog_patience: int = 25,
                 max_preemptions: int = 8,
                 fault_plan: Optional[FaultPlan] = None,
                 pipeline_depth: int = 0, preplan: bool = False,
                 runner: Optional[Any] = None):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        if runner is not None:
            # injected runner (e.g. the model-free StubRunner): anything
            # implementing the ModelRunner host-facing surface serves —
            # scheduler/pipeline semantics are testable in milliseconds
            # without compiling a single jitted program
            self.runner = runner
        else:
            self.runner = ModelRunner(cfg, params, max_slots=max_slots,
                                      max_seq_len=max_seq_len, par=par,
                                      min_bucket=min_bucket, paged=paged,
                                      block_size=block_size,
                                      num_blocks=num_blocks,
                                      prefill_chunk=prefill_chunk,
                                      speculate_k=speculate_k,
                                      draft_tracks=draft_tracks,
                                      prefix_cache=prefix_cache,
                                      kv_dtype=kv_dtype,
                                      weight_dtype=weight_dtype,
                                      fault_plan=fault_plan)
        if preplan:
            self.runner.plan_programs()
        self.scheduler = Scheduler(max_slots, self.runner.bucket_for,
                                   max_waiting_prefill_tokens,
                                   charge_fn=self.runner.admission_charge)
        self.metrics = EngineMetrics()
        self.seed = seed               # base for derived per-request seeds
        # robustness knobs: bounded queue (None = unbounded), stall
        # watchdog patience (consecutive no-progress steps before it
        # fires) and the per-request eviction cap (a request preempted
        # more often than this is REJECTED — termination guarantee)
        self.max_queue = max_queue
        self.watchdog_patience = watchdog_patience
        self.max_preemptions = max_preemptions
        self.faults = fault_plan
        self._stalled_steps = 0        # consecutive no-progress steps
        self._step_ema = None          # EMA of wall-clock step time (s),
                                       # feeds SLO admission estimates
        self._next_rid = 0
        self.steps_run = 0

        # per-slot device-step inputs, updated on admit/finish
        B = max_slots
        self._tok = np.zeros((B,), np.int32)
        self._pos = np.zeros((B,), np.int32)
        self._active = np.zeros((B,), bool)
        self._temps = np.zeros((B,), np.float32)
        self._topks = np.zeros((B,), np.int32)
        self._topps = np.ones((B,), np.float32)
        self._eos = np.full((B,), -1, np.int32)
        self._remaining = np.zeros((B,), np.int32)
        self._seeds = np.zeros((B,), np.uint32)    # per-request PRNG seed
        self._counts = np.zeros((B,), np.int32)    # tokens emitted so far
        self._emit_work = np.zeros((B,), np.int64)  # chunk + prefill calls
                                                    # at the last emission

        # pipelined stepping (pipeline_depth >= 1): dispatched steps
        # whose packed transfer has not been waited on yet, oldest first.
        # ``_host_fresh[slot]`` marks lanes whose host-side inputs are
        # authoritative for the next dispatch (newly admitted / forked);
        # carried lanes advance on device from the previous dispatch's
        # packed result instead.  ``_slot_gen`` counts slot reassignments
        # so an in-flight emission for a previous tenant (or a preempted-
        # and-resumed tenant) of the slot is discarded, never applied.
        self.pipeline_depth = pipeline_depth
        self._inflight: deque = deque()
        self._host_fresh = np.ones((B,), bool)
        self._slot_gen = np.zeros((B,), np.int64)

    def capabilities(self) -> Dict[str, Dict[str, Any]]:
        """Unified feature report for this (architecture, engine-config)
        pair: per feature, whether the architecture *supports* it (with
        the gating reason when not), and whether this engine instance has
        it *active* (a supported feature stays inactive when the caller
        didn't ask for it).  Quantization fallbacks fold in here — this
        is the single table the serve launcher prints and the README
        support matrix is generated from."""
        r = self.runner
        live = {"paged": r.paged,
                "chunked_prefill": r.prefill_chunk > 0,
                "speculative": r.speculate_k > 0,
                "prefix_cache": r.prefix_cache,
                "int8_kv": r.kv_dtype == "int8",
                "fork": r.paged}
        out: Dict[str, Dict[str, Any]] = {}
        for name, cap in r.capabilities.items():
            out[name] = {"supported": cap.supported, "reason": cap.reason,
                         "active": live[name]}
        wfall = next((f for f in r.quant_fallbacks
                      if f.startswith("weight_dtype")), None)
        kfall = next((f for f in r.quant_fallbacks
                      if f.startswith("kv_dtype")), None)
        out["int8_weights"] = {"supported": wfall is None, "reason": wfall,
                               "active": r.weight_dtype == "int8"}
        if kfall is not None and out["int8_kv"]["reason"] is None:
            # requested but gated off at runtime (e.g. engine not paged)
            out["int8_kv"]["reason"] = kfall
        return out

    # ------------------------------------------------------------------
    def _reserve_tokens(self, req: Request) -> int:
        """Cache positions a request occupies over its lifetime: prompt
        + decode writes (the last sampled token is never written)."""
        L = len(req.prompt)
        cap = self.max_seq_len - L + 1
        return L + min(req.max_new_tokens, cap) - 1

    def _estimate_completion_s(self, req: Request) -> float:
        """Optimistic submit-to-done estimate at current load, from the
        step-time EMA: the request's own prefill + decode steps, scaled
        by how many full queue waves run ahead of it.  Deliberately a
        LOWER bound (ignores chunk/decode cost asymmetry, preemption,
        compile stalls) — admission must only reject deadlines that are
        unmeetable even under ideal scheduling.  0.0 before any step has
        run: with no evidence, every deadline is admissible."""
        if self._step_ema is None:
            return 0.0
        L = len(req.prompt)
        C = self.runner.prefill_chunk
        prefill_steps = -(-L // C) if C else 1
        cap = self.max_seq_len - L + 1
        decode_steps = min(req.max_new_tokens, cap)
        if self.runner.speculate_k:
            decode_steps = -(-decode_steps // (self.runner.speculate_k + 1))
        own = (prefill_steps + decode_steps) * self._step_ema
        waves = len(self.scheduler.queue) // self.max_slots
        return own * (1 + waves)

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               params: SampleParams = SampleParams(),
               on_token: Optional[Callable[[Request, int], None]] = None,
               seed: Optional[int] = None, *, priority: int = 0,
               deadline_s: Optional[float] = None,
               on_event: Optional[Callable[[Request, str], None]] = None
               ) -> Request:
        """``seed`` keys this request's sampling stream; with the same
        seed a request replays bit-identically regardless of what else
        shares its batch.  Defaults to a deterministic function of the
        engine seed and the submission index.

        ``priority`` orders eviction under memory pressure (a higher-
        priority admission may preempt strictly-lower-priority decoders);
        ``deadline_s`` bounds submit-to-done time (exceeding it yields
        TIMED_OUT); a deadline the step-time EMA says is unmeetable at
        current queue depth is REJECTED on arrival instead
        (``finish_reason`` starts with ``unmeetable_deadline``), so the
        caller can retry elsewhere before burning compute; ``on_event``
        streams terminal transitions.

        Invalid requests (empty/overlong prompt, non-positive token
        budget, reservation larger than the whole block pool) and
        overload (bounded queue full) come back as a ``REJECTED`` request
        with ``finish_reason`` set, delivered through ``on_event`` — an
        exception never escapes into the caller's serving loop."""
        if seed is None:
            seed = (self.seed * 1_000_003 + self._next_rid) & 0x7FFFFFFF
        req = Request(self._next_rid, list(prompt), max_new_tokens, eos_id,
                      params, on_token, seed=seed, priority=priority,
                      deadline_s=deadline_s, on_event=on_event)
        req.t_submit = time.perf_counter()     # monotonic: latency math
        req.t_submit_wall = time.time()        # wall-clock: logs only
        self._next_rid += 1
        if not req.prompt:
            return self._reject(req, "empty prompt")
        if max_new_tokens <= 0:
            return self._reject(req, "max_new_tokens must be positive, "
                                     f"got {max_new_tokens}")
        if len(req.prompt) > self.max_seq_len:
            return self._reject(req, f"prompt length {len(req.prompt)} "
                                     "exceeds engine capacity "
                                     f"{self.max_seq_len}")
        kv = self.runner.kv
        if kv is not None and \
                kv.blocks_for(self._reserve_tokens(req)) > kv.num_blocks - 1:
            return self._reject(
                req,
                f"request needs {kv.blocks_for(self._reserve_tokens(req))} "
                f"KV blocks but the pool holds {kv.num_blocks - 1}")
        if deadline_s is not None:
            # SLO-aware admission: an optimistic completion estimate
            # already over budget means the request would only burn
            # compute before timing out — reject on arrival so the
            # caller can retry elsewhere.  est == 0.0 (no step has run
            # yet) admits unconditionally: no evidence, no rejection.
            est = self._estimate_completion_s(req)
            if est > deadline_s:
                return self._reject(
                    req, "unmeetable_deadline: needs "
                         f"~{est:.3f}s at current load, "
                         f"budget {deadline_s:.3f}s")
        if self.max_queue is not None \
                and len(self.scheduler.queue) >= self.max_queue:
            self.metrics.shed += 1
            return self._reject(req, f"queue full ({self.max_queue} "
                                     "waiting): overload shed",
                                count=False)
        self.metrics.start()
        self.scheduler.submit(req)
        return req

    # -- terminal transitions ------------------------------------------
    def _event(self, req: Request) -> None:
        if req.on_event is not None:
            req.on_event(req, req.finish_reason or req.state.value)

    def _reject(self, req: Request, reason: str, *,
                count: bool = True) -> Request:
        req.state = RequestState.REJECTED
        req.finish_reason = reason
        req.t_done = time.perf_counter()
        if count:
            self.metrics.rejected += 1
        self._event(req)
        return req

    def _slot_of(self, req: Request) -> Optional[int]:
        for s, r in self.scheduler.active_slots():
            if r is req:
                return s
        return None

    def _evict_slot(self, slot: int, req: Request) -> None:
        """Reclaim a slot whose request leaves mid-flight (preemption /
        cancel / timeout): commit every position actually written — the
        blocks park in the refcount-zero LRU, so an identical prompt (or
        this request's own resume) reuses them — then drop the refs."""
        self._active[slot] = False
        if self.runner.paged:
            kv = self.runner.kv
            if req.state is RequestState.DECODE:
                # [0, pos) is written: the prompt plus every emitted
                # token but the last (chunked-prefill rows committed
                # their finished chunks already)
                kv.commit_tokens(slot, req.seq_tokens[:-1])
            kv.free_slot(slot)
        self._slot_gen[slot] += 1      # in-flight emissions: discard
        self.scheduler.release(slot)

    def cancel(self, req: Request,
               reason: str = "cancelled by caller") -> bool:
        """Terminate a request wherever it is: drop it from the queue, or
        reclaim its slot and KV blocks mid-prefill/decode/spec.  Safe to
        call from a streaming callback mid-step — the decode loops skip
        slots whose request is gone.  Returns False when the request is
        already terminal."""
        if req.finished:
            return False
        if req.state is RequestState.QUEUED:
            self.scheduler.remove(req)
        else:
            slot = self._slot_of(req)
            if slot is not None:
                self._evict_slot(slot, req)
        req.state = RequestState.CANCELLED
        req.finish_reason = reason
        req.t_done = time.perf_counter()
        self.metrics.cancelled += 1
        self._event(req)
        return True

    def _time_out(self, req: Request) -> None:
        if req.state is RequestState.QUEUED:
            self.scheduler.remove(req)
        else:
            slot = self._slot_of(req)
            if slot is not None:
                self._evict_slot(slot, req)
        req.state = RequestState.TIMED_OUT
        req.finish_reason = f"deadline {req.deadline_s:.3f}s exceeded"
        req.t_done = time.perf_counter()
        self.metrics.timed_out += 1
        self._event(req)

    def _expire_deadlines(self) -> None:
        now = time.perf_counter()
        late = [r for r in self.scheduler.queue
                if r.deadline_s is not None
                and now - r.t_submit > r.deadline_s]
        late += [r for _, r in self.scheduler.active_slots()
                 if r.deadline_s is not None
                 and now - r.t_submit > r.deadline_s]
        for req in late:
            self._time_out(req)

    # -- preemption -----------------------------------------------------
    def _pick_victim(self, max_priority: int, exclude: Sequence[int] = ()
                     ) -> Optional[Tuple[int, Request]]:
        """Eviction victim: the lowest-priority, most-recently-submitted
        decoding slot with priority <= max_priority.  The strict ordering
        keeps preemption from ping-ponging — the newest cheapest request
        always loses first."""
        cands = [(s, r) for s, r in self.scheduler.active_slots()
                 if r.state is RequestState.DECODE and s not in exclude
                 and r.priority <= max_priority]
        if not cands:
            return None
        return min(cands, key=lambda sr: (sr[1].priority, -sr[1].t_submit))

    def _preempt(self, slot: int, req: Request, why: str) -> None:
        """Evict a decoding request and recycle it through the queue.
        Its committed blocks park in the refcount-zero LRU, so the
        recompute on re-admission is mostly a prefix-cache hit, and the
        resume samples at the same per-request key counters — the
        finished output is bitwise-identical to an uncontended run.  A
        request evicted more than ``max_preemptions`` times is REJECTED
        instead: pressure that persistent means it would never finish,
        and the cap guarantees the engine terminates."""
        self._evict_slot(slot, req)
        if req.preemptions >= self.max_preemptions:
            req.state = RequestState.REJECTED
            req.finish_reason = (f"gave up after {req.preemptions} "
                                 f"preemptions under memory pressure "
                                 f"({why})")
            req.t_done = time.perf_counter()
            self.metrics.rejected += 1
            self._event(req)
            return
        req.preemptions += 1
        req.state = RequestState.QUEUED
        req.prefilled = 0
        req.cached_prefix = 0
        req.draft_filled = 0
        req.pending_first = None
        self.scheduler.queue.append(req)   # back of the line: the victim
                                           # must never re-block the head
        self.metrics.preemptions += 1
        if req.on_event is not None:
            req.on_event(req, f"preempted ({why})")

    def _preempt_for_admission(self) -> None:
        """Head-of-line blocked on a slot or on KV blocks: evict
        strictly-lower-priority decoders until it fits.  Equal priority
        never preempts — FCFS among peers, so default-priority workloads
        behave exactly as before this layer existed (the head waits for
        blocks to free)."""
        if not self.scheduler.queue:
            return
        head = self.scheduler.queue[0]
        for _ in range(self.max_slots + 1):
            blocked_slot = not self.scheduler.free_slots()
            blocked_blocks = (not blocked_slot and self.runner.paged
                              and not self._make_can_fit()(head))
            if not (blocked_slot or blocked_blocks):
                return
            victim = self._pick_victim(head.priority - 1)
            if victim is None:
                return
            self._preempt(victim[0], victim[1],
                          "admission of higher-priority request "
                          f"{head.rid}")

    # ------------------------------------------------------------------
    def _emit(self, slot: int, req: Request, tok: int) -> None:
        # a chunk or prefill program that ran since this slot's previous
        # token delayed this one: count it (the fetch of a chunk blocks
        # the host, so in the pipelined loop this is the token applied
        # after the chunk, not the one dispatched behind it)
        work = self.runner.chunk_calls + self.runner.prefill_calls
        if req.output and work != self._emit_work[slot]:
            req.chunk_step_tokens += 1
        self._emit_work[slot] = work
        req.output.append(tok)
        if req.on_token is not None:
            req.on_token(req, tok)

    def _finish(self, slot: int, req: Request) -> None:
        req.state = RequestState.DONE
        req.t_done = time.perf_counter()
        self._active[slot] = False
        if self.runner.paged:
            kv = self.runner.kv
            # register the request's full blocks (prompt + every output
            # token whose K/V was written — all but the last) before the
            # refcount drop parks them in the cached-free LRU: a
            # multi-turn follow-up or duplicate prompt reuses them
            kv.commit_tokens(slot, req.prompt + req.output[:-1])
            kv.free_slot(slot)                 # refcount drop -> pool
        self._slot_gen[slot] += 1      # in-flight emissions: discard
        self.scheduler.release(slot)
        self.metrics.observe(req)
        self._event(req)

    def _make_can_fit(self) -> Callable[[Request], bool]:
        """Block-availability gate for one admission round.  Each True
        answer is immediately followed by an admission, so the closure
        accumulates the blocks already promised this round — otherwise
        two requests could both be judged against the same free pool."""
        if not self.runner.paged:
            return lambda req: True
        kv = self.runner.kv
        planned = 0

        def can_fit(req: Request) -> bool:
            nonlocal planned
            need = kv.blocks_for(self._reserve_tokens(req))
            if self.runner.prefix_cache:
                # blocks covered by a still-live cached prefix are
                # shared, not allocated (cached-free matches still cost
                # a slot of the free pool, so only live ones discount)
                _, blocks = kv.match_prefix(req.seq_tokens)
                need -= sum(1 for b in blocks if kv.refcount(b) > 0)
            if planned + need > kv.free_blocks:
                return False
            planned += need
            return True

        return can_fit

    def _start_decode(self, slot: int, req: Request, tok: int,
                      batch_draft: bool = False) -> None:
        """A (re)prefill sampled its token: move the request into the
        decode batch.  Handles both a fresh prompt (no output yet) and a
        preempted request resuming with m tokens already emitted — the
        decode lane continues at position L+m with key counter m+1, so
        the remainder of the stream is bitwise what the uncontended run
        would have produced.  ``batch_draft``: the caller (bucketed
        admission) runs one batched draft prefill for the whole group
        afterwards."""
        if req.t_first == 0.0:
            req.t_first = time.perf_counter()
        req.state = RequestState.DECODE
        L = len(req.prompt)
        m = len(req.output)            # tokens emitted before preemption
        # positions L .. L+new-1 must stay inside the cache
        cap = self.max_seq_len - L + 1
        req.truncated = req.max_new_tokens > cap
        self._tok[slot] = tok
        self._pos[slot] = L + m
        self._active[slot] = True
        self._remaining[slot] = min(req.max_new_tokens, cap) - 1 - m
        self._counts[slot] = m + 1
        self._host_fresh[slot] = True  # host lanes authoritative again
        self._emit(slot, req, int(tok))
        if (self._remaining[slot] <= 0
                or (req.eos_id is not None and tok == req.eos_id)):
            self._finish(slot, req)
        elif self.runner.speculate_k and not batch_draft:
            # the drafter joins here: one narrow forward fills its dense
            # per-slot cache with every written position [0, L+m) —
            # ``seq_tokens[:-1]`` (= the prompt when fresh).  A preempted
            # drafting slot is thereby rebuilt from scratch: its stale
            # dense rows are overwritten wholesale
            seq = req.seq_tokens[:-1]
            self.runner.draft_prefill([seq],
                                      self.runner.bucket_for(len(seq)),
                                      [slot])

    def _unadmit(self, rows: List[Tuple[int, Request]]) -> None:
        """Roll an admission back (allocation fault mid-round, or a
        transfer fault on the prefill that would have produced the first
        tokens): blocks freed — nothing was committed, so no later match
        can see the half-written bytes — and the requests requeued at
        the FRONT, keeping (rid-ordered) their FCFS turn for the retry."""
        for slot, req in rows:
            if self.runner.paged:
                self.runner.kv.free_slot(slot)     # idempotent rollback
            self._active[slot] = False
            self._slot_gen[slot] += 1
            self.scheduler.release(slot)
            req.state = RequestState.QUEUED
            req.cached_prefix = 0
            req.prefilled = 0
            req.draft_filled = 0
            req.pending_first = None
            if not req.preemptions:
                req.t_admitted = 0.0       # its first admission undone
        self.scheduler.queue.extendleft(
            [r for _, r in sorted(rows, key=lambda sr: sr[1].rid,
                                  reverse=True)])

    def _admit(self) -> List[Tuple[int, Request]]:
        """Admit queued requests into slots.  Returns the (slot, request)
        rows that made prefill progress this round (admission progress,
        for the stall watchdog)."""
        self._preempt_for_admission()
        chunked = self.runner.prefill_chunk > 0
        warm_rows: List[Tuple[int, Request]] = []
        admitted: List[Tuple[int, Request]] = []
        for bucket, group in self.scheduler.plan_admission(
                self._make_can_fit()):
            if self.runner.paged:
                kept: List[Tuple[int, Request]] = []
                bounced: List[Tuple[int, Request]] = []
                for slot, req in group:
                    # share the longest cached block-aligned prefix; the
                    # matched span's K/V is already in the pool, so only
                    # the tail needs prefill.  A block is only matchable
                    # after commit_tokens, which runs AFTER the prefill
                    # writing it was issued — a same-round match can
                    # only hit blocks whose writes are already in the
                    # device stream.  For a preempted request the match
                    # runs over prompt+output, making its recompute
                    # mostly a cache hit.  An (injected or real)
                    # allocation failure un-admits just that request —
                    # ``allocate`` may have shared prefix blocks before
                    # faulting, so the rollback frees the slot.
                    try:
                        req.cached_prefix = self.runner.kv.allocate(
                            slot, self._reserve_tokens(req),
                            tokens=req.seq_tokens)
                        kept.append((slot, req))
                    except MemoryError:
                        bounced.append((slot, req))
                if bounced:
                    self._unadmit(bounced)
                group = kept
            admitted += group
            now = time.perf_counter()
            for slot, req in group:
                if req.output:
                    self.metrics.resumes += 1
                if not req.t_admitted:
                    req.t_admitted = now
                self._temps[slot] = req.params.temperature
                self._topks[slot] = req.params.top_k
                self._topps[slot] = req.params.top_p
                self._eos[slot] = -1 if req.eos_id is None else req.eos_id
                self._seeds[slot] = req.seed
                self._counts[slot] = len(req.output)   # resume counter
            if chunked:
                # chunks run in _prefill_chunks; a cached prefix just
                # advances the chunk cursor past the matched span.  The
                # drafter (when speculating) has no prefix cache, so its
                # chunk cursor always starts at zero.  Dense ring/state
                # rows are per-slot tenants: zero the incoming slots so a
                # previous occupant's state can't leak into the chunked
                # recurrence (paged leaves need no reset — the block
                # table already isolates them)
                for slot, req in group:
                    req.prefilled = req.cached_prefix
                    req.draft_filled = 0
                    req.pending_first = None
                self.runner.reset_slots([s for s, _ in group])
                continue
            if self.runner.kv_dtype == "int8":
                # int8 KV: cold prompts run through the chunk program too
                # (matched = 0), so cold and warm first tokens both come
                # from attention over the quantized pool bytes — a prefix
                # hit is bitwise-identical to a cold miss
                warm_rows += group
                continue
            cold = [(s, r) for s, r in group if not r.cached_prefix]
            warm_rows += [(s, r) for s, r in group if r.cached_prefix]
            if not cold:
                continue
            slots = [s for s, _ in cold]
            reqs = [r for _, r in cold]
            try:
                toks = self.runner.prefill(
                    [r.seq_tokens for r in reqs], bucket, slots,
                    [r.seed for r in reqs],
                    [len(r.output) for r in reqs],
                    [r.params for r in reqs])
            except TransferFault:
                self.metrics.transfer_faults += 1
                self._unadmit(cold)
                continue
            if self.runner.paged:
                for slot, req in cold:
                    self.runner.kv.commit_tokens(slot, req.seq_tokens)
            for slot, req, tok in zip(slots, reqs, toks):
                req.prefilled = len(req.seq_tokens)
                self._start_decode(slot, req, tok, batch_draft=True)
            if self.runner.speculate_k:
                # one batched narrow forward fills the drafter's cache
                # for every request of the group still decoding
                started = [(s, r) for s, r in zip(slots, reqs)
                           if r.state is RequestState.DECODE]
                if started:
                    self.runner.draft_prefill(
                        [r.seq_tokens[:-1] for _, r in started], bucket,
                        [s for s, _ in started])
        if warm_rows:
            # warm tails run after every cold prefill of the round, one
            # batched chunk-program call for the whole set
            try:
                toks = self.runner.warm_prefill(
                    [r.seq_tokens for _, r in warm_rows],
                    [r.cached_prefix for _, r in warm_rows],
                    [s for s, _ in warm_rows],
                    [r.seed for _, r in warm_rows],
                    [len(r.output) for _, r in warm_rows],
                    [r.params for _, r in warm_rows])
            except TransferFault:
                self.metrics.transfer_faults += 1
                self._unadmit(warm_rows)
            else:
                for slot, req in warm_rows:
                    self.runner.kv.commit_tokens(slot, req.seq_tokens)
                for (slot, req), tok in zip(warm_rows, toks):
                    req.prefilled = len(req.seq_tokens)
                    self._start_decode(slot, req, tok)   # per-slot draft
        # rows a transfer fault rolled back are queued again
        return [(s, r) for s, r in admitted
                if r.state is not RequestState.QUEUED]

    def _prefill_chunks(self) -> int:
        """Advance every prefilling request by one chunk (one batched
        call), finishing rows whose (effective) prompt is now fully
        consumed.  A preempted request's chunks run over prompt+output —
        the recompute stream.  When speculating, the drafter's dense
        cache fills chunk-by-chunk in lockstep (its own batched call):
        a target row that finishes first parks its sampled token in
        ``pending_first`` until the drafter catches up, so decode never
        pays a whole-prompt draft forward.  Returns rows advanced (0 on
        an injected transfer fault: nothing host-side moves, and the
        retry next step rewrites the identical chunk bytes)."""
        C = self.runner.prefill_chunk
        rows = [(s, r) for s, r in self.scheduler.active_slots()
                if r.state is RequestState.PREFILL]
        if not rows:
            return 0
        with _span("serve.prefill") as sp:
            spec = self.runner.speculate_k > 0
            tgt = [(s, r) for s, r in rows
                   if r.prefilled < len(r.seq_tokens)]
            if tgt:
                n = len(tgt)
                toks = np.zeros((n, C), np.int32)
                pos = np.empty((n,), np.int32)
                last_idx = np.zeros((n,), np.int32)
                for i, (slot, req) in enumerate(tgt):
                    seq = req.seq_tokens
                    chunk = seq[req.prefilled:req.prefilled + C]
                    toks[i, :len(chunk)] = chunk
                    pos[i] = req.prefilled
                    last_idx[i] = min(C - 1, len(seq) - 1 - req.prefilled)
                sp.set_metadata(rows=n, tokens=int(last_idx.sum()) + n,
                                padded=n * C, rids=_rids(tgt))
                try:
                    cand = self.runner.chunk(toks, pos, [s for s, _ in tgt],
                                             last_idx,
                                             [r.seed for _, r in tgt],
                                             [len(r.output) for _, r in tgt],
                                             [r.params for _, r in tgt])
                except TransferFault:
                    self.metrics.transfer_faults += 1
                    return 0
                for i, (slot, req) in enumerate(tgt):
                    seq = req.seq_tokens
                    req.prefilled += C
                    if req.prefilled >= len(seq):
                        req.prefilled = len(seq)
                        self.runner.kv.commit_tokens(slot, seq)
                        if spec:
                            req.pending_first = int(cand[i])
                        else:
                            self._start_decode(slot, req, cand[i])
                    else:
                        # the chunk's writes are in the device stream: its
                        # full blocks are now matchable by later admissions
                        self.runner.kv.commit_tokens(slot, seq[:req.prefilled])
            advanced = len(tgt)
            if spec:
                # the drafter fills [0, N) — it has no prefix cache, so its
                # cursor can trail a prefix-hit target row; pad positions
                # past the end are causally masked and later overwritten
                drows = [(s, r) for s, r in rows
                         if r.draft_filled < len(r.seq_tokens)]
                if drows:
                    m = len(drows)
                    dtoks = np.zeros((m, C), np.int32)
                    dpos = np.empty((m,), np.int32)
                    for i, (slot, req) in enumerate(drows):
                        seq = req.seq_tokens
                        chunk = seq[req.draft_filled:req.draft_filled + C]
                        dtoks[i, :len(chunk)] = chunk
                        dpos[i] = req.draft_filled
                    self.runner.draft_chunk(dtoks, dpos,
                                            [s for s, _ in drows])
                    for slot, req in drows:
                        req.draft_filled = min(req.draft_filled + C,
                                               len(req.seq_tokens))
                    advanced = len({s for s, _ in tgt}
                                   | {s for s, _ in drows})
                # both cursors caught up: release the parked first token
                # into the decode batch (batch_draft=True — the drafter is
                # already warm, skip the whole-prompt fill)
                for slot, req in rows:
                    if (self.scheduler.slots[slot] is req
                            and req.pending_first is not None
                            and req.prefilled >= len(req.seq_tokens)
                            and req.draft_filled >= len(req.seq_tokens)):
                        tok = req.pending_first
                        req.pending_first = None
                        self._start_decode(slot, req, tok, batch_draft=True)
            return advanced

    # ------------------------------------------------------------------
    def fork(self, parent: Request, n: int, *,
             seeds: Optional[Sequence[int]] = None,
             params: Optional[SampleParams] = None,
             on_token: Optional[Callable[[Request, int], None]] = None
             ) -> List[Request]:
        """Clone a decoding request into ``n`` children that share every
        KV block of its committed tokens (best-of-n / parallel sampling
        from one prompt's cache, zero recompute).  Children occupy free
        decode slots immediately and diverge through their own sampling
        seeds (``seeds`` or derived via ``fork_seeds``); a shared block
        is physically copied only on the first divergent write.

        Raises ValueError when the parent is not actively decoding or
        ``n`` free slots are unavailable, MemoryError when the pool
        cannot cover the children's uncommitted tails."""
        if not self.runner.paged:
            raise ValueError("fork requires the paged KV cache")
        # fork reads exact host state (parent tokens, positions, block
        # refcounts): apply every in-flight step first.  k pipelined
        # steps + drain leave the same host state as k sync steps, so
        # forked children diverge bitwise-identically in both modes.
        self._drain_inflight()
        if parent.state is not RequestState.DECODE:
            raise ValueError("fork parent must be actively decoding")
        pslot = next(s for s, r in self.scheduler.active_slots()
                     if r is parent)
        free = self.scheduler.free_slots()
        if len(free) < n:
            raise ValueError(f"fork needs {n} free slots, "
                             f"have {len(free)}")
        kv = self.runner.kv
        # sync the parent's committed watermark to everything actually
        # written ([0, pos): the prompt plus every emitted token but the
        # last) before computing what to share.  Without this, forking
        # right after a block-aligned commit point would hand children
        # zeroed fresh blocks for the decode positions written since —
        # they must share the partial block holding that K/V instead.
        kv.commit_tokens(pslot, parent.prompt + parent.output[:-1])
        while n * kv.fork_cost(pslot) > kv.free_blocks:
            # under pressure a fork storm preempts strictly-lower-
            # priority decoders instead of failing; among equals it
            # raises — forks never evict peers of their parent
            victim = self._pick_victim(parent.priority - 1,
                                       exclude=(pslot,))
            if victim is None:
                raise MemoryError(
                    f"fork needs {n * kv.fork_cost(pslot)} blocks, "
                    f"free {kv.free_blocks}")
            self._preempt(victim[0], victim[1],
                          f"fork of request {parent.rid}")
        child_seeds = (list(seeds) if seeds is not None
                       else fork_seeds(parent.seed, n))
        if len(child_seeds) != n:
            raise ValueError(f"fork needs {n} seeds, got {len(child_seeds)}")
        children: List[Request] = []
        for i in range(n):
            slot = free[i]
            try:
                kv.fork(pslot, slot)
            except MemoryError:
                # injected fault mid-fork: children already created stay
                # consistent but the caller sees an exception, so roll
                # them back before re-raising
                for c in children:
                    self.cancel(c, "fork aborted: allocation failure "
                                   "mid-fork")
                raise
            child = Request(self._next_rid, list(parent.prompt),
                            parent.max_new_tokens, parent.eos_id,
                            params if params is not None else parent.params,
                            on_token, seed=child_seeds[i])
            self._next_rid += 1
            child.state = RequestState.DECODE
            child.output = list(parent.output)
            child.prefilled = len(parent.prompt)
            child.cached_prefix = kv.committed(slot)
            child.truncated = parent.truncated
            child.t_submit = child.t_admitted = child.t_first = \
                time.perf_counter()
            child.t_submit_wall = time.time()
            self.scheduler.slots[slot] = child
            self._tok[slot] = self._tok[pslot]
            self._pos[slot] = self._pos[pslot]
            self._active[slot] = True
            self._temps[slot] = child.params.temperature
            self._topks[slot] = child.params.top_k
            self._topps[slot] = child.params.top_p
            self._eos[slot] = -1 if child.eos_id is None else child.eos_id
            self._remaining[slot] = self._remaining[pslot]
            self._seeds[slot] = child_seeds[i]
            self._counts[slot] = self._counts[pslot]
            self._host_fresh[slot] = True  # host lanes authoritative
            children.append(child)
        # paged leaves are shared through the block table; dense ring/
        # state leaves of the main cache are per-slot rows and need a
        # physical copy (no-op for all-paged architectures)
        self.runner.dense_fork(pslot, [free[i] for i in range(n)])
        if self.runner.speculate_k:
            # the drafter's cache is dense per-slot: children need a
            # physical copy of the parent's row (the paged target cache
            # is shared through the block table instead)
            self.runner.draft_fork(pslot, [free[i] for i in range(n)])
        self.metrics.max_active = max(
            self.metrics.max_active, len(self.scheduler.active_slots()))
        return children

    def _cow(self, active: List[Tuple[int, Request]], span: int) -> None:
        """Copy-on-write gate before a decode/verify step: any block a
        slot is about to write while sharing it (fork siblings, live
        prefix-cache readers) is duplicated first, so the other readers
        keep the original bytes.  ``span`` is the per-slot write window
        past the host position mirror: K+1 for this step's decode or
        verify, times the steps in flight (pipelined loop) plus one.

        Under block exhaustion (a fork storm about to diverge
        everywhere) the writer preempts equal-or-lower-priority decoders
        to free copy targets and retries; with nobody left to evict it
        preempts ITSELF — its committed prefix parks in the LRU, so the
        recompute after re-admission is cheap.  ``ensure_writable`` is
        all-or-nothing, so a failed attempt leaves nothing to unwind.
        Pairs of a writer that got preempted mid-pass are dropped before
        the device copy: its swapped-in blocks returned to the pool, and
        copying into them could race a later writer's reuse."""
        slot_pairs: List[Tuple[int, Request,
                               List[Tuple[int, int]]]] = []
        kv = self.runner.kv
        for slot, req in active:
            if self.scheduler.slots[slot] is not req:
                continue                 # preempted by an earlier writer
            lo = int(self._pos[slot])
            while True:
                try:
                    slot_pairs.append(
                        (slot, req,
                         kv.ensure_writable(slot, lo, lo + span)))
                    break
                except MemoryError as e:
                    victim = self._pick_victim(req.priority,
                                               exclude=(slot,))
                    if victim is None:
                        self._preempt(slot, req, f"copy-on-write: {e}")
                        break
                    self._preempt(victim[0], victim[1],
                                  f"copy-on-write by request {req.rid}")
        pairs = [p for slot, req, ps in slot_pairs
                 if self.scheduler.slots[slot] is req for p in ps]
        self.runner.copy_blocks(pairs)

    # -- applying step results -----------------------------------------
    #
    # The device result of a decode / speculative step is applied to
    # host state through exactly one routine per kind, shared by the
    # synchronous and the pipelined loop — parity between the two modes
    # is by construction, not by keeping two emission loops in sync.
    # ``rows`` is the (slot, request, slot-generation) snapshot taken at
    # dispatch: a row whose slot was released since (finish / cancel /
    # preempt bumps the generation) is discarded, even if the same
    # request was re-admitted into the same slot in between.

    def _snap_rows(self, active: List[Tuple[int, Request]]
                   ) -> List[Tuple[int, Request, int]]:
        return [(s, r, int(self._slot_gen[s])) for s, r in active]

    def _apply(self, rows: List[Tuple[int, Request, int]], out,
               spec: bool) -> int:
        """Apply one decode (``out`` = tokens, done) or speculative
        (tokens [slots, K+1], counts) step."""
        with _span("serve.emit"):
            if spec:
                return self._apply_spec(rows, *out)
            return self._apply_decode(rows, *out)

    def _apply_decode(self, rows: List[Tuple[int, Request, int]],
                      toks, done) -> int:
        n = 0
        for slot, req, gen in rows:
            if self.scheduler.slots[slot] is not req \
                    or gen != self._slot_gen[slot] \
                    or req.state is not RequestState.DECODE:
                continue   # cancelled/finished/preempted since dispatch
            tok = int(toks[slot])
            self._emit(slot, req, tok)
            self._tok[slot] = tok
            self._pos[slot] += 1
            self._counts[slot] += 1
            self._remaining[slot] -= 1
            if done[slot]:
                self._finish(slot, req)
            n += 1
        return n

    def _apply_spec(self, rows: List[Tuple[int, Request, int]],
                    toks_mat, counts) -> int:
        """Every decoding slot advances by 1..K+1 tokens (per-slot
        variable acceptance).  EOS and the remaining-budget cap are
        applied here, so a slot never advances past its reservation."""
        acc = prop = n = 0
        K = self.runner.speculate_k
        for slot, req, gen in rows:
            if self.scheduler.slots[slot] is not req \
                    or gen != self._slot_gen[slot] \
                    or req.state is not RequestState.DECODE:
                continue       # cancelled/timed out from a callback
            m = int(counts[slot])
            # acceptance accounting charges only proposals the slot
            # could actually use: the remaining-budget cap truncates the
            # adjudicated window up front, and an EOS stop discards the
            # proposals after it — otherwise every slot finishing early
            # drags acceptance_rate (and the EMA) below its true value
            usable = min(K, int(self._remaining[slot]))
            emitted = 0
            eos_stop = False
            for j in range(m):
                tok = int(toks_mat[slot, j])
                self._emit(slot, req, tok)
                self._tok[slot] = tok
                self._pos[slot] += 1
                self._counts[slot] += 1
                self._remaining[slot] -= 1
                emitted += 1
                if req.eos_id is not None and tok == req.eos_id:
                    eos_stop = True
                if self._remaining[slot] <= 0 or eos_stop:
                    self._finish(slot, req)
                    break
            prop_eff = min(usable, emitted) if eos_stop else usable
            acc += min(emitted, m - 1, prop_eff)
            prop += prop_eff
            n += 1
        self.metrics.observe_spec(acc, prop)
        return n

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Expire deadlines, admit queued requests (preempting if a
        higher-priority head is starved), advance prefill chunks, and
        run one decode (or speculative draft+verify) step for all
        decoding slots.  Returns requests that made forward progress; a
        zero-progress step with work pending arms the stall watchdog.
        TransferFaults are absorbed here: the step simply retries next
        tick (recomputing bitwise-identical bytes), it never corrupts
        host state or escapes to the caller.

        With ``pipeline_depth > 0`` the loop runs asynchronously: step
        N+1 is dispatched before step N's host transfer is waited on,
        and every scheduler decision overlaps device execution."""
        states = [r.state for _, r in self.scheduler.active_slots()]
        with _span("serve.step", step=self.steps_run,
                   queued=len(self.scheduler.queue),
                   prefilling=states.count(RequestState.PREFILL),
                   decoding=states.count(RequestState.DECODE),
                   t=time.perf_counter_ns()):
            if self.pipeline_depth > 0:
                return self._step_pipelined()
            return self._step_sync()

    def _plan_step(self) -> Tuple[int, List[Tuple[int, Request]]]:
        """The scheduler half of a step, shared by both loops: deadlines,
        admission, one chunk of every prefilling row, and copy-on-write
        before the decode (which may preempt).  Returns the progress so
        far and the decoding rows."""
        if self.faults is not None:
            dt = self.faults.take_slow()
            if dt > 0:
                time.sleep(dt)         # injected slow step (chaos tests)
        self._expire_deadlines()
        with _span("serve.admit") as sp:
            admitted = self._admit()
            sp.set_metadata(admitted=len(admitted),
                            hit_tokens=sum(r.cached_prefix
                                           for _, r in admitted),
                            rids=_rids(admitted))
        progress = len(admitted)
        if self.runner.prefill_chunk:
            progress += self._prefill_chunks()
        self.metrics.max_active = max(
            self.metrics.max_active, len(self.scheduler.active_slots()))
        active = [(s, r) for s, r in self.scheduler.active_slots()
                  if r.state is RequestState.DECODE]
        if self.runner.paged and active:
            # the CoW window covers every position the in-flight steps
            # plus this one may write past the host mirror
            with _span("serve.cow"):
                self._cow(active, (self.runner.speculate_k + 1)
                          * (len(self._inflight) + 1))
            active = [(s, r) for s, r in active
                      if self.scheduler.slots[s] is r]
        return progress, active

    def _step_sync(self) -> int:
        t0 = time.perf_counter()
        progress, active = self._plan_step()
        if active:
            rows = self._snap_rows(active)
            spec = bool(self.runner.speculate_k)
            try:
                with _span("serve.decode", rids=_rids(active)):
                    if spec:
                        out = self.runner.draft_verify(
                            self._tok, self._pos, self._active, self._seeds,
                            self._counts, self._temps, self._topks,
                            self._topps)
                    else:
                        out = self.runner.decode(
                            self._tok, self._pos, self._active, self._seeds,
                            self._counts, self._temps, self._topks,
                            self._topps, self._eos, self._remaining)
                self._apply(rows, out, spec)
                progress += len(active)
            except TransferFault:
                self.metrics.transfer_faults += 1
        return self._finish_step(t0, progress)

    def _finish_step(self, t0: float, progress: int) -> int:
        self.steps_run += 1
        # step-time EMA for SLO admission estimates; alpha 0.2 forgets a
        # one-off compile spike within a few steps while tracking load
        dt = time.perf_counter() - t0
        self._step_ema = (dt if self._step_ema is None
                          else 0.8 * self._step_ema + 0.2 * dt)
        if progress > 0 or not self.scheduler.has_work():
            self._stalled_steps = 0
        else:
            self._stalled_steps += 1
            if self._stalled_steps >= self.watchdog_patience:
                self._watchdog_fire()
        return progress

    # -- pipelined stepping --------------------------------------------

    def _dispatch(self, active: List[Tuple[int, Request]]) -> None:
        """Enqueue the next decode/spec program without any host
        transfer.  When a step is already in flight, this step's inputs
        are composed ON DEVICE from its still-unfetched packed result
        (``carry``); lanes the host rewrote out-of-band since the last
        dispatch (fresh admissions, fork children, preempt-resumes) or
        that were inactive in the carried step take the host values
        instead (``override``)."""
        carry = self._inflight[-1]["handle"] if self._inflight else None
        override = (None if carry is None
                    else self._host_fresh | ~carry["active"])
        rows = self._snap_rows(active)
        spec = bool(self.runner.speculate_k)
        if spec:
            # the host position mirror lags the device by the in-flight
            # depth: widen the verify gather's live-length bound to it
            handle = self.runner.dispatch_spec(
                self._tok, self._pos, self._active, self._seeds,
                self._counts, self._temps, self._topks, self._topps,
                carry=carry, override=override,
                extra_len=(self.runner.speculate_k + 1)
                * len(self._inflight))
        else:
            handle = self.runner.dispatch_decode(
                self._tok, self._pos, self._active, self._seeds,
                self._counts, self._temps, self._topks, self._topps,
                self._eos, self._remaining, carry=carry,
                override=override)
        self._inflight.append({"handle": handle, "rows": rows,
                               "spec": spec})
        self.metrics.steps_in_flight = max(self.metrics.steps_in_flight,
                                           len(self._inflight))
        for s, _, _ in rows:
            # from here the device carry chain is the truth for these
            # lanes; host mirrors catch up when the result is applied
            self._host_fresh[s] = False

    def _process_oldest(self) -> int:
        """Wait on the oldest in-flight step's packed transfer and apply
        it.  A TransferFault leaves the entry at the queue head — the
        retry next tick re-fetches the SAME device buffers, so the
        stream stays bitwise-identical, just one step late — and
        returns -1.  Otherwise returns the number of rows applied."""
        entry = self._inflight[0]
        try:
            wait = (self.runner.wait_spec if entry["spec"]
                    else self.runner.wait_decode)
            out = wait(entry["handle"])
        except TransferFault:
            self.metrics.transfer_faults += 1
            return -1
        self._inflight.popleft()
        return self._apply(entry["rows"], out, entry["spec"])

    def _drain_inflight(self) -> None:
        """Apply every in-flight step (fork and shutdown paths need
        exact host state).  Bounded retries keep an injected transfer-
        fault storm from hanging the drain forever."""
        for _ in range(1000):
            if not self._inflight:
                return
            self._process_oldest()
        raise EngineStallError(
            "pipeline drain: transfer-fault storm outlived its retry "
            "budget", self.stall_diagnostic())

    def _step_pipelined(self) -> int:
        """One asynchronous engine step: all scheduler decisions
        (deadlines, admission, chunked prefill, CoW gating, preemption)
        run first — overlapping whatever step is still executing on the
        device — then the next step is DISPATCHED, and only then is the
        oldest in-flight transfer waited on.  Nothing happens between
        dispatch and wait, so the device never idles on host work."""
        t0 = time.perf_counter()
        progress, active = self._plan_step()
        dispatched = False
        # backpressure: a transfer-fault retry keeps the queue over
        # depth — don't dispatch on top of it, drain first
        if active and len(self._inflight) <= self.pipeline_depth:
            with _span("serve.decode", rids=_rids(active)):
                self._dispatch(active)
            dispatched = True
            progress += len(active)    # a dispatched step IS forward
                                       # progress: the watchdog must not
                                       # fire on work already running
        processed_any = False
        while len(self._inflight) > self.pipeline_depth:
            r = self._process_oldest()
            if r < 0:
                break                  # fault: retry next tick
            processed_any = True
            if not dispatched:
                progress += r
        if not dispatched and not processed_any and self._inflight:
            # tail drain: no new work to dispatch, finish what's there
            r = self._process_oldest()
            if r > 0:
                progress += r
        return self._finish_step(t0, progress)

    def _watchdog_fire(self) -> None:
        """No forward progress for ``watchdog_patience`` consecutive
        steps with work pending: break the stall instead of spinning.
        If the head of the queue is starved of a slot or of KV blocks,
        preempt a decoder (equal priority allowed — anything beats
        livelock); with nobody to evict, shed the head with a full
        diagnostic as the reason.  Every fire either frees resources or
        permanently removes a request, so repeated fires drain the queue
        rather than spin."""
        self.metrics.watchdog_fires += 1
        self._stalled_steps = 0
        if not self.scheduler.queue:
            return      # stall is device-side (e.g. a transfer-fault
                        # storm): scheduling can free nothing, and run()
                        # reports the diagnostic when its budget ends
        head = self.scheduler.queue[0]
        blocked_slot = not self.scheduler.free_slots()
        blocked_blocks = (not blocked_slot and self.runner.paged
                          and not self._make_can_fit()(head))
        if blocked_slot or blocked_blocks:
            victim = self._pick_victim(head.priority)
            if victim is not None:
                self._preempt(victim[0], victim[1],
                              "watchdog: head-of-line starved")
                return
        self.scheduler.remove(head)
        head.state = RequestState.REJECTED
        head.finish_reason = ("watchdog: no forward progress for "
                              f"{self.watchdog_patience} steps; "
                              f"{self._stall_summary()}")
        head.t_done = time.perf_counter()
        self.metrics.rejected += 1
        self._event(head)

    def stall_diagnostic(self) -> Dict[str, Any]:
        """Queued/active/pool snapshot for stall reports."""
        sched = self.scheduler
        active = sched.active_slots()
        diag: Dict[str, Any] = {
            "steps_run": self.steps_run,
            "queued": len(sched.queue),
            "head_rid": sched.queue[0].rid if sched.queue else None,
            "active_prefill": sum(1 for _, r in active
                                  if r.state is RequestState.PREFILL),
            "active_decode": sum(1 for _, r in active
                                 if r.state is RequestState.DECODE),
            "preemptions": self.metrics.preemptions,
            "watchdog_fires": self.metrics.watchdog_fires,
            "transfer_faults": self.metrics.transfer_faults,
            "steps_in_flight": len(self._inflight),
        }
        if self.runner.paged:
            kv = self.runner.kv
            util = kv.utilization()
            diag["free_blocks"] = kv.free_blocks
            diag["block_utilization"] = util["block_utilization"]
            if sched.queue:
                diag["head_needs_blocks"] = kv.blocks_for(
                    self._reserve_tokens(sched.queue[0]))
        return diag

    def _stall_summary(self) -> str:
        return ", ".join(f"{k}={v}" for k, v in
                         self.stall_diagnostic().items())

    def run(self, max_steps: int = 10000, *,
            allow_incomplete: bool = False) -> None:
        """Drain queue + slots.  Exhausting ``max_steps`` with work still
        pending raises :class:`EngineStallError` carrying a queued/
        active/pool-utilization diagnostic — pass ``allow_incomplete=
        True`` to return silently instead (engine state stays intact and
        ``run`` can simply be called again)."""
        for _ in range(max_steps):
            if not self.scheduler.has_work() and not self._inflight:
                return
            self.step()
        if ((self.scheduler.has_work() or self._inflight)
                and not allow_incomplete):
            raise EngineStallError(
                f"engine stalled: {max_steps} steps exhausted with "
                f"{len(self.scheduler.queue)} queued and "
                f"{len(self.scheduler.active_slots())} active requests "
                f"({self._stall_summary()})", self.stall_diagnostic())

    # ------------------------------------------------------------------
    def generate(self, prompts: List[List[int]], max_new_tokens: int = 32,
                 params: SampleParams = SampleParams()) -> List[List[int]]:
        reqs = [self.submit(p, max_new_tokens, params=params)
                for p in prompts]
        self.run()
        return [r.output for r in reqs]
