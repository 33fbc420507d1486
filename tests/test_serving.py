"""Serving engine: bucketed-prefill parity with the naive autoregressive
reference (dense, windowed, recurrent and PT configs), paged-vs-dense
cache equivalence, chunked prefill, batched admission, scheduler policy,
per-request sampling isolation, device-side sampling, per-request seeded
reproducibility, track-speculative decoding (greedy bitwise parity +
distribution preservation), streaming callbacks and metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.paged import PagedLeaf, wrap_paged
from repro.common.types import LayerSpec, ModelConfig
from repro.configs import reduced_config
from repro.core.track import pt_ify
from repro.launch import steps as steps_lib
from repro.models.attention import (attention_chunk, attention_decode,
                                    attention_init)
from repro.models.decoder import init_lm, lm_forward
from repro.serving.cache import (PagedKVCache, batch_axes, insert_rows,
                                 paged_insert_rows, seq_axes)
from repro.serving.engine import (Engine, EngineMetrics, Request,
                                  RequestState, Scheduler)
from repro.serving.sampler import (SALT_DRAFT, SampleParams, accept_step,
                                   row_keys, sample, sample_batched,
                                   sample_rows, stack_params)


def _naive_greedy(params, cfg, prompt, n_new):
    fns = steps_lib.model_fns(cfg)
    toks = list(prompt)
    for _ in range(n_new):
        out = fns["forward"](params,
                             {"inputs": jnp.asarray([toks], jnp.int32)},
                             cfg, mode="prefill")
        toks.append(int(jnp.argmax(out[0][0, -1])))
    return toks[len(prompt):]


def _tinyllama():
    cfg = reduced_config("tinyllama-1.1b")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# parity with the naive reference
# ---------------------------------------------------------------------------

def test_engine_matches_naive_greedy():
    cfg, params = _tinyllama()
    prompts = [[5, 9, 2, 7], [11, 3, 1, 8, 4, 2], [17, 23]]
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32)
    outs = eng.generate(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        ref = _naive_greedy(params, cfg, p, 6)
        assert o == ref, (p, o, ref)


def test_bucketed_prefill_parity_across_bucket_boundary():
    """Greedy outputs must be identical whether the prompt lands exactly
    on a bucket (8), one short of it (7 -> padded to 8) or one past it
    (9 -> padded to 16)."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=2, max_seq_len=48, min_bucket=4)
    rng = np.random.default_rng(7)
    for L in (7, 8, 9):
        p = rng.integers(1, cfg.vocab_size, L).tolist()
        out = eng.generate([p], max_new_tokens=6)[0]
        ref = _naive_greedy(params, cfg, p, 6)
        assert out == ref, (L, out, ref)


def test_bucketed_prefill_parity_pt_config():
    """Engine-on-PT: pt_decode_step serving (bucketed prefill + batched
    device-side sampling) matches the naive pt_forward reference across a
    bucket boundary."""
    cfg = reduced_config("pt-30b-d8")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32, min_bucket=4)
    for L in (7, 8, 9):
        p = [(3 * i + 1) % cfg.vocab_size for i in range(L)]
        out = eng.generate([p], max_new_tokens=5)[0]
        ref = _naive_greedy(params, cfg, p, 5)
        assert out == ref, (L, out, ref)


def test_bucketed_prefill_parity_windowed_ring_cache():
    """Sliding-window (ring buffer) caches must be built from the true
    prompt, not the padded tail: a 17-token prompt padded to bucket 32
    would otherwise evict most of the real window."""
    cfg = reduced_config("gemma2-2b")
    windows = [cfg.spec(nm).window for nm in set(cfg.layer_names)
               if cfg.spec(nm).window]
    assert windows, "gemma2 reduced config should have windowed layers"
    params = init_lm(jax.random.PRNGKey(1), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=64, min_bucket=4)
    rng = np.random.default_rng(0)
    for L in (7, 17, 21):
        p = rng.integers(1, cfg.vocab_size, L).tolist()
        out = eng.generate([p], max_new_tokens=6)[0]
        ref = _naive_greedy(params, cfg, p, 6)
        assert out == ref, (L, out, ref)


def test_moe_arch_uses_exact_prefill():
    """Capacity-based MoE routing is length-sensitive: padded bucket
    tokens would steal expert-capacity slots from real tokens, so MoE
    configs prefill at exact length.  (Incremental decode still routes
    each token with per-step capacity, which legitimately differs from
    a full recompute — only the prefill token is bit-compared here.)"""
    cfg = reduced_config("deepseek-v2-236b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_slots=1, max_seq_len=32)
    assert eng.runner.exact_prefill
    assert eng.runner.bucket_for(7) == 7
    p = [(7 * i + 3) % cfg.vocab_size for i in range(7)]
    out = eng.generate([p], max_new_tokens=2)[0]
    assert out[0] == _naive_greedy(params, cfg, p, 1)[0]


def test_truncation_flag_when_capacity_exceeded():
    """A request that cannot fit prompt+max_new in the cache is clamped
    to capacity and flagged, not silently shortened."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=1, max_seq_len=16)
    req = eng.submit([1] * 14, max_new_tokens=50)
    eng.run()
    assert req.truncated
    assert len(req.output) == 16 - 14 + 1    # positions 14, 15 + prefill tok
    assert req.state is RequestState.DONE
    ok = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run()
    assert not ok.truncated and len(ok.output) == 4


def test_recurrent_arch_uses_exact_prefill():
    """Mamba state would be corrupted by padded tokens: the bucket policy
    degrades to exact lengths and outputs still match the reference."""
    cfg = reduced_config("falcon-mamba-7b")
    params = init_lm(jax.random.PRNGKey(2), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32)
    assert eng.runner.exact_prefill
    assert eng.runner.bucket_for(7) == 7
    p = [3, 1, 4, 1, 5, 9, 2]
    out = eng.generate([p], max_new_tokens=5)[0]
    assert out == _naive_greedy(params, cfg, p, 5)


# ---------------------------------------------------------------------------
# compile stability + batched admission
# ---------------------------------------------------------------------------

def test_prefill_compiles_per_bucket_not_per_length():
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=1, max_seq_len=32, min_bucket=8)
    for L in (3, 5, 6, 7, 8):          # five lengths, one bucket
        eng.generate([list(range(1, L + 1))], max_new_tokens=2)
    assert eng.runner.prefill_shapes == {(1, 8)}


def test_batched_admission_single_prefill_call():
    """Same-bucket requests admitted together run as ONE batched prefill
    into several free slots, and each still matches the reference."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=4, max_seq_len=32, min_bucket=8)
    prompts = [[5, 9, 2, 7, 1], [11, 3, 1, 8, 4, 2], [17, 23, 5, 6, 7, 8, 9]]
    outs = eng.generate(prompts, max_new_tokens=5)
    assert eng.runner.prefill_shapes == {(3, 8)}
    for p, o in zip(prompts, outs):
        assert o == _naive_greedy(params, cfg, p, 5), p


def test_engine_continuous_batching_slot_reuse():
    cfg = reduced_config("gemma2-2b")
    params = init_lm(jax.random.PRNGKey(1), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=48)
    reqs = [eng.submit([3, 1, 4, 1, 5], max_new_tokens=4 + i)
            for i in range(5)]
    eng.run()
    assert all(len(r.output) == 4 + i for i, r in enumerate(reqs))
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(r.t_done > r.t_first > r.t_submit > 0 for r in reqs)
    assert all(r.ttft >= 0 and r.tpot >= 0 for r in reqs)
    # 5 requests through 2 slots => more engine steps than the longest req
    assert eng.steps_run >= 8


def test_scheduler_fcfs_budget():
    """Admission is strict FCFS under the padded-token budget; an
    oversized head-of-line request is admitted alone, never skipped."""
    bucket = lambda L: max(8, 1 << (L - 1).bit_length())
    sched = Scheduler(max_slots=4, bucket_fn=bucket,
                      max_waiting_prefill_tokens=16)
    for rid, L in enumerate((8, 8, 8)):      # buckets 8, 8, 8; budget 16
        sched.submit(Request(rid, [1] * L))
    groups = sched.plan_admission()
    admitted = [r.rid for _, g in groups for _, r in g]
    assert admitted == [0, 1]                # third exceeds the budget
    assert all(r.state is RequestState.PREFILL for _, g in groups
               for _, r in g)
    assert [r.rid for r in sched.queue] == [2]
    # oversized head-of-line request: admitted alone once slots free up
    sched2 = Scheduler(max_slots=2, bucket_fn=bucket,
                       max_waiting_prefill_tokens=4)
    sched2.submit(Request(0, [1] * 30))      # bucket 32 >> budget 4
    groups = sched2.plan_admission()
    assert [r.rid for _, g in groups for _, r in g] == [0]


# ---------------------------------------------------------------------------
# device-side sampling
# ---------------------------------------------------------------------------

def test_engine_sampled_tokens_in_vocab():
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=2, max_seq_len=24)
    outs = eng.generate([[1, 2, 3]] * 3, max_new_tokens=5,
                        params=SampleParams(temperature=0.8, top_k=10))
    for o in outs:
        assert len(o) == 5
        assert all(0 <= t < cfg.vocab_size for t in o)


def test_per_request_sampling_params_isolation():
    """A greedy request decoding next to a high-temperature request must
    produce exactly the tokens it produces alone: per-slot sampling params
    are per-row traced arrays, not engine-global state."""
    cfg, params = _tinyllama()
    solo = Engine(cfg, params, max_slots=2, max_seq_len=32, seed=3)
    ref = solo.generate([[1, 2, 3, 4]], max_new_tokens=6)[0]

    mixed = Engine(cfg, params, max_slots=2, max_seq_len=32, seed=11)
    r_greedy = mixed.submit([1, 2, 3, 4], 6)
    r_hot = mixed.submit([9, 8, 7], 6,
                         params=SampleParams(temperature=1.5, top_k=5))
    mixed.run()
    assert r_greedy.output == ref
    assert all(0 <= t < cfg.vocab_size for t in r_hot.output)


def test_decode_single_host_transfer_per_step():
    """The decode loop must not round-trip per-slot tokens through the
    host: exactly one packed transfer per engine step."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=3, max_seq_len=32)
    eng.generate([[1, 2, 3], [4, 5], [6, 7, 8, 9]], max_new_tokens=6)
    assert eng.runner.decode_transfers == eng.steps_run


def test_sample_batched_matches_single_param_sampler():
    """sample_batched with uniform rows == the scalar-params sampler, and
    per-row params are honoured (greedy rows exactly argmax)."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    # all-greedy
    t, k, p = stack_params([SampleParams()] * 4)
    out = sample_batched(logits, key, jnp.asarray(t), jnp.asarray(k),
                         jnp.asarray(p))
    assert (np.asarray(out) == np.asarray(jnp.argmax(logits, -1))).all()
    # mixed: greedy rows stay argmax; top-k rows stay in the top-k support
    mix = [SampleParams(), SampleParams(temperature=1.0, top_k=3),
           SampleParams(), SampleParams(temperature=0.7, top_k=8)]
    t, k, p = stack_params(mix)
    out = np.asarray(sample_batched(logits, key, jnp.asarray(t),
                                    jnp.asarray(k), jnp.asarray(p)))
    am = np.asarray(jnp.argmax(logits, -1))
    assert out[0] == am[0] and out[2] == am[2]
    for row, kk in ((1, 3), (3, 8)):
        top = np.asarray(jax.lax.top_k(logits[row], kk)[1])
        assert out[row] in top.tolist()


def test_sampler_greedy_and_top_p():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    t = sample(logits, jax.random.PRNGKey(0))
    assert int(t[0]) == 1
    t2 = sample(logits, jax.random.PRNGKey(0),
                SampleParams(temperature=1.0, top_p=0.5))
    assert int(t2[0]) == 1     # nucleus of p=.5 is just the argmax here


# ---------------------------------------------------------------------------
# streaming + metrics
# ---------------------------------------------------------------------------

def test_streaming_callback_sees_every_token_in_order():
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32)
    seen = {}

    def on_token(req, tok):
        seen.setdefault(req.rid, []).append(tok)

    r1 = eng.submit([1, 2, 3], 5, on_token=on_token)
    r2 = eng.submit([4, 5, 6, 7], 4, on_token=on_token)
    eng.run()
    assert seen[r1.rid] == r1.output and len(r1.output) == 5
    assert seen[r2.rid] == r2.output and len(r2.output) == 4


def test_engine_metrics_summary():
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32)
    eng.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=4)
    m = eng.metrics.summary()
    assert m["requests"] == 2
    assert m["output_tokens"] == 8
    assert m["throughput_tok_s"] > 0
    for key in ("ttft_ms", "tpot_ms"):
        assert m[key]["p50"] <= m[key]["p90"] <= m[key]["p99"]


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------

def _gqa_cfg(KH=2, G=2, hd=8):
    return ModelConfig(
        name="paged-test", family="dense", n_layers=1, d_model=16,
        n_heads=KH * G, n_kv_heads=KH, d_ff=32, vocab_size=64,
        head_dim=hd, dtype="float32",
        layer_specs={"x": LayerSpec(mixer="gqa", mlp="none")},
        pattern_unit=("x",))


def test_paged_random_alloc_free_decode_bitwise_matches_dense():
    """Property test (seeded): random allocate / append / free sequences
    on the block pool, mirrored into a dense per-slot cache, followed by
    a decode step — the paged path must reproduce the dense logits
    BIT-FOR-BIT at fp32 (same values, same contraction order; the block
    table only changes where the bytes live)."""
    cfg = _gqa_cfg()
    KH, hd = cfg.n_kv_heads, cfg.head_dim
    spec = cfg.spec("x")
    params = attention_init(jax.random.PRNGKey(0), cfg.d_model,
                            cfg.n_heads, KH, hd)
    B, S, bs = 4, 32, 8
    init_kv = lambda c, b, s: (jnp.zeros((b, s, KH, hd), jnp.float32),
                               jnp.zeros((b, s, KH, hd), jnp.float32))
    rng = np.random.default_rng(42)
    for trial in range(3):
        kv = PagedKVCache(init_kv, cfg, max_slots=B, max_seq_len=S,
                          block_size=bs)
        dense = init_kv(cfg, B, S)
        lengths = np.zeros((B,), np.int64)
        # random interleaving of allocate / append / free with real writes
        for op in range(25):
            slot = int(rng.integers(B))
            choice = rng.random()
            if choice < 0.25 and lengths[slot]:
                kv.free_slot(slot)
                lengths[slot] = 0
                continue
            if lengths[slot] == 0:
                n = int(rng.integers(1, S // 2))
                kv.allocate(slot, n)
            else:
                n = int(min(S - 1, lengths[slot] + rng.integers(1, bs + 1)))
                kv.append(slot, n)
            # write rows [lengths[slot], n) into both layouts
            new_k = rng.normal(size=(n - lengths[slot], KH, hd)
                               ).astype(np.float32)
            new_v = rng.normal(size=(n - lengths[slot], KH, hd)
                               ).astype(np.float32)
            lo = int(lengths[slot])
            dense = (dense[0].at[slot, lo:n].set(new_k),
                     dense[1].at[slot, lo:n].set(new_v))
            # paged write goes through the table like a prefill chunk;
            # re-scattering the full prefix keeps the helper call simple
            # (positions < lo rewrite identical values)
            full_k = np.asarray(dense[0][slot])[None, :n]
            full_v = np.asarray(dense[1][slot])[None, :n]
            kv.data = paged_insert_rows(
                kv.data, (jnp.asarray(full_k), jnp.asarray(full_v)),
                kv.axes, kv.seq, kv.pageable, [slot],
                kv.table_rows([slot]), bs)
            lengths[slot] = n
        # decode one token on every slot, both layouts; freed slots write
        # to the shared trash block (their lanes are dead in the engine),
        # so only live rows are compared
        pos = jnp.asarray(np.maximum(lengths, 1) - 1, jnp.int32)
        x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
        out_d, _ = attention_decode(params, x, dense, spec=spec, cfg=cfg,
                                    pos=pos)
        paged_cache = tuple(PagedLeaf(l) for l in kv.data)
        out_p, new_p = attention_decode(params, x, paged_cache, spec=spec,
                                        cfg=cfg, pos=pos,
                                        block_table=kv.table())
        live = lengths > 0
        assert live.any()
        np.testing.assert_array_equal(np.asarray(out_d)[live],
                                      np.asarray(out_p)[live])
        assert isinstance(new_p[0], PagedLeaf)


def test_paged_engine_matches_contiguous_engine_bitwise():
    """End-to-end: the paged engine and the contiguous engine produce
    IDENTICAL greedy outputs over an interleaved mixed-length workload —
    including a PT (track-stacked cache) config."""
    for name in ("tinyllama-1.1b", "pt-30b-d8"):
        cfg = reduced_config(name)
        fns = steps_lib.model_fns(cfg)
        params = fns["init"](jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab_size, int(L)).tolist()
                   for L in rng.integers(2, 14, 5)]
        outs = {}
        for paged in (True, False):
            eng = Engine(cfg, params, max_slots=2, max_seq_len=32,
                         paged=paged, block_size=8)
            assert eng.runner.paged == paged
            outs[paged] = eng.generate(prompts, max_new_tokens=5)
        assert outs[True] == outs[False], name


def test_paged_blocks_reclaimed_and_reused():
    """Done slots return their blocks to the pool (sampler's done flag is
    the reclamation signal); a pool far smaller than slots*capacity still
    serves the whole workload by reuse."""
    cfg, params = _tinyllama()
    # pool: 6 blocks of 8 = 48 tokens, while 4 slots * 64 would need 32
    eng = Engine(cfg, params, max_slots=4, max_seq_len=64, paged=True,
                 block_size=8, num_blocks=6)
    reqs = [eng.submit([1 + i] * 10, max_new_tokens=4) for i in range(6)]
    eng.run()
    assert all(r.state is RequestState.DONE for r in reqs)
    for r in reqs:
        ref = _naive_greedy(params, cfg, r.prompt, 4)
        assert r.output == ref
    u = eng.runner.kv.utilization()
    assert u["used_blocks"] == 0 and u["tokens_stored"] == 0
    # the pool (48 tokens) can hold at most 2 requests of 13 tokens * 2
    # blocks... verify the engine never over-allocated
    assert eng.metrics.max_active >= 2


def test_paged_admission_waits_for_blocks_fcfs():
    """A request that does not fit the free block pool waits (strict
    FCFS) and is admitted once a running request frees its blocks."""
    cfg, params = _tinyllama()
    # 4 blocks of 8 = 32 tokens; each request reserves 10+6-1=15 tokens
    # = 2 blocks; three requests cannot all run at once
    eng = Engine(cfg, params, max_slots=3, max_seq_len=32, paged=True,
                 block_size=8, num_blocks=4)
    reqs = [eng.submit([2 + i] * 10, max_new_tokens=6) for i in range(3)]
    eng.step()
    states = [r.state for r in reqs]
    assert states[:2] == [RequestState.DECODE] * 2
    assert states[2] is RequestState.QUEUED      # pool full: waits
    eng.run()
    assert all(r.state is RequestState.DONE for r in reqs)
    assert reqs[2].output == _naive_greedy(params, cfg, reqs[2].prompt, 6)


def test_oversized_request_rejected_at_submit():
    """A reservation larger than the whole pool can never run: submit
    returns a terminal REJECTED request (reason via the event callback)
    instead of raising out of the caller's serving loop."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=2, max_seq_len=64, paged=True,
                 block_size=8, num_blocks=3)   # 24-token pool
    events = []
    req = eng.submit([1] * 40, max_new_tokens=4,
                     on_event=lambda r, why: events.append(why))
    assert req.state is RequestState.REJECTED
    assert "KV blocks" in req.finish_reason
    assert events and "KV blocks" in events[0]
    assert not eng.scheduler.has_work()          # never queued
    eng.run()                                    # still serviceable
    assert eng.metrics.summary()["rejected"] == 1


def test_paged_windowed_arch_keeps_rings_dense():
    """gemma2 alternates sliding-window and full attention: full layers
    page, ring layers stay dense — and outputs still match the naive
    reference."""
    cfg = reduced_config("gemma2-2b")
    params = init_lm(jax.random.PRNGKey(1), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=64, paged=True)
    assert eng.runner.paged
    flags = jax.tree_util.tree_leaves(eng.runner.kv.pageable)
    assert any(flags) and not all(flags)
    rng = np.random.default_rng(0)
    p = rng.integers(1, cfg.vocab_size, 17).tolist()
    out = eng.generate([p], max_new_tokens=6)[0]
    assert out == _naive_greedy(params, cfg, p, 6)


def test_recurrent_arch_serves_paged_with_state_leaves():
    """An all-SSM stack has ZERO pageable leaves, but still serves
    through the paged engine: every cache leaf is a per-slot 'state'
    row, and admission/reclamation meters virtual blocks so scheduling
    policy (FCFS, preemption, watchdog) is architecture-independent."""
    cfg = reduced_config("falcon-mamba-7b")
    params = init_lm(jax.random.PRNGKey(2), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32, paged=True)
    assert eng.runner.paged              # virtual block accounting
    assert eng.runner.kv.leaf_kinds() == {"state": 2}
    assert not eng.runner.kv.any_pageable
    assert eng.runner.has_dense_leaves
    out = eng.generate([[3, 1, 4, 1, 5]], max_new_tokens=4)[0]
    assert out == _naive_greedy(params, cfg, [3, 1, 4, 1, 5], 4)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_parity_lm_and_pt():
    """Feeding the prompt chunk-by-chunk through the paged cache must
    reproduce the whole-prompt greedy outputs exactly, across chunk
    boundaries (L < C, L == k*C, L % C != 0)."""
    for name in ("tinyllama-1.1b", "pt-30b-d8"):
        cfg = reduced_config(name)
        fns = steps_lib.model_fns(cfg)
        params = fns["init"](jax.random.PRNGKey(0), cfg)
        eng = Engine(cfg, params, max_slots=2, max_seq_len=32,
                     prefill_chunk=4)
        assert eng.runner.prefill_chunk == 4 and eng.runner.paged
        for L in (3, 8, 9):
            p = [(5 * i + 2) % cfg.vocab_size for i in range(L)]
            out = eng.generate([p], max_new_tokens=5)[0]
            ref = _naive_greedy(params, cfg, p, 5)
            assert out == ref, (name, L, out, ref)


def test_chunked_prefill_interleaves_with_decode():
    """A short request admitted behind a long prompt gets its first token
    while the long prefill is still in flight — the chunked scheduler
    never stalls decode behind a monolithic prefill."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=2, max_seq_len=64, paged=True,
                 block_size=8, prefill_chunk=4)
    long_req = eng.submit(list(range(1, 33)), max_new_tokens=4)   # 8 chunks
    short_req = eng.submit([7, 8, 9], max_new_tokens=8)           # 1 chunk
    saw_overlap = False
    for _ in range(64):
        eng.step()
        if (long_req.state is RequestState.PREFILL
                and len(short_req.output) > 0):
            saw_overlap = True
        if not eng.scheduler.has_work():
            break
    assert saw_overlap, "short request should decode during long prefill"
    assert long_req.state is RequestState.DONE
    assert short_req.output == _naive_greedy(params, cfg, [7, 8, 9], 8)
    assert long_req.output == _naive_greedy(params, cfg, long_req.prompt, 4)
    # the long prompt advanced one chunk per engine step
    assert (1, 4) in eng.runner.chunk_shapes or \
        (2, 4) in eng.runner.chunk_shapes


def test_chunked_prefill_serves_ring_and_state_archs():
    """Sliding-window rings and recurrent state take multi-token
    cache-append chunks through the layout-polymorphic chunk program
    (ring side-buffer / masked state scan) — the knob stays ON and
    greedy outputs still match the whole-prompt reference, across
    chunk boundaries (L < C, L == k*C, L % C != 0)."""
    for name in ("falcon-mamba-7b", "gemma2-2b", "recurrentgemma-9b"):
        cfg = reduced_config(name)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        eng = Engine(cfg, params, max_slots=2, max_seq_len=32,
                     prefill_chunk=4)
        assert eng.runner.prefill_chunk == 4, name
        for L in (3, 8, 9):
            p = [(5 * i + 2) % cfg.vocab_size for i in range(L)]
            out = eng.generate([p], max_new_tokens=5)[0]
            ref = _naive_greedy(params, cfg, p, 5)
            assert out == ref, (name, L, out, ref)


def test_chunked_prefill_stays_off_for_moe():
    """Capacity-based MoE routing is batch-global: a padded chunk row
    would steal expert capacity from real tokens, so MoE configs keep
    whole-prompt (exact-length) prefill."""
    cfg = reduced_config("deepseek-v2-236b")
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_slots=1, max_seq_len=32,
                 prefill_chunk=4)
    assert eng.runner.prefill_chunk == 0


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

def test_probe_axes_on_stacked_layouts_with_aliasing_dims():
    """The batch/seq probes must see through stacking dims whose size
    equals the probe values (the PT track dim or a window of 8), and must
    flag genuinely ambiguous layouts instead of guessing."""
    # track-stacked leaf [8, b, s, 4] + ring leaf [b, min(s, 8), 4]
    def init_fn(cfg, b, s):
        return {"stacked": jnp.zeros((8, b, s, 4)),
                "ring": jnp.zeros((b, min(s, 8), 4)),
                "state": jnp.zeros((b, 13))}

    assert batch_axes(init_fn, None) == {"stacked": 1, "ring": 0,
                                         "state": 0}
    assert seq_axes(init_fn, None) == {"stacked": 2, "ring": None,
                                       "state": None}

    def ambiguous(cfg, b, s):
        return jnp.zeros((b, b, 4))          # batch twice: must raise

    with pytest.raises(ValueError, match="ambiguous batch axis"):
        batch_axes(ambiguous, None)


def test_insert_rows_single_scatter_per_leaf():
    """The vectorized insert matches per-row insertion semantics (with
    zero-padding of short non-batch dims) and lowers as scatters, not a
    per-row chain of dynamic_update_slices."""
    dst = {"a": jnp.full((8, 6, 2, 4), -1.0), "b": jnp.full((3, 8, 5), -1.0)}
    axes = {"a": 0, "b": 1}
    src = {"a": jnp.ones((3, 4, 2, 4)), "b": 2 * jnp.ones((3, 3, 5))}
    slots = [6, 0, 2]
    out = insert_rows(dst, src, axes, jnp.asarray(slots))
    a, b = np.asarray(out["a"]), np.asarray(out["b"])
    for r, slot in enumerate(slots):
        np.testing.assert_array_equal(a[slot, :4], np.ones((4, 2, 4)))
        np.testing.assert_array_equal(a[slot, 4:], np.zeros((2, 2, 4)))
        np.testing.assert_array_equal(b[:, slot, :], 2 * np.ones((3, 5)))
    untouched = [i for i in range(8) if i not in slots]
    np.testing.assert_array_equal(a[untouched], -np.ones((5, 6, 2, 4)))
    np.testing.assert_array_equal(b[:, untouched], -np.ones((3, 5, 5)))
    ir = jax.jit(lambda d, s, sl: insert_rows(d, s, axes, sl)).lower(
        dst, src, jnp.asarray(slots)).as_text()
    assert "dynamic_update_slice" not in ir and "scatter" in ir


def test_eos_stops_generation():
    """A request stops as soon as the (greedy) model emits its eos id."""
    cfg, params = _tinyllama()
    probe = Engine(cfg, params, max_slots=1, max_seq_len=32)
    out = probe.generate([[1, 2, 3]], max_new_tokens=6)[0]
    eos = out[2]                              # pretend token #3 is EOS
    eng = Engine(cfg, params, max_slots=1, max_seq_len=32)
    req = eng.submit([1, 2, 3], 6, eos_id=eos)
    eng.run()
    assert req.output == out[:3]
    assert req.state is RequestState.DONE


# ---------------------------------------------------------------------------
# per-request seeded reproducibility
# ---------------------------------------------------------------------------

def test_per_request_seed_reproducible_across_batch_composition():
    """Sampling randomness is keyed by (request seed, token counter), so
    a sampled request replays BIT-IDENTICALLY whether it runs alone or
    next to other (differently-parameterized) requests."""
    cfg, params = _tinyllama()
    sp = SampleParams(temperature=0.9, top_k=20)
    solo = Engine(cfg, params, max_slots=2, max_seq_len=32, seed=0)
    r_solo = solo.submit([1, 2, 3, 4], 6, params=sp, seed=1234)
    solo.run()

    mixed = Engine(cfg, params, max_slots=2, max_seq_len=32, seed=99)
    r_other = mixed.submit([9, 8, 7, 6, 5], 6,
                           params=SampleParams(temperature=1.3), seed=777)
    r_same = mixed.submit([1, 2, 3, 4], 6, params=sp, seed=1234)
    mixed.run()
    assert r_same.output == r_solo.output
    assert all(0 <= t < cfg.vocab_size for t in r_other.output)

    # and two identical engines are trivially bitwise-equal end to end
    again = Engine(cfg, params, max_slots=2, max_seq_len=32, seed=99)
    a = again.submit([9, 8, 7, 6, 5], 6,
                     params=SampleParams(temperature=1.3), seed=777)
    b = again.submit([1, 2, 3, 4], 6, params=sp, seed=1234)
    again.run()
    assert a.output == r_other.output and b.output == r_same.output


def test_default_seeds_deterministic_per_engine_seed():
    """Without explicit per-request seeds, outputs are still a pure
    function of (engine seed, submission order)."""
    cfg, params = _tinyllama()
    outs = []
    for _ in range(2):
        eng = Engine(cfg, params, max_slots=2, max_seq_len=32, seed=5)
        outs.append(eng.generate([[1, 2, 3], [4, 5, 6]], 5,
                                 params=SampleParams(temperature=1.0)))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# sampler parity grids
# ---------------------------------------------------------------------------

def test_sampler_parity_grid_scalar_vs_batched():
    """sample_batched with uniform rows is bitwise-equal to the scalar
    sampler across the temperature/top-k/top-p grid (same key, same
    filter, same categorical draw)."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    for temp in (0.0, 0.7, 1.0):
        for tk in (0, 3, 16):
            for tp in (1.0, 0.9, 0.5):
                sp = SampleParams(temperature=temp, top_k=tk, top_p=tp)
                key = jax.random.PRNGKey(int(temp * 10 + tk + tp * 100))
                ref = sample(logits, key, sp)
                t, k, p = stack_params([sp] * 5)
                out = sample_batched(logits, key, jnp.asarray(t),
                                     jnp.asarray(k), jnp.asarray(p))
                assert (np.asarray(ref) == np.asarray(out)).all(), sp


def test_sample_rows_respects_filters_per_row():
    """Per-row-keyed sampling stays inside each row's own filtered
    support: greedy rows are exactly argmax, top-k rows land in the
    row's top-k set, top-p rows inside the nucleus."""
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    mix = [SampleParams(), SampleParams(temperature=1.0, top_k=3),
           SampleParams(temperature=0.8, top_p=0.7),
           SampleParams(temperature=1.2, top_k=8, top_p=0.9)]
    t, k, p = stack_params(mix)
    am = np.asarray(jnp.argmax(logits, -1))
    for trial in range(20):
        keys = row_keys(jnp.full((4,), trial, jnp.uint32),
                        jnp.arange(4, dtype=jnp.int32), 0)
        out = np.asarray(sample_rows(logits, keys, jnp.asarray(t),
                                     jnp.asarray(k), jnp.asarray(p)))
        assert out[0] == am[0]
        top3 = np.asarray(jax.lax.top_k(logits[1], 3)[1])
        assert out[1] in top3.tolist()
        top8 = np.asarray(jax.lax.top_k(logits[3], 8)[1])
        assert out[3] in top8.tolist()


# ---------------------------------------------------------------------------
# speculative decoding: accept_step math
# ---------------------------------------------------------------------------

def test_accept_step_greedy_semantics():
    """Greedy rows: acceptance is exact argmax agreement; the first
    disagreement is replaced by the target argmax; full agreement earns
    the bonus token."""
    V, K = 8, 3
    tgt = np.full((2, K + 1, V), -5.0, np.float32)
    # target argmax chain: 3, 4, 5, 6
    for j, a in enumerate((3, 4, 5, 6)):
        tgt[:, j, a] = 5.0
    dl = np.full((2, K, V), -5.0, np.float32)
    # row 0 drafts agree everywhere; row 1 disagrees at position 1
    for j, a in enumerate((3, 4, 5)):
        dl[0, j, a] = 5.0
    for j, a in enumerate((3, 0, 5)):
        dl[1, j, a] = 5.0
    d_toks = jnp.asarray([[3, 4, 5], [3, 0, 5]], jnp.int32)
    zeros = jnp.zeros((2,), jnp.int32)
    packed = accept_step(jnp.asarray(tgt), jnp.asarray(dl), d_toks,
                         jnp.zeros((2,), jnp.uint32), zeros,
                         jnp.zeros((2,), jnp.float32), zeros,
                         jnp.ones((2,), jnp.float32),
                         jnp.ones((2,), bool))
    toks = np.asarray(packed[:-1].T)
    m = np.asarray(packed[-1])
    assert m.tolist() == [K + 1, 2]
    assert toks[0].tolist() == [3, 4, 5, 6]          # all + bonus argmax
    assert toks[1, :2].tolist() == [3, 4]            # d_1, then target argmax


def test_accept_step_inactive_rows_emit_nothing():
    V, K = 8, 2
    rng = np.random.default_rng(0)
    packed = accept_step(
        jnp.asarray(rng.normal(size=(3, K + 1, V)), jnp.float32),
        jnp.asarray(rng.normal(size=(3, K, V)), jnp.float32),
        jnp.asarray(rng.integers(0, V, (3, K)), jnp.int32),
        jnp.arange(3, dtype=jnp.uint32), jnp.zeros((3,), jnp.int32),
        jnp.ones((3,), jnp.float32), jnp.zeros((3,), jnp.int32),
        jnp.ones((3,), jnp.float32), jnp.asarray([True, False, True]))
    m = np.asarray(packed[-1])
    toks = np.asarray(packed[:-1].T)
    assert m[1] == 0 and (toks[1] == 0).all()
    assert m[0] >= 1 and m[2] >= 1


def test_accept_step_matches_target_distribution():
    """The statistical heart of speculative decoding: whatever the
    drafter proposes, the emitted-token marginal equals the target
    softmax.  4000 seeded trials of the same (target, draft) logits;
    position-0 and accepted-position-1 frequencies must match the target
    distribution (binomial tolerance)."""
    V, K, N = 16, 3, 4000
    rng = np.random.default_rng(0)
    t_log = (rng.normal(size=(K + 1, V)) * 1.5).astype(np.float32)
    d_log = (rng.normal(size=(K, V)) * 1.5).astype(np.float32)
    seeds = jnp.arange(N, dtype=jnp.uint32)
    counters = jnp.zeros((N,), jnp.int32)
    temps = jnp.ones((N,), jnp.float32)
    tks = jnp.zeros((N,), jnp.int32)
    tps = jnp.ones((N,), jnp.float32)
    # drafts sampled from q exactly as the runner's draft loop does
    d_toks = jnp.stack(
        [sample_rows(jnp.broadcast_to(jnp.asarray(d_log[j]), (N, V)),
                     row_keys(seeds, counters + j, SALT_DRAFT),
                     temps, tks, tps) for j in range(K)], axis=1)
    packed = accept_step(
        jnp.broadcast_to(jnp.asarray(t_log)[None], (N, K + 1, V)),
        jnp.broadcast_to(jnp.asarray(d_log)[None], (N, K, V)),
        d_toks, seeds, counters, temps, tks, tps, jnp.ones((N,), bool))
    toks = np.asarray(packed[:-1].T)
    m = np.asarray(packed[-1])
    assert (m >= 1).all() and (m <= K + 1).all()
    p0 = np.asarray(jax.nn.softmax(jnp.asarray(t_log[0])))
    freq = np.bincount(toks[:, 0], minlength=V) / N
    assert np.abs(freq - p0).max() < 4 * np.sqrt(0.25 / N) + 0.01
    # conditional correctness at position 1, among rows that accepted d_1
    deep = m >= 2
    assert deep.sum() > 300
    p1 = np.asarray(jax.nn.softmax(jnp.asarray(t_log[1])))
    freq1 = np.bincount(toks[deep, 1], minlength=V) / deep.sum()
    assert np.abs(freq1 - p1).max() < 4 * np.sqrt(0.25 / deep.sum()) + 0.02


# ---------------------------------------------------------------------------
# speculative decoding: engine end-to-end
# ---------------------------------------------------------------------------

def _spec_pt_cfg(vocab: int = 64) -> ModelConfig:
    """Small 4-track PT config (D=2, tiny vocab) for speculative tests."""
    dense = ModelConfig(
        name="pt-spec-test", family="dense", n_layers=4, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=vocab,
        layer_specs={"full": LayerSpec(mixer="gqa", mlp="swiglu")},
        pattern_unit=("full",), tie_embeddings=False, dtype="float32")
    return pt_ify(dense, 4, 2, width_mult=8)


def test_spec_greedy_bitwise_matches_plain_decode():
    """THE acceptance bar: greedy track-speculative decode is bitwise-
    identical to plain greedy decode, whatever the drafter predicts —
    on the small PT config and on the reduced paper config."""
    for cfg, n_new in ((_spec_pt_cfg(), 10),
                       (reduced_config("pt-30b-d8"), 5)):
        fns = steps_lib.model_fns(cfg)
        params = fns["init"](jax.random.PRNGKey(0), cfg)
        prompts = [[5, 9, 2, 7], [11, 3, 1, 8, 4, 2], [17, 23]]
        plain = Engine(cfg, params, max_slots=2, max_seq_len=48)
        ref = plain.generate(prompts, max_new_tokens=n_new)
        spec = Engine(cfg, params, max_slots=2, max_seq_len=48,
                      speculate_k=3, draft_tracks=2)
        assert spec.runner.speculate_k == 3
        out = spec.generate(prompts, max_new_tokens=n_new)
        assert out == ref, cfg.name
        m = spec.metrics.summary()
        assert m["spec_steps"] > 0
        assert 0.0 <= m["acceptance_rate"] <= 1.0


def test_spec_tied_tracks_accept_everything_and_save_steps():
    """With identical tracks the d-track drafter IS the target model:
    acceptance hits 1.0, every spec step advances K+1 tokens, and the
    engine finishes in ~1/(K+1) of the plain step count — while output
    stays bitwise-identical."""
    cfg = _spec_pt_cfg()
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    params["blocks"] = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[:, :, :1], l.shape), params["blocks"])
    prompts = [[1, 2, 3, 4]] * 2
    plain = Engine(cfg, params, max_slots=2, max_seq_len=64)
    ref = plain.generate(prompts, max_new_tokens=16)
    spec = Engine(cfg, params, max_slots=2, max_seq_len=64,
                  speculate_k=4, draft_tracks=1)
    out = spec.generate(prompts, max_new_tokens=16)
    assert out == ref
    assert spec.metrics.summary()["acceptance_rate"] == 1.0
    assert spec.steps_run * 3 < plain.steps_run


def test_spec_sampled_distribution_matches_plain():
    """Sampled speculative output follows the target distribution: token
    frequencies over a few hundred sampled tokens match plain decode
    within a loose total-variation tolerance (deterministic given the
    fixed seeds, so this never flakes)."""
    cfg = _spec_pt_cfg(vocab=32)
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(1), cfg)
    sp = SampleParams(temperature=1.0)
    hists = {}
    for mode, k in (("plain", 0), ("spec", 3)):
        eng = Engine(cfg, params, max_slots=4, max_seq_len=32,
                     speculate_k=k, draft_tracks=2, seed=0)
        toks = []
        for i in range(40):
            toks += eng.generate([[1 + (i % 5), 2, 3]], max_new_tokens=8,
                                 params=sp)[0]
        hists[mode] = np.bincount(toks, minlength=cfg.vocab_size) \
            / len(toks)
    tv = 0.5 * np.abs(hists["plain"] - hists["spec"]).sum()
    assert tv < 0.22, tv


def test_spec_with_chunked_prefill_greedy_parity():
    """Speculation composes with chunked prefill: the drafter's cache is
    filled at decode start and greedy outputs still match the naive
    reference exactly."""
    cfg = _spec_pt_cfg()
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=48,
                 prefill_chunk=4, speculate_k=3, draft_tracks=2)
    assert eng.runner.prefill_chunk == 4 and eng.runner.speculate_k == 3
    for L in (3, 8, 9):
        p = [(5 * i + 2) % cfg.vocab_size for i in range(L)]
        out = eng.generate([p], max_new_tokens=6)[0]
        ref = _naive_greedy(params, cfg, p, 6)
        assert out == ref, (L, out, ref)


def test_spec_eos_and_capacity_truncation():
    """EOS inside an accepted run stops the request mid-pack, and the
    remaining-budget cap truncates a speculative burst exactly like
    plain decode."""
    cfg = _spec_pt_cfg()
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    probe = Engine(cfg, params, max_slots=1, max_seq_len=48)
    out = probe.generate([[1, 2, 3]], max_new_tokens=8)[0]
    eos = out[3]
    eng = Engine(cfg, params, max_slots=1, max_seq_len=48,
                 speculate_k=4, draft_tracks=2)
    req = eng.submit([1, 2, 3], 8, eos_id=eos)
    eng.run()
    assert req.output == out[:4]
    assert req.state is RequestState.DONE
    # capacity clamp: prompt 12 + room for 5 positions only
    plain = Engine(cfg, params, max_slots=1, max_seq_len=16)
    ref = plain.submit([1] * 12, max_new_tokens=50)
    plain.run()
    spec = Engine(cfg, params, max_slots=1, max_seq_len=16,
                  speculate_k=3, draft_tracks=2)
    r = spec.submit([1] * 12, max_new_tokens=50)
    spec.run()
    assert r.truncated and r.output == ref.output


def test_spec_gating_falls_back_to_plain_decode():
    """speculate_k is silently dropped where the draft/verify structure
    does not exist: non-PT configs, contiguous caches, recurrent archs."""
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=1, max_seq_len=32, speculate_k=4)
    assert eng.runner.speculate_k == 0            # non-PT
    out = eng.generate([[1, 2, 3]], max_new_tokens=4)[0]
    assert out == _naive_greedy(params, cfg, [1, 2, 3], 4)

    pt = _spec_pt_cfg()
    fns = steps_lib.model_fns(pt)
    pt_params = fns["init"](jax.random.PRNGKey(0), pt)
    eng = Engine(pt, pt_params, max_slots=1, max_seq_len=32,
                 paged=False, speculate_k=4)
    assert eng.runner.speculate_k == 0            # needs the paged cache

    rec = reduced_config("falcon-mamba-7b")
    rec_params = init_lm(jax.random.PRNGKey(2), rec)
    eng = Engine(rec, rec_params, max_slots=1, max_seq_len=32,
                 speculate_k=4)
    assert eng.runner.speculate_k == 0            # recurrent mixer


def test_spec_single_host_transfer_per_step():
    """The speculative step keeps the one-packed-transfer protocol."""
    cfg = _spec_pt_cfg()
    fns = steps_lib.model_fns(cfg)
    params = fns["init"](jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_slots=2, max_seq_len=32,
                 speculate_k=3, draft_tracks=2)
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=6)
    assert eng.runner.decode_transfers == eng.steps_run


def test_paged_kernel_decode_is_one_program_at_any_live_length():
    """With the paged kernel (forced through ``use_pallas``: interpret
    mode off the chip) the decode program reads the live lengths at run
    time: decoding at two quite different live lengths compiles it once.
    The runner counts the kernel's calls and the live pool blocks they
    stream, from the positions."""
    cfg = _spec_pt_cfg().replace(use_pallas=True)
    params = steps_lib.model_fns(cfg)["init"](jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_slots=3, max_seq_len=64, block_size=8,
                 num_blocks=24)
    r = eng.runner
    assert r.decode_attn == "paged_kernel"
    S = r.max_slots
    z = np.zeros((S,), np.int32)
    seeds, temps = np.zeros((S,), np.uint32), np.zeros((S,), np.float32)
    steps = [([3, 0, 0], [True, False, False]),
             ([50, 20, 63], [True, True, False])]
    for pos, active in steps:
        r.decode(z, np.asarray(pos, np.int32), np.asarray(active), seeds,
                 z, temps, z, np.ones((S,), np.float32),
                 np.full((S,), -1, np.int32), z + 8)
    assert r._decode._cache_size() == 1
    assert r.decode_kernel_calls == 2
    # ceil((pos + 1) / 8) over the active rows: 1, then 7 + 3
    assert r.decode_live_blocks == 1 + 7 + 3
    # the jnp path on this backend gathers, and says so
    eng = Engine(_spec_pt_cfg(), params, max_slots=3, max_seq_len=64,
                 block_size=8, num_blocks=24)
    assert eng.runner.decode_attn == "gather"


def test_attention_chunk_kv_max_len_parity():
    """Truncating the verify gather to the live prefix must not change
    the attention output (dropped columns are causally masked and
    contribute exact zeros to the online softmax)."""
    cfg = _gqa_cfg()
    spec = cfg.spec("x")
    KH, hd = cfg.n_kv_heads, cfg.head_dim
    params = attention_init(jax.random.PRNGKey(0), cfg.d_model,
                            cfg.n_heads, KH, hd)
    B, S, bs, C = 2, 32, 8, 3
    init_kv = lambda c, b, s: (jnp.zeros((b, s, KH, hd), jnp.float32),
                               jnp.zeros((b, s, KH, hd), jnp.float32))
    kv = PagedKVCache(init_kv, cfg, max_slots=B, max_seq_len=S,
                      block_size=bs)
    rng = np.random.default_rng(0)
    for slot in range(B):
        kv.allocate(slot, 12)
        rows = (jnp.asarray(rng.normal(size=(1, 12, KH, hd)), jnp.float32),
                jnp.asarray(rng.normal(size=(1, 12, KH, hd)), jnp.float32))
        kv.data = paged_insert_rows(kv.data, rows, kv.axes, kv.seq,
                                    kv.pageable, [slot],
                                    kv.table_rows([slot]), bs)
    x = jnp.asarray(rng.normal(size=(B, C, cfg.d_model)), jnp.float32)
    pos = jnp.asarray([4, 9], jnp.int32)
    cache = tuple(PagedLeaf(l) for l in kv.data)
    full, _ = attention_chunk(params, x, cache, spec=spec, cfg=cfg,
                              pos=pos, block_table=kv.table())
    trunc, _ = attention_chunk(params, x, cache, spec=spec, cfg=cfg,
                               pos=pos, block_table=kv.table(),
                               kv_max_len=16)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(trunc))


# ---------------------------------------------------------------------------
# metrics hardening
# ---------------------------------------------------------------------------

def test_metrics_summary_safe_on_empty_and_reports_acceptance():
    """summary() must not crash before any request finishes (empty
    percentile lists, no timestamps) and must expose acceptance_rate."""
    m = EngineMetrics().summary()
    assert m["requests"] == 0
    assert m["ttft_ms"]["p50"] == 0.0 and m["tpot_ms"]["p99"] == 0.0
    assert m["throughput_tok_s"] == 0.0
    assert m["acceptance_rate"] == 0.0 and m["spec_steps"] == 0

    # engine with work submitted but zero steps run: still safe
    cfg, params = _tinyllama()
    eng = Engine(cfg, params, max_slots=1, max_seq_len=32)
    eng.submit([1, 2, 3], 4)
    m = eng.metrics.summary()
    assert m["requests"] == 0 and m["output_tokens"] == 0

    # acceptance accounting
    em = EngineMetrics()
    em.observe_spec(3, 4)
    em.observe_spec(1, 4)
    s = em.summary()
    assert s["spec_steps"] == 2
    assert abs(s["acceptance_rate"] - 0.5) < 1e-9
    assert 0.0 < s["acceptance_ema"] <= 1.0
