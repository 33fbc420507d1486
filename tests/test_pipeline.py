"""Async pipelined engine: bitwise parity against the synchronous loop
(`pipeline_depth=0`) across every serving feature arm, pre-planned
program replay, and metrics correctness under pipelining.

Parity here is exact list equality of every emitted token: the pipelined
loop dispatches step N+1 from step N's still-on-device packed result, so
any divergence in the device-side carry, the host-override masking, or
the slot-generation guard shows up as a token mismatch."""
import time

import jax
import numpy as np
import pytest

from repro.common.types import LayerSpec, ModelConfig
from repro.configs import reduced_config
from repro.launch import steps as steps_lib
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine, RequestState
from repro.serving.sampler import SampleParams

from tests.stub_runner import stub_engine

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10], [11, 12, 13, 14, 15]]


def _cfg():
    return ModelConfig(
        name="pipeline-test", family="dense", n_layers=1, d_model=16,
        n_heads=2, n_kv_heads=1, d_ff=32, vocab_size=64,
        layer_specs={"full": LayerSpec(mixer="gqa", mlp="swiglu")},
        pattern_unit=("full",), tie_embeddings=False, dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = steps_lib.model_fns(cfg)["init"](jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def pt_model():
    # speculation needs a parallel-track architecture (the drafter is a
    # track slice); dense configs gate speculate_k off silently
    cfg = reduced_config("pt-30b-d8")
    params = steps_lib.model_fns(cfg)["init"](jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(model, depth, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 24)
    return Engine(cfg, params, pipeline_depth=depth, **kw)


def _both(model, gen, **kw):
    """Run ``gen(engine)`` on a sync and a depth-1 pipelined engine and
    return both results (the pipelined engine too, for extra asserts)."""
    sync = gen(_engine(model, 0, **kw))
    eng = _engine(model, 1, **kw)
    piped = gen(eng)
    return sync, piped, eng


# ---------------------------------------------------------------------------
# bitwise parity arms
# ---------------------------------------------------------------------------

def test_pipelined_greedy_matches_sync(model):
    gen = lambda e: e.generate(PROMPTS, max_new_tokens=6)
    sync, piped, eng = _both(model, gen)
    assert piped == sync
    assert not eng._inflight            # fully drained
    assert eng.metrics.steps_in_flight >= 1


def test_pipelined_sampled_matches_sync(model):
    sp = SampleParams(temperature=1.0, top_k=8)
    gen = lambda e: e.generate(PROMPTS, max_new_tokens=6, params=sp)
    sync, piped, _ = _both(model, gen)
    assert piped == sync


def test_pipelined_chunked_prefill_matches_sync(model):
    gen = lambda e: e.generate(PROMPTS, max_new_tokens=6)
    sync, piped, _ = _both(model, gen, prefill_chunk=4)
    assert piped == sync


def test_pipelined_speculative_matches_sync(pt_model):
    gen = lambda e: e.generate(PROMPTS[:3], max_new_tokens=8)
    sync, piped, eng = _both(pt_model, gen, speculate_k=2, max_slots=4)
    assert eng.runner.speculate_k == 2   # really speculating, not gated
    assert piped == sync


def test_pipelined_warm_prefix_cache_matches_sync(model):
    def gen(e):
        a = e.generate([[1, 2, 3, 4, 5, 6, 7, 8]], max_new_tokens=4)
        b = e.generate([[1, 2, 3, 4, 5, 6, 7, 8]], max_new_tokens=4)
        return a + b
    sync, piped, _ = _both(model, gen)
    assert piped == sync


def test_pipelined_fork_matches_sync(model):
    """fork() drains the pipeline first, so k pipelined steps + fork
    see exactly the host state of k sync steps + fork — children and
    parent streams stay bitwise-identical."""
    def gen(e):
        sp = SampleParams(temperature=1.0, top_k=8)
        r = e.submit([1, 2, 3, 4], 10, params=sp)
        for _ in range(4):
            e.step()
        kids = e.fork(r, 2)
        e.run()
        return [r.output] + [k.output for k in kids]
    sync, piped, eng = _both(model, gen, max_slots=4)
    assert piped == sync
    assert not eng._inflight


def test_pipelined_depth_two_matches_sync(model):
    sync = _engine(model, 0).generate(PROMPTS, max_new_tokens=6)
    deep = _engine(model, 2).generate(PROMPTS, max_new_tokens=6)
    assert deep == sync


def test_pipelined_dense_cache_matches_sync(model):
    gen = lambda e: e.generate(PROMPTS, max_new_tokens=6)
    sync, piped, _ = _both(model, gen, paged=False)
    assert piped == sync


# ---------------------------------------------------------------------------
# pre-planned per-bucket programs
# ---------------------------------------------------------------------------

def test_preplanned_programs_replay_bitwise(model):
    sync = _engine(model, 0).generate(PROMPTS, max_new_tokens=6)
    eng = _engine(model, 1, preplan=True)
    piped = eng.generate(PROMPTS, max_new_tokens=6)
    assert piped == sync
    assert len(eng.runner._planned) >= 1
    assert eng.runner.planned_hits > 0   # dispatch replayed AOT programs


def test_preplan_covers_spec_programs(pt_model):
    eng = _engine(pt_model, 0, speculate_k=2, max_slots=4, preplan=True)
    assert eng.runner.speculate_k == 2
    assert any(kind == "spec" for kind, _ in eng.runner._planned)
    outs = eng.generate(PROMPTS[:3], max_new_tokens=8)
    ref = _engine(pt_model, 0, speculate_k=2,
                  max_slots=4).generate(PROMPTS[:3], max_new_tokens=8)
    assert outs == ref
    assert eng.runner.planned_hits > 0


# ---------------------------------------------------------------------------
# metrics under pipelining
# ---------------------------------------------------------------------------

def test_pipelined_metrics_report_gap_and_depth(model, monkeypatch):
    """Peak depth comes from the metrics; the gaps between dispatches
    from the engine's ``serve.launch`` spans, as a profile shows them."""
    launched = []

    class Recording(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            super().__init__(name, **kw)
            if name == "serve.launch":
                launched.append(time.perf_counter())

    monkeypatch.setattr(engine_mod, "_span", Recording)
    eng = _engine(model, 1)
    eng.generate(PROMPTS, max_new_tokens=6)
    s = eng.metrics.summary()
    assert s["steps_in_flight"] >= 1
    assert "dispatch_gap_ms" not in s
    gaps = np.diff(launched)
    assert len(gaps) >= 1 and (gaps > 0).all()
    sync = _engine(model, 0)
    sync.generate(PROMPTS, max_new_tokens=6)
    assert sync.metrics.summary()["steps_in_flight"] == 0


def test_pipelined_tpot_not_under_reported():
    """TTFT/TPOT are stamped at transfer COMPLETION, not dispatch: with
    a simulated device time of ``s`` per step, the pipelined per-token
    latency must still report ~s — a loop that stamped at dispatch
    would report near zero."""
    s = 0.003
    def tpots(depth):
        eng, _ = stub_engine(max_slots=2, num_blocks=64,
                             step_time_s=s, pipeline_depth=depth)
        outs = eng.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=8)
        assert all(len(o) == 8 for o in outs)
        return eng.metrics.summary()["tpot_ms"]["mean"]
    piped = tpots(1)
    assert piped >= 0.9 * s * 1e3, (
        f"pipelined TPOT {piped:.3f}ms under-reports the {s*1e3:.1f}ms "
        "simulated device step: stamped at dispatch, not completion?")
