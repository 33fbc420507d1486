"""The engine traced from inside: request marks (``t_admitted``), the
count of output tokens emitted by steps that also ran a chunk program
(``chunk_step_tokens``), the step-phase spans on the profiler's host
plane (``engine.SPANS``) and the named scopes in the step programs."""
import importlib.util
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.common.types import LayerSpec, ModelConfig
from repro.configs import reduced_config
from repro.launch import steps as steps_lib
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine, RequestState
from repro.serving.faults import FaultPlan

from tests.stub_runner import stub_engine

ROOT = Path(__file__).resolve().parents[1]
SCOPES = ("embed", "attn", "kv_gather", "kv_write", "mlp", "lm_head",
          "sample")


# ---------------------------------------------------------------------------
# request marks and counters (model-free)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 8])
@pytest.mark.parametrize("depth", [0, 1])
def test_marks_come_in_order(chunk, depth):
    eng, _ = stub_engine(max_slots=2, num_blocks=64, prefill_chunk=chunk,
                         pipeline_depth=depth)
    reqs = [eng.submit(list(range(1, n + 1)), 6) for n in (3, 20, 9, 12)]
    eng.run()
    for r in reqs:
        assert r.state is RequestState.DONE
        assert 0 < r.t_submit <= r.t_admitted <= r.t_first <= r.t_done
    # two slots, four requests: the last two waited for a slot
    assert reqs[3].t_admitted > reqs[0].t_first


def test_unadmit_clears_first_admission():
    """A transfer fault on the bucketed prefill rolls the admission back:
    the request is queued again with no admission mark."""
    eng, _ = stub_engine(max_slots=1, num_blocks=64,
                         fault_plan=FaultPlan(transfer_ops=frozenset({0})))
    req = eng.submit([1, 2, 3], 4)
    eng.step()
    assert req.state is RequestState.QUEUED
    assert req.t_admitted == 0.0
    eng.step()
    assert req.state is RequestState.DECODE
    assert req.t_admitted > req.t_submit
    eng.run()
    assert req.state is RequestState.DONE


def test_preemption_resume_keeps_admission_mark():
    eng, _ = stub_engine(max_slots=1, num_blocks=64, prefill_chunk=8)
    low = eng.submit([1, 2, 3], 8, priority=0)
    eng.step()
    eng.step()
    admitted = low.t_admitted
    assert low.state is RequestState.DECODE and admitted > 0
    high = eng.submit([4, 5, 6], 4, priority=1)
    eng.step()                           # high preempts low for the slot
    assert low.preemptions == 1 and low.state is RequestState.QUEUED
    assert low.t_admitted == admitted
    eng.run()
    assert low.state is high.state is RequestState.DONE
    assert low.t_admitted == admitted < high.t_admitted < low.t_done


@pytest.mark.parametrize("depth", [0, 1])
def test_chunk_step_tokens_on_a_scripted_schedule(depth):
    """Chunk 8.  A (5 prompt tokens) takes its one chunk in step 1; B
    (20 tokens), submitted after step 4, takes chunks in steps 5, 6, 7;
    C (12 tokens), submitted after step 8, in steps 9 and 10.  A token
    counts when a chunk ran between it and the request's previous token:
    A's tokens of steps 5, 6, 7, 9 and 10, B's of 9 and 10.  A request's
    second token follows its first, which its own last chunk made, so
    it never counts.  Pipelined, the chunk's blocking fetch comes before
    the oldest in-flight step is applied: the same tokens count."""
    eng, runner = stub_engine(max_slots=3, num_blocks=64, prefill_chunk=8,
                              pipeline_depth=depth)
    a = eng.submit([1, 2, 3, 4, 5], 20)
    for _ in range(4):
        eng.step()
    b = eng.submit(list(range(1, 21)), 6)
    for _ in range(4):
        eng.step()
    c = eng.submit(list(range(30, 42)), 4)
    eng.run()
    assert runner.chunk_calls == 6
    assert a.state is b.state is c.state is RequestState.DONE
    assert [len(r.output) for r in (a, b, c)] == [20, 6, 4]
    assert [r.chunk_step_tokens for r in (a, b, c)] == [5, 2, 0]


# ---------------------------------------------------------------------------
# spans on the profiler's host plane, and scopes in the step programs
# ---------------------------------------------------------------------------

def _dense_cfg():
    return ModelConfig(
        name="tracing-test", family="dense", n_layers=2, d_model=16,
        n_heads=2, n_kv_heads=1, d_ff=32, vocab_size=64,
        layer_specs={"full": LayerSpec(mixer="gqa", mlp="swiglu")},
        pattern_unit=("full",), tie_embeddings=False, dtype="float32")


def _engine(cfg, **kw):
    params = steps_lib.model_fns(cfg)["init"](jax.random.PRNGKey(0), cfg)
    return Engine(cfg, params, max_slots=3, max_seq_len=32, block_size=8,
                  num_blocks=24, prefill_chunk=8, **kw)


def _bench_spans():
    path = ROOT / "bench" / "trace.py"
    spec = importlib.util.spec_from_file_location("bench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SPANS


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events named in ``engine.SPANS`` from a profile taken around
    a warm engine serving three requests: [(name, start, end, stats)]."""
    eng = _engine(_dense_cfg())
    prompts = [list(range(1, 4)), list(range(1, 20)), list(range(5, 15))]
    # compile outside the profile, on prompts the prefix cache cannot
    # serve to the traced ones
    eng.generate([[t + 40 for t in p] for p in prompts], max_new_tokens=4)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        reqs = [eng.submit(p, 4) for p in prompts]
        eng.run()
    pb = sorted(out.rglob("*.xplane.pb"))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in engine_mod.SPANS:
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    return events, reqs


def test_phase_spans_nest_inside_serve_step(traced):
    events, _ = traced
    assert {e[0] for e in events} == set(engine_mod.SPANS)
    steps = [(a, b) for n, a, b, _ in events if n == "serve.step"]
    for name, a, b, _ in events:
        if name != "serve.step":
            assert any(s <= a and b <= e for s, e in steps), name


def test_span_names_are_not_the_benchmark_spans():
    assert not set(engine_mod.SPANS) & set(_bench_spans())


def test_step_t_argument_maps_marks_onto_the_trace_clock(traced):
    events, _ = traced
    offsets = [st["t"] - a for n, a, _, st in events if n == "serve.step"]
    assert len(offsets) >= 5
    assert max(offsets) - min(offsets) < 200_000     # ns


def test_spans_of_one_request_share_its_rid(traced):
    events, reqs = traced
    long = reqs[1]                       # 19 prompt tokens: three chunks
    carried = {n for n, _, _, st in events
               if str(long.rid) in str(st.get("rids", "")).split()}
    assert {"serve.admit", "serve.prefill", "serve.decode"} <= carried
    prefills = [st for n, _, _, st in events if n == "serve.prefill"
                and str(long.rid) in str(st["rids"]).split()]
    assert len(prefills) == 3
    assert all(st["padded"] == 8 * st["rows"] for st in prefills)


def _program_text(eng, kind):
    """The lowered decode or chunk program, with op locations."""
    r = eng.runner
    S = r.max_slots
    z = np.zeros((S,), np.int32)
    off = np.zeros((S,), bool)
    if kind == "decode":
        args = (r.params, r.cache, z, z, off, r._masked_table(off),
                np.zeros((S,), np.uint32), z, np.zeros((S,), np.float32),
                z, np.ones((S,), np.float32), np.full((S,), -1, np.int32),
                z)
        lowered = r._decode.lower(*args)
    else:
        C = r.prefill_chunk
        one = np.zeros((1,), np.int32)
        args = (r.params, r.cache, np.ones((1, C), np.int32), one,
                r.kv.table_rows([0]), one, one + C - 1,
                np.zeros((1,), np.uint32), one, np.zeros((1,), np.float32),
                one, np.ones((1,), np.float32))
        lowered = r._chunk.lower(*args)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_step_programs_carry_the_scope_names(kind):
    dense = _program_text(_engine(_dense_cfg()), kind)
    pt = _program_text(_engine(reduced_config("pt-30b-d8")), kind)
    for text, names in ((dense, SCOPES), (pt, SCOPES + ("track_fuse",))):
        paths = re.findall(r'loc\("([^"]*)"', text)
        for name in names:
            # PT layers run under the track vmap: "vmap(attn)/..."
            hit = re.compile(rf"(^|/)({name}|vmap\({name}\))/")
            assert any(hit.search(p) for p in paths), name
