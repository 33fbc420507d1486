"""The measured window: requests submitted when due, ``Engine.step()``
driven by the engine's own synchronous loop, and the host-clock marks
and counts every metric reads.

Spans (``jax.profiler.TraceAnnotation``, only in a traced run) go around
each ``Engine.step()`` and, from here, around the runner's ``chunk``,
``prefill``, ``warm_prefill``, ``dispatch_decode`` and ``wait_decode``
of this one engine instance: the program's code is not touched.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import traffic as traffic_lib


@dataclass
class Rec:
    """One request, as the client saw it."""
    due: float                    # host clock
    prompt_len: int
    max_new: int
    req: Any = None               # the engine's Request
    submitted: float = 0.0
    left_queue: Optional[float] = None
    stamps: List[float] = field(default_factory=list)


@dataclass
class Window:
    t0: float
    t_end: float = 0.0
    t_close: float = 0.0
    recs: List[Rec] = field(default_factory=list)
    steps: List[tuple] = field(default_factory=list)          # (t0, t1)
    chunks: List[Dict[str, Any]] = field(default_factory=list)
    decodes: List[Dict[str, Any]] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)
    trace_span: Optional[tuple] = None                        # host clock


class Recorder:
    """Wraps one runner instance's host-facing calls to count the work
    each carries and, when ``spans`` is set, to mark them in a trace."""

    def __init__(self, runner, win: Window):
        import jax
        self.spans = False
        self._annotate = jax.profiler.TraceAnnotation
        self.win = win
        self._wrap(runner, "prefill")
        self._wrap(runner, "warm_prefill")
        self._wrap(runner, "wait_decode")
        self._wrap(runner, "chunk", self._on_chunk)
        self._wrap(runner, "dispatch_decode", self._on_decode)

    def span(self, name: str):
        return self._annotate(name) if self.spans \
            else contextlib.nullcontext()

    def _wrap(self, runner, name: str, note: Optional[Callable] = None):
        orig = getattr(runner, name)

        def wrapped(*args, **kwargs):
            t = time.perf_counter()
            with self.span(f"runner.{name}"):
                out = orig(*args, **kwargs)
            if note is not None:
                note(t, time.perf_counter(), *args)
            return out

        setattr(runner, name, wrapped)

    def _on_chunk(self, t0, t1, toks, pos, slots, last_idx, *rest):
        count = np.asarray(last_idx, np.int64) + 1
        self.win.chunks.append({"t0": t0, "t1": t1, "rows": len(count),
                                "padded": int(np.asarray(toks).size),
                                "start": np.asarray(pos, np.int64),
                                "count": count})

    def _on_decode(self, t0, t1, toks, pos, active, *rest):
        act = np.asarray(active, bool)
        self.win.decodes.append({"t0": t0, "t1": t1,
                                 "pos": np.asarray(pos, np.int64)[act]})


def drive(eng, rec: Recorder, *, seconds: float,
          items: List[traffic_lib.Item], params=None,
          trace_at: Optional[float] = None,
          trace_dir: Optional[str] = None) -> Window:
    """Run the window: ``items`` are submitted once due.  A traced run
    profiles from ``trace_at`` seconds into the window to its close."""
    import jax
    from repro.serving.engine import RequestState
    queued = RequestState.QUEUED
    t0 = time.perf_counter()
    win = Window(t0=t0, t_end=t0 + seconds)
    rec.win = win
    pending = deque(sorted(items, key=lambda i: i.due))
    waiting: List[Rec] = []
    tracing = False

    def submit(item, due):
        r = Rec(due=due, prompt_len=len(item.prompt), max_new=item.max_new)
        stamps = r.stamps
        r.req = eng.submit(item.prompt, item.max_new, params=params,
                           on_token=lambda _req, _tok: stamps.append(
                               time.perf_counter()))
        r.submitted = time.perf_counter()
        win.lateness.append(r.submitted - due)
        win.recs.append(r)
        waiting.append(r)

    while True:
        now = time.perf_counter()
        while pending and t0 + pending[0].due <= min(now, win.t_end):
            item = pending.popleft()
            submit(item, t0 + item.due)
        if now >= win.t_end:
            break
        if trace_at is not None and not tracing and now >= t0 + trace_at:
            jax.profiler.start_trace(trace_dir)
            rec.spans, tracing = True, True
            win.trace_span = (time.perf_counter(), None)
        if not eng.scheduler.has_work():
            nxt = t0 + pending[0].due if pending else win.t_end
            time.sleep(max(0.0, min(nxt, win.t_end) - time.perf_counter()))
            continue
        ts = time.perf_counter()
        with rec.span("engine.step"):
            eng.step()
        te = time.perf_counter()
        win.steps.append((ts, te))
        still = []
        for r in waiting:
            if r.req.state is queued:
                still.append(r)
            elif r.left_queue is None:
                r.left_queue = te
        waiting[:] = still
    win.t_close = time.perf_counter()
    if tracing:
        rec.spans = False
        win.trace_span = (win.trace_span[0], win.t_close)
        jax.profiler.stop_trace()
    return win


def ttfts(win: Window) -> List[float]:
    """First token minus due time, for every request due in the window;
    one with no first token enters at the wait it has had by the close."""
    return [(r.stamps[0] if r.stamps else win.t_close) - r.due
            for r in win.recs]


def gaps(win: Window) -> List[float]:
    """Every gap between consecutive output tokens in the window, and for
    a request still decoding at the close, the gap it is waiting in."""
    out: List[float] = []
    for r in win.recs:
        s = [t for t in r.stamps if t <= win.t_close]
        out.extend(np.diff(s).tolist())
        if s and r.req is not None and not r.req.finished:
            out.append(win.t_close - s[-1])
    return out


def queue_waits(win: Window) -> List[float]:
    return [(r.left_queue if r.left_queue is not None else win.t_close)
            - r.due for r in win.recs]
