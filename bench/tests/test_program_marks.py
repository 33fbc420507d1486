"""The readers of the engine's own request marks and counters:
``admit_wait_p50_ms``, ``prefill_p50_ms`` and ``chunk_step_gap_share``.

On hand-made requests they give the numbers worked out here; on
requests of a program without the marks they give nothing (and do not
raise); and on a whole run of the tiny configuration on the CPU the two
waits split each request's TTFT with the client's submission lateness,
to well under a millisecond, and the counter finds the gaps the client
saw a chunk call start in."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run, spec, window

DATA = Path(__file__).parent / "data"
SEED = 2 ** 31 + 4099
NAMES = ("admit_wait_p50_ms", "prefill_p50_ms", "chunk_step_gap_share")


def _run(reqs, t_close=10.0):
    win = SimpleNamespace(t_close=t_close,
                          recs=[SimpleNamespace(req=q) for q in reqs])
    return SimpleNamespace(win=win)


def _req(t_submit, t_admitted=0.0, t_first=0.0, out=0, chunk_steps=0):
    return SimpleNamespace(t_submit=t_submit, t_admitted=t_admitted,
                           t_first=t_first, output=[7] * out,
                           chunk_step_tokens=chunk_steps)


def test_admission_wait_counts_a_queued_request_at_the_close():
    reqs = [_req(1.0, 1.010, 1.2, 3), _req(2.0, 2.030, 2.3, 2),
            _req(9.0)]                  # still queued: 10.0 - 9.0
    read = spec.reader("admit_wait_p50_ms")
    assert read(_run(reqs)) == pytest.approx(30.0)


def test_prefill_wait_counts_an_unfinished_request_at_the_close():
    reqs = [_req(1.0, 1.010, 1.2, 3),  # 190 ms
            _req(2.0, 2.030, 2.130, 2),  # 100 ms
            _req(9.0, 9.500),           # admitted, no first token: 500 ms
            _req(9.9)]                  # never admitted: left out
    read = spec.reader("prefill_p50_ms")
    assert read(_run(reqs)) == pytest.approx(190.0)


def test_chunk_step_share_over_the_gaps_after_each_first_token():
    reqs = [_req(1.0, 1.0, 1.1, out=11, chunk_steps=2),
            _req(2.0, 2.0, 2.1, out=1),
            _req(3.0, 3.0, 3.1, out=6, chunk_steps=1),
            _req(4.0)]
    read = spec.reader("chunk_step_gap_share")
    assert read(_run(reqs)) == pytest.approx(100.0 * 3 / 15)
    assert read(_run([_req(1.0, 1.0, 1.1, out=1)])) is None


def test_a_program_without_the_marks_reads_nothing():
    old = SimpleNamespace(t_submit=1.0, t_first=1.2, output=[1, 2])
    for name in NAMES:
        assert spec.reader(name)(_run([old])) is None
        assert spec.reader(name)(_run([])) is None


def test_the_marks_split_ttft_on_a_whole_run(monkeypatch):
    """submission lateness + admission wait + prefill = TTFT, per
    request, on a run of the tiny configuration (CPU, no chip check)."""
    monkeypatch.setattr(run, "require_chips", lambda jax, chips:
                        jax.devices()[0])
    v5e = spec.peaks("TPU v5 lite")
    monkeypatch.setattr(spec, "peaks", lambda kind: v5e)
    wins = []
    drive = window.drive
    monkeypatch.setattr(window, "drive",
                        lambda *a, **k: wins.append(drive(*a, **k))
                        or wins[-1])
    conf = json.loads((DATA / "tiny-pt.json").read_text())
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    metrics = [{"name": n, "unit": "ms"} for n in NAMES]
    result = run.execute("tiny", conf, mix, chips=1, seed=SEED, seconds=2.0,
                         trace=False, metrics=metrics)
    assert result["correct"]
    assert set(result["metrics"]) == set(NAMES)
    assert 0.0 <= result["metrics"]["chunk_step_gap_share"]["value"] <= 100
    (win,) = wins
    served = [r for r in win.recs if r.stamps]
    assert served
    for r in served:
        q = r.req
        split = (r.submitted - r.due) + (q.t_admitted - q.t_submit) \
            + (q.t_first - q.t_admitted)
        assert abs(split - (r.stamps[0] - r.due)) < 1e-3
    # the counter agrees with the client's view: gaps between two
    # stamps of a request in which a chunk call started
    starts = sorted(c["t0"] for c in win.chunks)
    stalled = sum(1 for r in win.recs for a, b in zip(r.stamps, r.stamps[1:])
                  if any(a < t < b for t in starts))
    assert stalled > 0
    assert sum(r.req.chunk_step_tokens for r in win.recs) == stalled
