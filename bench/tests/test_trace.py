"""The trace reducer against numbers worked out by hand.

``data/trace_v5e_probe.json`` is a trace recorded on one TPU v5e: three
host steps, each a 2048^2 bf16 matmul program ``_chunk_impl`` then a
two-matmul ``_decode_impl``, with 2 ms and 3 ms host sleeps between
(``extract``'s format; the probe's step span is named ``engine.step``
here)."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def probe():
    return json.loads((DATA / "trace_v5e_probe.json").read_text())


def test_skew_is_the_earliest_device_start(probe):
    # second decode: device 54,208,638 ns, host dispatch 55,384,885 ns
    assert trace.skew_ns(probe) == 54208638 - 55384885


def test_window_busy_idle_and_modules(probe):
    r = trace.reduce(probe)
    # window: first step start 43,842,566 to last step end
    # 59,725,584 + 4,229,669 = 63,955,253
    assert r["window_s"] == pytest.approx((63955253 - 43842566) * 1e-9)
    # the 21 ops never overlap, and all lie inside a step once shifted
    ops = (14 + 3 + 90767) + (13 + 11619 + 90763) + (13 + 11657 + 90762) \
        + (13 + 11617 + 90847 + 90876) + (13 + 11450 + 90851 + 90873) \
        + (13 + 11659 + 90846 + 90871)
    assert ops == 875540
    assert r["busy_s"] == pytest.approx(ops * 1e-9)
    steps = 4826229 + 4147829 + 4229669
    assert r["step_s"] == pytest.approx(steps * 1e-9)
    assert r["step_idle_share"] == pytest.approx(1 - ops / steps)
    assert sum(r["modules"]["_chunk_impl"]) == pytest.approx(
        (90788 + 102401 + 102441) * 1e-9)
    assert sum(r["modules"]["_decode_impl"]) == pytest.approx(
        (193366 + 193198 + 193400) * 1e-9)
    idle = dict(r["idle_gaps"])
    # between steps nothing runs: (52,122,865 - 48,668,795)
    # + (59,725,584 - 56,270,694)
    assert idle[trace.OUTSIDE] == pytest.approx((3454070 + 3454890) * 1e-9)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_overlaps_nesting_and_two_chips():
    t = {"chips": 2,
         "host": [["engine.step", 0, 100], ["runner.chunk", 10, 40],
                  ["engine.step", 150, 50]],
         "calls": [["_chunk_impl", 10, 5]],
         "device": [["_chunk_impl", 12, 30]],
         "ops": [["a", 12, 20, 0], ["b", 20, 22, 0], ["c", 60, 10, 1],
                 ["d", 160, 20, 0], ["e", 500, 5, 0]]}
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(200e-9)
    # chip 0 busy [12, 42) and [160, 180); chip 1 [60, 70): mean 30
    assert r["busy_s"] == pytest.approx(30e-9)
    # steps 150 ns long, 60 ns busy summed over chips, over 2 chips
    assert r["step_idle_share"] == pytest.approx(1 - 30 / 150)
    idle = dict(r["idle_gaps"])
    # gaps of the union: [0,12) chunk 10-12, step 0-10; [42,60) chunk
    # 42-50, step 50-60; [70,100) step; [100,150) outside; [150,160) and
    # [180,200) step
    assert idle["runner.chunk"] == pytest.approx(10e-9)
    assert idle["engine.step"] == pytest.approx((10 + 10 + 30 + 10 + 20) * 1e-9)
    assert idle[trace.OUTSIDE] == pytest.approx(50e-9)
    assert [n for n, _ in r["device_ops"]][:2] == ["_chunk_impl:b",
                                                   "_chunk_impl:a"]
    assert dict(r["device_ops"])["?:d"] == pytest.approx(10e-9)
