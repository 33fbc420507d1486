"""How ``correct`` is decided, at a size a CPU test can hold: the whole
run (weights, engine, warm-up, window, reference) on a two-track
miniature of pt-6b-d4 with the chip check skipped.

- a sound run is correct;
- a run whose served tokens are altered where the engine receives them
  from the device is not;
- the float8 control, put in the program's place, is not correct: it
  reads above the limit that the program stays under.
"""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run, spec, weights

DATA = Path(__file__).parent / "data"
SEED = 2 ** 31 + 977


@pytest.fixture(scope="module")
def tiny():
    conf = json.loads((DATA / "tiny-pt.json").read_text())
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    return conf, mix


@pytest.fixture(autouse=True)
def no_chip_check(monkeypatch):
    """Skip the harness's look for a chip: run on the CPU, with the
    v5e's peaks standing in for the CPU's (no device number is read)."""
    monkeypatch.setattr(run, "require_chips", lambda jax, chips:
                        jax.devices()[0])
    v5e = spec.peaks("TPU v5 lite")
    monkeypatch.setattr(spec, "peaks", lambda kind: v5e)


def execute(conf, mix, seed=SEED, control=False):
    return run.execute("tiny", conf, mix, chips=1, seed=seed, seconds=2.0,
                       trace=False,
                       metrics=[{"name": "itl_p95_ms", "unit": "ms"}],
                       control=control)


def test_weights_are_one_function_of_the_seed(tiny):
    from bench import program
    conf, _ = tiny
    m = conf["model"]
    tree = weights.fill(SEED, m, program.param_shapes(
        program.program_config(conf)))
    key = weights.base_key(SEED)
    D = m["block_depth"]
    for role in ("mixer.wq", "mlp.wo", "ln2.scale"):
        leaf = tree["blocks"]
        for k in role.split("."):
            leaf = leaf[k]
        for layer in (0, m["n_layers"] - 1):
            want = weights.layer_slice(key, m, role, layer)
            got = leaf[layer // D, layer % D]
            assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(tree["head"]), np.asarray(
        weights.global_weight(key, m, "head")))


def test_sound_run_is_correct(tiny):
    res = execute(*tiny)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_altered_token_is_not_correct(tiny, monkeypatch):
    from repro.serving.engine import ModelRunner
    orig = ModelRunner.wait_decode
    calls = [0]

    def altered(self, handle):
        toks, done = orig(self, handle)
        calls[0] += 1
        if calls[0] % 5 == 0:        # every fifth step's tokens, all rows
            toks = (toks + 1) % tiny[0]["model"]["vocab_size"]
        return toks, done

    monkeypatch.setattr(ModelRunner, "wait_decode", altered)
    res = execute(*tiny)
    assert calls[0] >= 5
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] \
        > res["checks"]["logit_gap"]["limit"]


def test_float8_control_reads_above_the_limit(tiny):
    """The control (``run.py --control 1``): the float8 stream's first
    choice at each served position, judged by the run's own check, comes
    out not correct, at the limit that sound runs stay under."""
    conf, mix = tiny
    res = execute(conf, mix, control=True)
    gap = res["checks"]["logit_gap"]
    assert gap["limit"] == conf["check"]["logit_gap_limit"]
    assert gap["value"] > gap["limit"]
    assert not res["correct"]
