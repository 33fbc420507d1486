"""Work counts against hand arithmetic for both configurations."""
import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name, **over):
    m = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    m.update(over)
    return m


def test_pt_6b_d4_parameters_and_kv():
    m = model("pt-6b-d4")
    # per track and layer: q 1408x512, k and v 1408x128, o 512x1408,
    # gate and up 1408x3968, down 3968x1408
    layer = 1408 * 512 * 2 + 1408 * 128 * 2 + 3 * 1408 * 3968
    assert work.layer_matmul_params(m) == layer == 18_563_072
    assert work.matmul_params(m) == 32 * 8 * layer + 1408 * 100352
    total = work.total_params(m)
    assert total == 32 * 8 * (layer + 2 * 1408) + 1408 + 2 * 1408 * 100352
    assert round(total / 1e9, 3) == 5.035
    assert work.kv_bytes_per_token(m) == 128 * 1024


def test_dense_6b_parameters_and_kv():
    m = model("dense-6b")
    assert m["n_layers"] == 32      # as benchmarked: every layer of Table 1
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 11008
    assert work.layer_matmul_params(m) == layer
    total = work.total_params(m)
    assert total == 32 * (layer + 2 * 4096) + 4096 + 2 * 4096 * 100352
    assert round(total / 1e9, 3) == 6.493
    assert work.kv_bytes_per_token(m) == 128 * 1024


def test_decode_call_counts():
    m = model("pt-6b-d4")
    pos = [100, 2000]
    w = work.decode_call(m, pos)
    mm = work.matmul_params(m)
    attn = 4 * 32 * 8 * 4 * 128 * (101 + 2001)
    assert w["flops"] == pytest.approx(2 * mm * 2 + attn)
    weights = mm * 2 + 32 * 8 * 2 * 1408 * 4 + 1408 * 4
    kv = 128 * 1024 * (100 + 2000) + 128 * 1024 * 2
    assert w["bytes"] == pytest.approx(weights + 2 * 1408 * 2 + kv)
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # two rows read every weight once: memory bound
    assert work.least_time(w["flops"], w["bytes"], peaks) == pytest.approx(
        w["bytes"] / 819e9)


def test_span_positions():
    # tokens at 10, 11, 12 attend to 11 + 12 + 13 positions
    assert work.span_positions(10, 3) == 36
