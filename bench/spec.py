"""The benchmark's data: ``BENCHMARK.json`` at the checkout root, and the
configuration, traffic and metric files it names.

Everything one configuration, traffic mix or metric needs lives in a file
of its own, found by its name:

  bench/configs/<config>.json   sizes and engine settings, as run
  bench/traffic/<traffic>.json  parameters for ``traffic.py``
  bench/metrics/<metric>.py     ``read(run) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "traffic" / f"{name}.json")


def metrics_for(cell: str, trace: bool) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (on): every entry that lists the cell, or lists no cells at all."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in benchmark()[key]
            if "workloads" not in m or cell in m["workloads"]]


def reader(metric: str) -> Callable[[Any], Optional[float]]:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, Any]:
    """Peak rates of one chip, from ``peaks.json``.  A kind missing from
    the table is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json; add its published peaks")
    return table[device_kind]
