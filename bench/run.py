"""Run one cell of the benchmark once.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration file and a traffic mix.  The run makes the weights from the
seed, builds the engine with the configuration's settings, runs every
program shape the window can reach once (set-up, ``setup_s``), serves the
mix for ``--seconds`` and then compares what the window served with the
plain reference (``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, from a profiled few seconds of
the window), ``device``, ``breakdown`` (traced runs) and ``checks``, each
number compared beside its limit.  Everything else goes to standard
error.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.

``--control 1`` runs the check's control: the reference computed from
float8 matmul inputs puts its first choice at every served position in
place of the served token, and the same comparison has to come out not
correct.  The benchmark's own runs never set it.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import spec  # noqa: E402

# JAX's persistent compilation cache stays inside the checkout unless the
# environment names one; the path is fixed, so every run finds it
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(spec.ROOT / ".jax_cache"))

# a traced run profiles the last seconds of its window, so that the
# profiler's collection, which stalls the host, falls after the close
TRACE_SHARE, TRACE_MAX_S = 0.3, 5.0
SAMPLE = 4          # requests compared with the reference per run


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
        raise SystemExit(3)
    return devs[0]


class Compiles:
    """Counts the programs JAX obtains (compiled or read from the
    persistent cache), and how many of them it read from the cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.times: List[float] = []
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == self.EVENT:
                self.times.append(time.perf_counter())

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def after(self, t: float) -> int:
        return sum(1 for x in self.times if x >= t)


def sample(recs, seed: int) -> list:
    """The longest finished request and others drawn from the seed."""
    import numpy as np
    if not recs:
        return []
    longest = max(recs, key=lambda r: r.prompt_len + len(r.req.output))
    rest = [r for r in recs if r is not longest]
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 2])
    pick = rng.choice(len(rest), size=min(SAMPLE - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def prepare(conf: Dict[str, Any], seed: int):
    """Weights from the seed, the engine with the configuration's
    settings, and every program of the window run once.  Returns the
    weights, the engine, what was run, and when each part ended."""
    import jax
    from bench import program, weights
    marks = {"jax": time.perf_counter()}
    cfg = program.program_config(conf)
    params = weights.fill(seed, conf["model"], program.param_shapes(cfg))
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    eng = program.build_engine(cfg, params, conf["engine"])
    marks["engine"] = time.perf_counter()
    ran = program.warm_up(eng)
    marks["warm-up"] = time.perf_counter()
    return params, eng, ran, marks


def execute(cell: str, conf: Dict[str, Any], mix: Dict[str, Any], *,
            chips: int, seed: int, seconds: float, trace: bool,
            metrics: List[Dict[str, Any]],
            control: bool = False) -> Dict[str, Any]:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = require_chips(jax, chips)
    peaks = spec.peaks(dev.device_kind)
    compiles = Compiles(jax)

    from bench import program, reference, traffic, window
    from repro.serving.engine import RequestState

    m, e = conf["model"], conf["engine"]
    if traffic.longest(mix) > e["max_seq_len"]:
        raise SystemExit(f"{cell}: the mix's longest request does not fit "
                         f"max_seq_len {e['max_seq_len']}")
    params, eng, ran, marks = prepare(conf, seed)
    rec = window.Recorder(eng.runner, None)
    if mix["kind"] != "open_loop":
        raise SystemExit(f"unknown traffic kind {mix['kind']!r}")
    items = traffic.open_loop(mix, seconds, seed, m["vocab_size"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    t_ready = time.perf_counter()
    setup_s = t_ready - PROCESS_START
    parts, last = [], PROCESS_START
    for name, t in marks.items():
        parts.append(f"{name} {t - last:.3f}")
        last = t
    log(f"set-up {setup_s:.3f} s ({', '.join(parts)} s): "
        f"{len(compiles.times)} programs obtained ({compiles.hits} from "
        f"the persistent cache); warmed {', '.join(ran)}")

    traced_s = min(TRACE_MAX_S, TRACE_SHARE * seconds)
    win = window.drive(eng, rec, seconds=seconds, params=program.GREEDY,
                       trace_at=seconds - traced_s if trace else None,
                       trace_dir=trace_dir, items=items)
    in_window = compiles.after(t_ready)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    late = sorted(win.lateness) or [0.0]
    log(f"window {win.t_close - win.t0:.3f} s: {len(win.recs)} requests "
        f"due, {len(win.steps)} steps, {len(win.chunks)} chunk calls, "
        f"{len(win.decodes)} decode calls; programs obtained inside the "
        f"window {in_window}; generator lateness p50 "
        f"{late[len(late) // 2] * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms")

    reduced = None
    if trace:
        from bench import trace as trace_lib
        paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if paths:
            reduced = trace_lib.reduce(trace_lib.extract(str(paths[-1])))
        shutil.rmtree(trace_dir, ignore_errors=True)

    a, b = win.trace_span or (0.0, 0.0)
    run = SimpleNamespace(
        win=win, model=m, slots=e["max_slots"], peaks=peaks,
        setup_s=setup_s, trace=reduced,
        traced=lambda calls: [c for c in calls if a <= c["t0"] < b])
    out_metrics = {}
    for spec_m in metrics:
        v = spec.reader(spec_m["name"])(run)
        if v is not None:
            out_metrics[spec_m["name"]] = {"value": v, "unit": spec_m["unit"]}

    done = [r for r in win.recs if r.req.state is RequestState.DONE]
    failed = sum(1 for r in win.recs if r.req.state in (
        RequestState.REJECTED, RequestState.TIMED_OUT,
        RequestState.CANCELLED))
    picked = sample(done, seed)
    prompts = [r.req.prompt for r in picked]
    outputs = [list(r.req.output) for r in picked]
    wrong_len = sum(1 for r in done if len(r.req.output) != r.max_new)
    # the program's state goes before the reference runs
    del eng, rec, params, run
    gc.collect()
    t_ref = time.perf_counter()
    gap = float("inf")
    if picked:
        read = reference.gaps(seed, m, prompts, outputs,
                              max_len=e["max_seq_len"],
                              max_new=mix["output"]["max"], control=control)
        gap = float(read["served"].max())
        if control:
            # the control: the float8 stream's first choice at every
            # position stands in for the served token
            log(f"control: the program's served tokens read {gap}")
            gap = float(read["control"].max())
    log(f"reference over {len(picked)} finished requests "
        f"({sum(map(len, outputs))} served tokens, longest "
        f"{max((len(p) + len(o) for p, o in zip(prompts, outputs)), default=0)}"
        f" positions) in {time.perf_counter() - t_ref:.3f} s")
    limit = conf["check"]["logit_gap_limit"]
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "wrong_length": {"value": wrong_len, "limit": 0},
        "compiles_in_window": {"value": in_window, "limit": 0},
    }
    correct = bool(picked) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    result = {"correct": correct, "attempted": len(win.recs),
              "failed": failed, "metrics": out_metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count(),
                         "memory_peak_bytes": int(peak)}}
    if reduced:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduced["device_ops"]],
            "idle_gaps": [list(x) for x in reduced["idle_gaps"]]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control's tokens in place of "
                         "the served ones (must come out not correct)")
    args = ap.parse_args(argv)
    cell = spec.workload(args.workload)
    result = execute(args.workload, spec.config(cell["config"]),
                     spec.traffic(cell["traffic"]), chips=cell["chips"],
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace),
                     metrics=spec.metrics_for(args.workload,
                                              bool(args.trace)),
                     control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
