"""Find the highest arrival rate a configuration sustains under an
open-loop mix: one process, one engine, the mix served at each rate in
turn for ``--seconds``.  What is still running after a rate's window is
cancelled before the next.

  python bench/tools/sweep.py --config pt-6b-d4 --traffic <mix> \
      --rates 0.5,0.6,0.7 --seconds 100 --seed 7

Prints one JSON line per rate: offered and completed requests per
second, TTFT p50/p90, inter-token p95, queue waits, and what was left
queued or running at the close.  The queue wait is judged by its tail,
not its median: a rate is ``sustained`` when at most one request is
left queued at the close and the p90 queue wait of the later half of
the window's requests (by due time) is at most twice that of the
earlier half.  Above the knee the queue grows through the window, so
the later half waits longer and the close leaves a queue.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run as bench_run  # noqa: E402  (sets the compile cache)
from bench import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench_run.require_chips(jax, 1)
    from bench import program, traffic, window

    conf = spec.config(args.config)
    mix = spec.traffic(args.traffic)
    params, eng, ran, _ = bench_run.prepare(conf, args.seed)
    bench_run.log(f"set-up done: {', '.join(ran)}")
    rec = window.Recorder(eng.runner, None)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        items = traffic.open_loop(dict(mix, rate_rps=rate), args.seconds,
                                  args.seed + i, conf["model"]["vocab_size"])
        win = window.drive(eng, rec, seconds=args.seconds, items=items,
                           params=program.GREEDY)
        span = win.t_close - win.t0
        done = sum(1 for r in win.recs if r.req.finished)
        queued = len(eng.scheduler.queue)
        running = len(eng.scheduler.active_slots())
        tt, g, q = window.ttfts(win), window.gaps(win), window.queue_waits(win)
        half = len(q) // 2
        p90_early = float(np.percentile(q[:half], 90) * 1e3)
        p90_late = float(np.percentile(q[half:], 90) * 1e3)
        toks = sum(len(r.stamps) for r in win.recs)
        print(json.dumps({
            "rate_rps": rate, "offered_rps": len(win.recs) / span,
            "completed_rps": done / span, "out_tok_s": toks / span,
            "ttft_p50_ms": float(np.percentile(tt, 50) * 1e3),
            "ttft_p90_ms": float(np.percentile(tt, 90) * 1e3),
            "itl_p50_ms": float(np.percentile(g, 50) * 1e3),
            "itl_p95_ms": float(np.percentile(g, 95) * 1e3),
            "queue_wait_p50_ms": float(np.percentile(q, 50) * 1e3),
            "queue_wait_p90_ms": float(np.percentile(q, 90) * 1e3),
            # a queue that grows through the window: later requests wait
            # longer than earlier ones
            "queue_wait_p90_first_half_ms": p90_early,
            "queue_wait_p90_second_half_ms": p90_late,
            "left_queued": queued, "left_running": running,
            "sustained": queued <= 1 and p90_late <= 2 * p90_early,
            "decode_rows_mean": float(np.mean([len(c["pos"])
                                               for c in win.decodes])),
            "steps": len(win.steps)}), flush=True)
        for r in win.recs:
            eng.cancel(r.req)
        win.decodes.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
