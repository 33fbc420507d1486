"""Scheduler: median over the window's requests of the engine's own
admission wait, ``Request.t_admitted - t_submit`` (the request's first
slot, marked in ``Engine._admit``); one still queued at the close counts
at its wait.  Nothing to read from a program without the mark."""
import numpy as np


def read(run):
    reqs = [r.req for r in run.win.recs]
    if not reqs or not all(hasattr(q, "t_admitted") for q in reqs):
        return None
    waits = [(q.t_admitted or run.win.t_close) - q.t_submit for q in reqs]
    return float(np.percentile(waits, 50) * 1e3)
