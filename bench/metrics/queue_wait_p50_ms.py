"""Scheduler: median over the window's requests of the time from due to
the end of the engine step in which the request left the queue (its
state no longer QUEUED); one still queued at the close counts at its
wait."""
import numpy as np

from bench import window


def read(run):
    q = window.queue_waits(run.win)
    return float(np.percentile(q, 50) * 1e3) if q else None
