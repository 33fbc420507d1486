"""Median over every request due in the window of its first token's
arrival minus the time it was due; a request with no first token by the
close counts at the wait it has had."""
import numpy as np

from bench import window


def read(run):
    t = window.ttfts(run.win)
    return float(np.percentile(t, 50) * 1e3) if t else None
