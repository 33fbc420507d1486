"""p95 of every gap between consecutive output tokens of every request in
the window, with the open gap of each request still decoding at the
close."""
import numpy as np

from bench import window


def read(run):
    g = window.gaps(run.win)
    return float(np.percentile(g, 95) * 1e3) if g else None
