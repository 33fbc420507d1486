"""Whole engine step: the model FLOPs the window's steps did for real
tokens (prompt tokens in chunks and decoded tokens, each 2 x matmul
weights plus attention over its live context) over the summed wall time
of ``Engine.step()`` times the chip's peak FLOP/s."""
import numpy as np

from bench import work


def read(run):
    w = run.win
    steps = sum(b - a for a, b in w.steps)
    if not steps:
        return None
    m = run.model
    flops = 0.0
    for c in w.chunks:
        flops += work.tokens_flops(
            m, c["count"].sum(),
            work.span_positions(c["start"], c["count"]).sum())
    for c in w.decodes:
        flops += work.tokens_flops(m, len(c["pos"]), np.sum(c["pos"] + 1))
    return 100.0 * flops / (steps * run.peaks["flops_bf16"])
