"""Kernels: the least time the traced decode calls need at the chip's
peaks (weights read once, the active rows' live KV read and new KV
written; or their FLOPs, whichever bounds), over the device time of the
decode program.  No Pallas kernel is on this path: the decode program is
the kernel."""
from bench import work


def read(run):
    t = (run.trace or {}).get("modules", {}).get("_decode_impl")
    calls = run.traced(run.win.decodes)
    if not t or len(t) != len(calls):
        return None
    need = 0.0
    for c in calls:
        w = work.decode_call(run.model, c["pos"])
        need += work.least_time(w["flops"], w["bytes"], run.peaks)
    return 100.0 * need / sum(t)
