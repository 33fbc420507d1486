"""Engine step: share of the window's inter-token gaps in which a chunk
(or prefill) program ran, so the token waited behind it: the sum of
``Request.chunk_step_tokens`` over the sum of each request's output
tokens after its first.  Nothing to read from a program without the
counter."""


def read(run):
    reqs = [r.req for r in run.win.recs]
    if not all(hasattr(q, "chunk_step_tokens") for q in reqs):
        return None
    gaps = sum(max(0, len(q.output) - 1) for q in reqs)
    if not gaps:
        return None
    return 100.0 * sum(q.chunk_step_tokens for q in reqs) / gaps
