"""Model step: device time of the chunked-prefill program
(``_chunk_impl``) in the trace, per thousand prompt tokens its calls
carried (padding not counted)."""


def read(run):
    t = (run.trace or {}).get("modules", {}).get("_chunk_impl")
    tokens = sum(int(c["count"].sum()) for c in run.traced(run.win.chunks))
    if not t or not tokens:
        return None
    return sum(t) * 1e3 / (tokens / 1e3)
