"""Model step: median over the window's admitted requests of admission
to first token, ``Request.t_first - t_admitted`` (chunks of the prompt,
the steps they share); one admitted with no first token by the close
counts at the wait it has had.  Nothing to read from a program without
the admission mark."""
import numpy as np


def read(run):
    reqs = [r.req for r in run.win.recs]
    if not all(hasattr(q, "t_admitted") for q in reqs):
        return None
    waits = [(q.t_first or run.win.t_close) - q.t_admitted
             for q in reqs if q.t_admitted]
    return float(np.percentile(waits, 50) * 1e3) if waits else None
