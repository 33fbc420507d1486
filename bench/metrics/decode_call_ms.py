"""Model step: device time per call of the decode program
(``_decode_impl``), from the trace."""
import numpy as np


def read(run):
    t = (run.trace or {}).get("modules", {}).get("_decode_impl")
    return float(np.mean(t) * 1e3) if t else None
