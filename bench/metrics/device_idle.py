"""Device: share of the time inside ``Engine.step()`` spans of the trace
in which no operation ran on the device."""


def read(run):
    s = (run.trace or {}).get("step_idle_share")
    return None if s is None else 100.0 * s
