"""Share of the traced part of the serving window in which no
operation ran on a chip, averaged over the cell's chips (profiler
trace)."""
from benchmarks.chip import trace


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    return trace.idle_percent(tr, obs["trace_devices"])
