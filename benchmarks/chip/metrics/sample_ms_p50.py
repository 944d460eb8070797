"""Median duration of the program's ``sample`` spans that open in the
traced serving window: the sampler's host time per step, whether or not
the device is busy meanwhile."""
from benchmarks.chip import harness, spans


def read(obs):
    tr = spans.readable(obs)
    times = spans.in_window(tr, "sample") if tr is not None else []
    if not times:
        return None
    return 1e3 * harness.percentile(times, 50)
