"""80th percentile of how late the load generator submitted a request
after its due time, over the requests due in the window (it shares its
thread with the scheduler loop)."""
from benchmarks.chip import harness


def read(obs):
    lags = obs.get("gen_lags_s")
    if not lags:
        return None
    return 1e3 * harness.percentile(lags, 80)
