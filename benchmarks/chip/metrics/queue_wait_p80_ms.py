"""80th percentile of the wait from a request's due time to the
``admit`` instant the scheduler records in its telemetry, over the
requests due in the window (the highest percentile with ten of the
window's ~51 requests beyond it)."""
from benchmarks.chip import harness


def read(obs):
    waits = obs.get("queue_waits_s")
    if not waits:
        return None
    return 1e3 * harness.percentile(waits, 80)
