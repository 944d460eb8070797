"""Share of the traced serving window in which chip 0 sat idle inside
the harness's ``serve.step`` spans but outside every program span: what
the program's own spans still miss.  The log gives the whole window's
idle time by span, the rest of the account."""
from benchmarks.chip import harness, spans


def read(obs):
    tr = spans.readable(obs)
    if tr is None:
        return None
    lo, hi = tr.window()
    window = (hi - lo) / 1e9
    idle = spans.idle_by_span(tr, obs["trace_devices"][0])
    harness.log("idle by span, % of the traced window: " + ", ".join(
        f"{k} {100.0 * v / window:.3f}"
        for k, v in sorted(idle.items(), key=lambda kv: -kv[1])))
    return 100.0 * idle.get(spans.UNTRACED, 0.0) / window
