"""Share of the traced serving window in which chip 0 sat idle while
the scheduler sampled the step's next tokens on the host: its idle time
inside the self time of the program's ``sample`` spans, over the
window."""
from benchmarks.chip import spans


def read(obs):
    return spans.idle_share(obs, "sample")
