"""Share of the traced serving window in which chip 0 sat idle while
the host copied a step's logits off it: its idle time inside the
program's ``logits_fetch`` spans (JAX's own ``np.asarray`` annotation
within them takes none of it), over the window."""
from benchmarks.chip import spans


def read(obs):
    return spans.idle_share(obs, "logits_fetch")
