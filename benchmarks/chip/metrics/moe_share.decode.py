"""Share of the decode program's device time spent in the MoE layers in
the traced window: the self time of the operations XLA recorded under
the program's ``moe`` scope (``jax.named_scope("moe")`` around the
router, the experts and the shared experts), read from the trace's op
metadata (``xplane_scopes``), over the device time of the decode
program (``jit__step_tables_fn``)."""
from benchmarks.chip import trace, xplane_scopes

PROGRAM = "jit__step_tables_fn"
SCOPE = "/moe/"


def read(obs):
    tr, path = obs.get("trace"), obs.get("trace_dir")
    if tr is None or not path:
        return None
    dev = obs["trace_devices"][0]
    meta = xplane_scopes.op_metadata(path, dev)
    if not meta:
        return None
    moe = {text for text, scope, _ in meta
           if scope.startswith(f"jit({PROGRAM[4:]})") and SCOPE in scope}
    if not moe:
        return None
    lo, hi = tr.window()
    total = sum(trace.module_seconds(tr, dev, lo, hi,
                                     lambda m: m == PROGRAM).values())
    if total <= 0:
        return None
    t = trace.ops_in_module(tr, dev, lo, hi, PROGRAM,
                            lambda short, text: text in moe)
    return 100.0 * t / total
