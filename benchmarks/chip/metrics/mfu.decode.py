"""Model FLOP/s utilisation of the decode steps in the traced part of
the window: the operations those steps needed (``flops.decode_flops``
over the rows that held a request, at their context lengths) over the
device time of the decode program in the trace and the chip's bf16
peak."""
from benchmarks.chip import flops, trace

PROGRAM = "jit__step_tables_fn"


def read(obs):
    tr, calls = obs.get("trace"), obs.get("decode_calls")
    if tr is None or not calls:
        return None
    lo, hi = tr.window()
    dev = obs["trace_devices"][0]
    t = sum(trace.module_seconds(tr, dev, lo, hi,
                                 lambda m: m == PROGRAM).values())
    if t <= 0:
        return None
    need = sum(flops.decode_flops(obs["config"], rows) for rows in calls)
    return 100.0 * need / (t * obs["peaks"]["bf16_flops_per_s"])
