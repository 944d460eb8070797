"""Roofline share of the latent (MLA) paged decode kernel in the traced
window: the least time its calls needed (per call the larger of
operations over the bf16 peak and bytes over the HBM bandwidth, from
``flops_mla.latent_attention_cost``: whole latent pages, the query and
the output) over the kernel's device time in the trace.  The kernel is
the Pallas (``tpu_custom_call``) operation inside the decode program,
the only one there for a latent model; it runs once a layer.  Decode
attention is bound by memory; the log says which bound held."""
import sys

from benchmarks.chip import flops, flops_mla, trace

PROGRAM = "jit__step_tables_fn"


def is_kernel(short: str, text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def read(obs):
    tr, calls = obs.get("trace"), obs.get("decode_calls")
    cfg = obs["config"]
    if tr is None or not calls or "kv_lora_rank" not in cfg:
        return None
    lo, hi = tr.window()
    dev = obs["trace_devices"][0]
    t = trace.ops_in_module(tr, dev, lo, hi, PROGRAM, is_kernel)
    if t <= 0:
        return None
    layers = cfg["num_hidden_layers"]
    least, bounds = 0.0, set()
    for rows in calls:
        c = flops_mla.latent_attention_cost(cfg, rows, obs["block_size"])
        r = flops.roofline_seconds(c["flops"], c["bytes"], obs["peaks"])
        least += layers * r["seconds"]
        bounds.add(r["bound"])
    print(f"[bench] latent attention: {len(calls)} decode steps x {layers} "
          f"calls, least {least:.6f} s ({'/'.join(sorted(bounds))} bound),"
          f" kernel {t:.6f} s", file=sys.stderr)
    return 100.0 * least / t
