"""How unevenly the routed tokens of the window fell on the experts: in
each MoE layer the busiest expert's routed tokens over the mean over
the layer's experts, averaged over the MoE layers.  From the program's
per-expert counters (``moe_stats``, counted on the device by every
decode step and prefill chunk, zeroed when the window opens and read
once when it closes).  1 is even; with dropless serving nothing is
dropped, but a routed batch runs as long as its busiest expert."""


def read(obs):
    counts = obs.get("expert_tokens")
    if not counts:
        return None
    ratios = []
    for layer in counts:
        total = sum(layer)
        if total > 0:
            ratios.append(max(layer) * len(layer) / total)
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
