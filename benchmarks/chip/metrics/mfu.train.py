"""Model FLOP/s utilisation of the training window: the operations the
benchmark credits to one CycleGAN step (``flops.icf_step_flops``, from
the configuration's shapes) times the steps of all trainers, over the
window's length, the cell's chips and the chip's bf16 peak (the f32
matrix products run as bf16 passes at the default precision)."""
from benchmarks.chip import flops


def read(obs):
    if not obs.get("steps") or not obs.get("window_s"):
        return None
    done = flops.icf_step_flops(obs["config"], obs["batch"]) * obs["steps"]
    peak = obs["peaks"]["bf16_flops_per_s"] * obs["chips"]
    return 100.0 * done / (obs["window_s"] * peak)
