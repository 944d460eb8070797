"""Operations and bytes the benchmark credits, computed from shapes.

These counts are the yardstick for ``mfu.*`` and ``*_roofline``: they
depend only on the configuration's sizes and the traffic, never on how
the program compiles the work, so a change that restructures the
program cannot change what it is credited with.  Matrix products count
2 operations per multiply-add; elementwise work (activations, Adam,
softmax) is not counted.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Iterable, Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# ICF CycleGAN train step (one discriminator + one generator update)
# ---------------------------------------------------------------------------


def _macs(dims: Sequence[int]) -> int:
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def icf_stacks(cfg: dict) -> Dict[str, tuple]:
    """Layer widths of the five fully connected stacks."""
    out = cfg["num_scalars"] + cfg["num_images"] * cfg["image_size"] ** 2
    x, z = cfg["input_dim"], cfg["latent_dim"]
    return {"fwd": (x, *cfg["fwd_hidden"], z),
            "inv": (z, *cfg["inv_hidden"], x),
            "enc": (out, *cfg["enc_hidden"], z),
            "dec": (z, *cfg["dec_hidden"], out),
            "disc": (z, *cfg["disc_hidden"], 1)}


def icf_step_flops(cfg: dict, batch: int) -> float:
    """Matrix-product operations one CycleGAN train step needs.

    Discriminator update: F(x) and E(y) forward (held constant), D
    forward on both latents and its weight gradients.  Generator update:
    F, E, both decoder passes (y_hat = Dec(F(x)), y_rec = Dec(E(y))) and
    G forward and backward, and D forward plus the gradient with respect
    to its input.  E(y) with the same generator weights is needed once
    (both updates read it), so it is counted once.  A backward pass costs
    two forward passes (weight and input gradients), less the input
    gradient of a first layer whose input needs none.
    """
    s = {k: _macs(v) for k, v in icf_stacks(cfg).items()}
    first = {k: v[0] * v[1] for k, v in icf_stacks(cfg).items()}
    fwd = lambda k: s[k]                                   # noqa: E731
    bwd_w = lambda k: 2 * s[k] - first[k]                  # noqa: E731
    bwd_in = lambda k: 2 * s[k]                            # noqa: E731
    macs = (fwd("fwd") + fwd("enc")                        # disc update
            + 2 * fwd("disc") + 2 * bwd_w("disc")
            + bwd_w("fwd") + bwd_w("enc")                  # gen update
            + 2 * (fwd("dec") + bwd_in("dec"))
            + fwd("inv") + bwd_in("inv")
            + fwd("disc") + s["disc"])                     # D input grads
    return 2.0 * batch * macs


def icf_param_count(cfg: dict) -> int:
    return sum(_macs(v) + sum(v[1:]) for v in icf_stacks(cfg).values())


# ---------------------------------------------------------------------------
# qwen3-style decoder: decode step and the paged-attention kernel
# ---------------------------------------------------------------------------


def lm_shapes(cfg: dict) -> Dict[str, int]:
    """The sizes the counts need, from a Hugging Face style config."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    Hkv = cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or d // H
    return {"d": d, "H": H, "Hkv": Hkv, "D": D,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """Operations one decode step needs for rows that attend over
    ``lengths`` tokens each (the new token included): the projections
    and MLP of every layer, attention over the row's context, and the
    logits over the whole vocabulary."""
    s = lm_shapes(cfg)
    d, H, Hkv, D = s["d"], s["H"], s["Hkv"], s["D"]
    per_layer = d * (H * D + 2 * Hkv * D) + H * D * d + 3 * d * s["ff"]
    per_token = 2 * (s["layers"] * per_layer + d * s["V"])
    total = 0.0
    for L in lengths:
        total += per_token + s["layers"] * 4 * H * D * L
    return total


def paged_attention_cost(cfg: dict, lengths: Iterable[int],
                         block_size: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> Dict[str, float]:
    """Operations and bytes one paged-attention call (one layer) needs
    for rows attending over ``lengths`` tokens: QK^T and PV over the
    context, the live K/V pages (whole pages: the DMA unit), the query
    and the output.  Idle rows (length 0) need nothing."""
    s = lm_shapes(cfg)
    H, Hkv, D = s["H"], s["Hkv"], s["D"]
    flops = 0.0
    nbytes = 0.0
    for L in lengths:
        if L <= 0:
            continue
        flops += 4.0 * H * D * L
        pages = math.ceil(L / block_size)
        nbytes += 2.0 * pages * block_size * Hkv * D * kv_bytes
        nbytes += 2.0 * H * D * q_bytes
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Dict[str, float]:
    """Least time on the chip: the larger of the compute and the memory
    bound, and which one it is."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "compute_s": t_c, "memory_s": t_m,
            "bound": "compute" if t_c >= t_m else "memory"}
