"""Architecture registry: ``--arch <id>`` resolution.

Each assigned architecture lives in its own module with FULL (exact
literature config) and SMOKE (reduced same-family config for CPU tests).
"""
from __future__ import annotations

from typing import Dict, Tuple

from repro.configs import (
    codeqwen15,
    deepseek_moe,
    deepseek_v2_lite,
    granite_8b,
    icf_cyclegan,
    jamba_15_large,
    musicgen_medium,
    phi35_moe,
    qwen2_vl,
    qwen25_3b,
    qwen3_06b,
    xlstm_125m,
)

_MODULES = (
    phi35_moe,
    deepseek_moe,
    codeqwen15,
    qwen3_06b,
    qwen25_3b,
    granite_8b,
    xlstm_125m,
    qwen2_vl,
    jamba_15_large,
    musicgen_medium,
)

ARCHS: Dict[str, object] = {m.ARCH_ID: m for m in _MODULES}
ARCHS[icf_cyclegan.ARCH_ID] = icf_cyclegan
ARCHS[deepseek_v2_lite.ARCH_ID] = deepseek_v2_lite

# The LM architecture families the model and serving tests sweep.
LM_ARCH_IDS: Tuple[str, ...] = tuple(m.ARCH_ID for m in _MODULES)


def get_config(arch_id: str, smoke: bool = False):
    if arch_id not in ARCHS:
        raise KeyError(
            f"unknown arch {arch_id!r}; known: {sorted(ARCHS)}")
    mod = ARCHS[arch_id]
    return mod.SMOKE if smoke else mod.FULL

