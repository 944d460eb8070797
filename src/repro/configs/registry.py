"""Architecture registry: ``--arch <id>`` resolution.

Each assigned architecture lives in its own module with FULL (exact
literature config) and SMOKE (reduced same-family config for CPU tests).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

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
from repro.configs.base import SHAPE_BY_NAME, SHAPES, ModelConfig, ShapeConfig

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
# served through the paged path only (latent attention has no dense-row
# cache), so it stays out of the dry-run grid below
ARCHS[deepseek_v2_lite.ARCH_ID] = deepseek_v2_lite

# LM architectures participating in the arch x shape dry-run grid.
LM_ARCH_IDS: Tuple[str, ...] = tuple(m.ARCH_ID for m in _MODULES)

# Families that support the long_500k sub-quadratic decode shape.
SUBQUADRATIC_FAMILIES = ("ssm", "hybrid")


def get_config(arch_id: str, smoke: bool = False):
    if arch_id not in ARCHS:
        raise KeyError(
            f"unknown arch {arch_id!r}; known: {sorted(ARCHS)}")
    mod = ARCHS[arch_id]
    return mod.SMOKE if smoke else mod.FULL


def shapes_for(cfg: ModelConfig) -> List[ShapeConfig]:
    """Applicable input shapes for an architecture (skips documented in
    DESIGN.md section 4): long_500k only for sub-quadratic families."""
    out = []
    for s in SHAPES:
        if s.name == "long_500k" and cfg.family not in SUBQUADRATIC_FAMILIES:
            continue
        out.append(s)
    return out


def dryrun_cells() -> List[Tuple[str, str]]:
    """All (arch_id, shape_name) dry-run cells."""
    cells = []
    for arch_id in LM_ARCH_IDS:
        cfg = get_config(arch_id)
        for s in shapes_for(cfg):
            cells.append((arch_id, s.name))
    return cells


def get_shape(name: str) -> ShapeConfig:
    return SHAPE_BY_NAME[name]
