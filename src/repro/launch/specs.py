"""ShapeDtypeStruct stand-ins (with logical axes) for params, train
state and the serving cache.

No device allocation happens here — array stand-ins come from
``jax.eval_shape``; the logical-axes trees (pure Python) are captured
by closure while tracing the init functions, so the FULL 398B configs
cost nothing to "initialize".
"""
from __future__ import annotations

import jax

from repro.configs.base import ModelConfig, OptimizerConfig
from repro.models import lm
from repro.train import steps as steps_lib


def _shapes_and_aux(fn):
    """eval_shape a function returning (arrays, python_aux)."""
    captured = {}

    def wrapper(*args):
        arrays, aux = fn(*args)
        captured["aux"] = aux
        return arrays

    shapes = jax.eval_shape(wrapper)
    return shapes, captured["aux"]


def param_specs(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical axes) — no allocation."""
    return _shapes_and_aux(
        lambda: lm.init_lm(cfg, jax.random.PRNGKey(0)))


def state_specs(cfg: ModelConfig, opt_cfg: OptimizerConfig):
    """Train state (params + opt state) specs and axes."""
    p_shapes, p_axes = param_specs(cfg)
    optimizer_init = steps_lib.opt_lib.make_optimizer(opt_cfg).init
    o_shapes = jax.eval_shape(optimizer_init, p_shapes)
    o_axes = steps_lib.opt_state_axes(opt_cfg, p_axes)
    return ({"params": p_shapes, "opt_state": o_shapes},
            {"params": p_axes, "opt_state": o_axes})


def serve_cache_specs(cfg: ModelConfig, num_slots: int, num_pages: int,
                      block_size: int = 16):
    """PAGED serving-cache specs (ShapeDtypeStructs + logical axes).

    The stand-in for the live serving mesh's cache pytree: attention
    layers get ``(num_pages + 1, block_size, Hkv, D)`` pools (axes
    include ``"pages"``, which the serve rules shard over ``data``),
    recurrent layers per-slot state rows (``"batch"`` over ``data``).
    Lets capacity studies resolve the mesh placement of any
    (arch x pool) cell without allocating a byte — the same axes the
    runtime (:mod:`repro.serve.mesh`) places the real pools with.
    """
    return _shapes_and_aux(
        lambda: lm.init_cache(cfg, num_slots,
                              pages=(num_pages, block_size)))

