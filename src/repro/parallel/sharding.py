"""Logical-axis sharding rules (MaxText-style) for DP / FSDP / TP / EP / SP.

Models annotate params and activations with *logical* axis names; a
:class:`ShardingRules` object (active via :func:`use_sharding`) maps those
names onto physical mesh axes.  Outside a sharding context every
constraint is a no-op, so the same model code runs on 1 CPU device in
tests and sharded on a device mesh.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AxisName = Union[str, None]
LogicalAxes = Tuple[AxisName, ...]


# ---------------------------------------------------------------------------
# Default logical -> physical rules
# ---------------------------------------------------------------------------

# Weight axes
#   "embed"    : the d_model dim of weights — FSDP (ZeRO-3) over the data axes
#   "heads_w"  : flattened (num_heads*head_dim) projection dim — TP
#   "mlp"      : FFN hidden dim — TP
#   "experts"  : MoE expert dim — EP
#   "vocab"    : embedding/logits vocab dim — TP
#   "layers"/"period" : scan-stacking dims — never sharded
# Activation axes
#   "batch"    : global batch — DP over (pod, data)
#   "seq"      : sequence — unsharded (or "model" when seq_parallel)
#   "heads"    : per-head activation dim — TP
#   "mlp_act"  : FFN hidden activation — TP
#   "kv_seq"   : KV-cache sequence dim — TP (flash-decode style)
#   "pages"    : paged-KV pool page dim — DP over `data` (serving mesh)
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_sp": None,          # becomes "model" when seq_parallel is on
    "act_embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp_act": "model",
    "experts_act": "model",
    "kv_seq": "model",
    "state": "model",        # SSM/mLSTM inner state dim
    # weights
    "embed": ("pod", "data"),
    "vocab": "model",
    "heads_w": "model",
    "mlp": "model",
    "experts": "model",
    "state_w": "model",
    "layers": None,
    "period": None,
    "conv": None,
    "pages": "data",
    None: None,
}

# serve — weights-stationary decode: pure TP over `model` (weights never
# gathered; per-token collectives are tiny activation all-reduces), the
# decode batch DP over (pod, data), and every KV-cache leaf over `data`:
# recurrent state on its batch dim, paged pools on the page dim (each
# data shard owns a private sub-pool its block tables address — see
# repro.serve.mesh).
SERVE_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq_sp": None,
    "embed": None,
    "vocab": "model",
    "heads_w": "model",
    "mlp": "model",
    "experts": "model",
    "state_w": "model",
    "kv_seq": "model",
    "kv_heads": "model",
    "pages": "data",
}


def serve_rules(**overrides) -> Dict[str, Any]:
    """The weights-stationary serving rule set (copy; override freely)."""
    rules = dict(SERVE_RULES)
    rules.update(overrides)
    return rules


@dataclass
class ShardingRules:
    mesh: Optional[Mesh] = None
    rules: Dict[str, Any] = field(default_factory=dict)

    def resolve(self, axes: Sequence[AxisName], shape=None) -> P:
        """Map logical axes -> PartitionSpec, dropping mesh axes that are
        absent from the mesh or that do not divide the dimension."""
        if self.mesh is None:
            return P()
        mesh_axes = dict(zip(self.mesh.axis_names, self.mesh.shape.values())) \
            if hasattr(self.mesh.shape, "values") else \
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        spec = []
        used = set()
        for i, name in enumerate(axes):
            phys = self.rules.get(name, DEFAULT_RULES.get(name))
            if phys is None:
                spec.append(None)
                continue
            if isinstance(phys, str):
                phys = (phys,)
            phys = tuple(a for a in phys if a in mesh_axes and a not in used)
            if not phys:
                spec.append(None)
                continue
            if shape is not None:
                total = 1
                for a in phys:
                    total *= mesh_axes[a]
                if shape[i] % total != 0:
                    # drop trailing axes until divisible
                    while phys and shape[i] % _prod(mesh_axes, phys) != 0:
                        phys = phys[:-1]
                    if not phys:
                        spec.append(None)
                        continue
            used.update(phys)
            spec.append(phys if len(phys) > 1 else phys[0])
        return P(*spec)

    def sharding(self, axes: Sequence[AxisName], shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.resolve(axes, shape))


def _prod(mesh_axes, phys):
    t = 1
    for a in phys:
        t *= mesh_axes[a]
    return t


# ---------------------------------------------------------------------------
# Context management
# ---------------------------------------------------------------------------

_local = threading.local()


def _current() -> Optional[ShardingRules]:
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], **rule_overrides):
    """Activate sharding rules for model code executed inside."""
    prev = _current()
    _local.ctx = ShardingRules(mesh, dict(rule_overrides)) if mesh is not None \
        else None
    try:
        yield _local.ctx
    finally:
        _local.ctx = prev


def current_mesh() -> Optional[Mesh]:
    """Mesh of the active sharding context (None outside one).

    Read at TRACE time: kernel dispatchers (``repro.kernels.ops``) use
    it to pick a shard_map lowering when model code is being traced
    under a serving mesh.
    """
    ctx = _current()
    return ctx.mesh if ctx is not None else None


def constrain(x: jax.Array, *axes: AxisName) -> jax.Array:
    """with_sharding_constraint under the active rules; no-op otherwise."""
    ctx = _current()
    if ctx is None or ctx.mesh is None:
        return x
    spec = ctx.resolve(axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def tree_shardings(mesh: Mesh, axes_tree, shape_tree=None,
                   **rule_overrides):
    """Map a tree of logical-axes tuples to a tree of NamedShardings.

    ``shape_tree`` (matching ShapeDtypeStructs or arrays) enables the
    divisibility check so non-divisible dims fall back to replication.
    """
    ctx = ShardingRules(mesh, dict(rule_overrides))

    if shape_tree is None:
        return jax.tree.map(
            lambda axes: ctx.sharding(axes),
            axes_tree, is_leaf=_is_axes_leaf)
    return jax.tree.map(
        lambda axes, arr: ctx.sharding(axes, arr.shape),
        axes_tree, shape_tree, is_leaf=_is_axes_leaf)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def seq_parallel_rules() -> Dict[str, Any]:
    """Rule overrides enabling sequence parallelism on the residual stream."""
    return {"seq_sp": "model"}
